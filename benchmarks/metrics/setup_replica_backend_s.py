"""Of setup_before_replica_s, the replica's own part: from its constructor's first statement (`ctor_began`) to
`init_began`: the compile cache placed, the chip claimed (the backend started), the engine's modules imported."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    return s["init_began"] - s["ctor_began"] if s else None
