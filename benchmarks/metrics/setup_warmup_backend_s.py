"""Of setup_warmup_s, the backend: on a warm start the compile cache's reads and their loads, on a cold one the compiles
(`stages["warmup"]["backend_s"]` of the replica's start-up record)."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    return s["stages"]["warmup"]["backend_s"] if s else None
