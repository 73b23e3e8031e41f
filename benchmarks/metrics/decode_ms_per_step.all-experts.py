"""decode_ms_per_step, under a name of its own in the cell whose routed layers hold every expert: the
lists it could join are held to their members by tests a PR that adds a cell may not edit (PERF.md
section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("decode_ms_per_step")
