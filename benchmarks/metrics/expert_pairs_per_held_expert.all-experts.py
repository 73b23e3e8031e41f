"""expert_pairs_per_held_expert (routed (token, expert) pairs an expert receives in one routed layer of
one decode step; here all 64 experts are held, so this is the deployment's own load at this batch
and no fraction of it: 128 rows x 4 choices / 64 experts = 8), under a name of its own in the cell
whose routed layers hold every expert: the lists it could join are held to their members by tests a
PR that adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("expert_pairs_per_held_expert")
