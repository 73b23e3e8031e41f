"""The paged kernel's device time in the decode program, the softmax layers' calls alone
(full_attn_time_share's reading: paged_attn_time_share sums every Mosaic call of the decode program,
and here the grouped matmul is one), over the device's busy time in the traced window, under a name
of its own in the cell whose routed layers hold every expert: the lists it could join are held to
their members by tests a PR that adds a cell may not edit (PERF.md section 7 asks the next benchmark
PR to fold the names)."""


def read(ctx):
    return ctx.same_as("full_attn_time_share")
