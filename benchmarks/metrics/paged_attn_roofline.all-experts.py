"""The least time the chip could take for the softmax layers' paged calls in the traced decode steps
over the time they took (full_attn_roofline's reading, from this architecture's `full_decode_needs`:
a head's own 64 columns, so the lane tile a row is padded to shows as a share under a half), under a
name of its own in the cell whose routed layers hold every expert: the lists it could join are held
to their members by tests a PR that adds a cell may not edit (PERF.md section 7 asks the next
benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("full_attn_roofline")
