"""The least time the chip could take for the softmax layers' paged calls in the
traced decode steps over the time they took (full_attn_roofline's reading, from
this architecture's `full_decode_needs`: a head's own 64 columns, so the lane
tile a row is padded to shows as a share under a half), under a name of its
own: the lists of the readers it could share are held to their members by a
test."""


def read(ctx):
    return ctx.same_as("full_attn_roofline")
