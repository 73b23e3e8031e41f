"""The paged kernel's device time in the decode program, the softmax layers'
calls alone (full_attn_time_share's reading: paged_attn_time_share sums every
Mosaic call of the decode program, and here `ssd_step` is one), over the
device's busy time in the traced window, under a name of its own: the lists of
the readers it could share are held to their members by a test."""


def read(ctx):
    return ctx.same_as("full_attn_time_share")
