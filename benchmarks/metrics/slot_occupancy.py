"""Slots holding a decoding request, over all slots, weighted by decode
steps, over the window."""


def read(ctx):
    w = ctx.r["window"]
    return 100.0 * w["slot_steps_active"] / w["slot_steps_total"] if w["slot_steps_total"] else None
