"""The slots whose convolution tail a decode step rewrote in a layer whose
whole state is that tail (`tail_rows` of the program's step records, counted
on the device once a step as `state_rows` is for a state, over the steps of
the decode blocks that started in the window). Near `max_slots` at full
occupancy; equal to the live rows a step if an empty slot costs nothing. A
program whose records lack the counter (the parent of the PR that brought the
kind, or a model without such layers) reads as None."""
from metrics._program_trace import in_window


def read(ctx):
    steps = [s for s in in_window(ctx, "steps", "t", "t") or [] if s.get("block")]
    if not steps or any("tail_rows" not in s for s in steps):
        return None
    return sum(s["tail_rows"] for s in steps) / sum(s["block"] for s in steps)
