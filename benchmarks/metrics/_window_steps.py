"""What the readers of the program's window counters share: the step records
of LLMServer.stats()["trace"] that started in the client's window and ran a
decode block, each with `block` (its steps), `live_pages` (the page steps ONE
layer that keeps every token walks in the block) and, since the PR that added
window layers, `window_pages` (the page steps one window layer walks) and
`window_tokens` (the positions one window layer attends: at most the window a
slot and step). A program whose records lack a counter (the parent of that
PR, or a model without a window) reads as None."""
from metrics._program_trace import in_window


def decode_steps_in_window(ctx, *counters):
    steps = [s for s in in_window(ctx, "steps", "t", "t") or [] if s.get("block")]
    if not steps or any(c not in s for s in steps for c in counters):
        return None
    return steps
