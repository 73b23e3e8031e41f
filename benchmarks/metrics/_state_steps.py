"""What the readers of the program's state counter share: the step records of
LLMServer.stats()["trace"] that started in the client's window and ran a
decode block, each with `block` (its steps) and, since the PR that added delta
layers, `state_rows` (the slots whose state a step rewrote, in each delta
layer alike, summed over the block's steps: the `kda_step` calls' grids). A
program whose records lack the counter (the parent of that PR, or a model
without such layers) reads as None."""
from metrics._program_trace import in_window


def rows_a_step(ctx):
    steps = [s for s in in_window(ctx, "steps", "t", "t") or [] if s.get("block")]
    if not steps or any("state_rows" not in s for s in steps):
        return None
    return sum(s["state_rows"] for s in steps) / sum(s["block"] for s in steps)
