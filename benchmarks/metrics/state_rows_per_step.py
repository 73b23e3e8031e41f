"""The slots whose state a decode step rewrote in a delta layer, mean over the
decode steps that started in the window: the program's own count of the
`kda_step` calls' grid steps. Equal to the live rows a step if an empty slot
costs nothing; `max_slots` if every slot is rewritten whether it holds a
request or not."""
from metrics._state_steps import rows_a_step


def read(ctx):
    return rows_a_step(ctx)
