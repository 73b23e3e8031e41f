"""state_rows_per_step, under a name of its own for the cells whose states are
a state-space layer's: the slots whose state a decode step rewrote in such a
layer (the `ssd_step` calls' grid steps), mean over the decode steps that
started in the window. Near `max_slots` at full occupancy; equal to the live
rows a step if an empty slot costs nothing."""
from metrics._state_steps import rows_a_step


def read(ctx):
    return rows_a_step(ctx)
