"""The pages a grid step of the paged kernel holds, one layer that keeps every
token: `live_pages` over `grid_steps`, over the decode blocks that started in
the window. 1.0 is a page a step; the kernel's page group (8 in the serve
cells) is the most it can read, and a batch of short sequences reads their
pages each. A program whose step records lack `grid_steps` (before page
groups) reads as None."""
from metrics._window_steps import decode_steps_in_window


def read(ctx):
    steps = decode_steps_in_window(ctx, "live_pages", "grid_steps")
    grid = sum(s["grid_steps"] for s in steps) if steps else 0
    return sum(s["live_pages"] for s in steps) / grid if grid else None
