"""The page steps a window layer's decode call walks over those a layer that
keeps every token walks, over the decode blocks that started in the window: how
far the window cuts a layer's walk. Near 100 would mean contexts too short to
show the mechanism."""
from metrics._window_steps import decode_steps_in_window


def read(ctx):
    steps = decode_steps_in_window(ctx, "window_pages", "live_pages")
    full = sum(s["live_pages"] for s in steps) if steps else 0
    return 100.0 * sum(s["window_pages"] for s in steps) / full if full else None
