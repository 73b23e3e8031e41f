"""Of setup_warmup_s, what no stage of JAX's holds: the warmed programs' first runs, their arrays and their
fetches (`warmup_s` less the warm-up's tracing, lowering and backend seconds; the four add up to setup_warmup_s)."""
from metrics._startup_stages import STAGES, staged


def read(ctx):
    s = staged(ctx)
    return s["warmup_s"] - sum(s["stages"]["warmup"][k] for k in STAGES) if s else None
