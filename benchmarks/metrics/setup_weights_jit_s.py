"""Of setup_weights_s, what JAX traced, lowered and handed its backend while the engine was built (the weights'
and pools' programs): the three stages' sum inside `engine_init`."""
from metrics._startup_stages import STAGES, staged


def read(ctx):
    s = staged(ctx)
    return sum(s["stages"]["engine_init"][k] for k in STAGES) if s else None
