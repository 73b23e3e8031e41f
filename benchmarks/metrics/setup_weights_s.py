"""Of setup_s, the replica's weights and pools: the parameters fetched and the
engine built (weights made on the device or resharded, the KV pools)."""
from metrics._startup import startup


def read(ctx):
    s = startup(ctx)
    return s["fetch_params_s"] + s["engine_init_s"] if s else None
