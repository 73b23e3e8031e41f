"""Of setup_warmup_s, lowering: jaxpr to MLIR, each pallas_call through Mosaic's lowering
(`stages["warmup"]["lower_s"]` of the replica's start-up record)."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    return s["stages"]["warmup"]["lower_s"] if s else None
