"""Host time the train loop spent waiting for its next batch (ray_tpu.data
shard -> prefetch_to_device), over the window."""


def read(ctx):
    w = ctx.r["worker"]
    return 100.0 * w["spans"]["data_wait"] / w["window_s"]
