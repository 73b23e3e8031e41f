"""A slot given to the request to its prefill program enqueued (the call that
dispatches it has returned): the padded arrays built, the groups ahead of it
in the step, any dispatch that waited for room in the device's queue. The
host's part of engine_prefill_p50_ms; requests admitted in the window, an
exact prefix hit (no prefill) left out. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "admitted", "prefill_enqueued", within="admitted")
