"""The request's prefill program enqueued to its first token on the host: the
decode block in flight in front of the program, the program, the fetch. The
device's part of engine_prefill_p50_ms (what letting a prefill overtake the
block would cut); requests admitted in the window. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "prefill_enqueued", "first_token", within="admitted")
