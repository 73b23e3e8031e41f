"""A slot given to the request's first token on the host (building and
enqueueing its prefill group, the groups ahead of it, the fetch); requests
admitted in the window. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "admitted", "first_token", within="admitted")
