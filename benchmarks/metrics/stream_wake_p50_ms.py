"""The first event on the request's stream to generate_stream awake and about
to yield its first frame: the consumer thread's wake-up. Requests whose first
event was emitted in the window. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "first_emitted", "first_yielded", within="first_emitted")
