"""Engine arrival (add_request) to the moment a step gave the request a slot,
from the program's own lifecycle record; requests admitted in the window. The
program's twin of sched_queue_wait_p50_ms, which the benchmark's wrapper takes
at the start of the admitting step. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "arrived", "admitted", within="admitted")
