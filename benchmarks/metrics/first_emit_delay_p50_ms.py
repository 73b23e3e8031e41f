"""The first token on the host (the engine's first_token_at) to the moment
LLMServer._loop has put its event on the request's stream: what the rest of
the step (the decode block dispatched and fetched after the prefill groups)
holds the token back. Requests whose first token fell in the window. Median."""
from metrics._program_trace import request_gap_p50_ms


def read(ctx):
    return request_gap_p50_ms(ctx, "first_token", "first_emitted", within="first_token")
