"""Host seconds of an engine step outside its two blocking fetches: every
phase of LLMEngine.step but prefill_fetch and decode_fetch (admission, prefix
lookup, building and enqueueing prefill, the device mirrors' updates, decode
dispatch, emit, the resync after a retire). Mean over the steps that started
in the window."""
from metrics._program_trace import in_window

WAITS = ("prefill_fetch", "decode_fetch")


def read(ctx):
    steps = in_window(ctx, "steps", "t", "t")
    if not steps:
        return None
    host = [sum(s for phase, s in step["phase_s"].items() if phase not in WAITS) for step in steps]
    return sum(host) / len(host) * 1e3
