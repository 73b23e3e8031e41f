"""What the readers of the stepping thread's CPU clock share. Since the PR
that gave util/tracing.PhaseSpans a second clock, a step record of
LLMServer.stats()["trace"] holds beside `phase_s` (wall seconds by phase, on
time.monotonic()) `phase_cpu_s` (the same phases on time.thread_time(), which
stands still while the thread is blocked in a dispatch, a fetch, on the GIL or
off its core) and `cpu_t` (that clock as the step began). A program whose
records lack the fields (the parent of that PR) reads as None."""
from metrics._program_trace import in_window


def steps_with_cpu(ctx):
    """The step records that started in the client's window, oldest first;
    None without them or where one lacks the CPU clock."""
    steps = in_window(ctx, "steps", "t", "t")
    if not steps or any("cpu_t" not in s or "phase_cpu_s" not in s for s in steps):
        return None
    return steps
