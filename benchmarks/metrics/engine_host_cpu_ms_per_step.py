"""CPU milliseconds of the thread that steps the engine, a step cycle: the
thread's CPU clock at the start of the window's last step less that at the
start of its first, over the cycles between them. It holds every phase's work
(the fetches' too: a fetch that waits takes none) and the serving loop between
two steps, and none of the waits that engine_host_ms_per_step has held since
the device runs a block ahead: what the host costs, whatever the device's
speed. A slower machine reads higher for the same work."""
from metrics._step_cpu import steps_with_cpu


def read(ctx):
    steps = steps_with_cpu(ctx)
    if not steps or len(steps) < 2:
        return None
    return (steps[-1]["cpu_t"] - steps[0]["cpu_t"]) / (len(steps) - 1) * 1e3
