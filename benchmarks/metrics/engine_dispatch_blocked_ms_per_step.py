"""Milliseconds of an engine step in which the stepping thread was inside a
host phase and not on the CPU: over every phase but the two fetches, wall
seconds less the thread's CPU seconds. With the device a block ahead that is
the host's calls waiting for room in the device's queue of about 32 programs
and, beyond the name, whatever else kept the thread off the CPU inside those
phases: the GIL, a core given to another thread. Mean over the steps that
started in the window. engine_host_ms_per_step is this plus the same phases'
CPU milliseconds.

A difference of two clocks: where the host's kernel accounts CPU time by the
tick (10 ms on the chip's host), a phase's CPU reads a tick or two over or
under its wall time and only the window's mean says anything. A value around
0 (a millisecond either way in a cell of short steps) is that scatter, not a
wait, and it may come out negative."""
from metrics._step_cpu import steps_with_cpu
from metrics.engine_host_ms_per_step import WAITS


def read(ctx):
    steps = steps_with_cpu(ctx)
    if not steps:
        return None
    blocked = [sum(s - step["phase_cpu_s"][phase] for phase, s in step["phase_s"].items() if phase not in WAITS)
               for step in steps]
    return sum(blocked) / len(blocked) * 1e3
