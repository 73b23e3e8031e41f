"""Executables the replica's process compiled, and did not read from the compile cache, from its start to the end
of warm-up (`misses` before `init_began`, inside `engine_init` and inside `warmup`). JAX caches only what took a
second to compile, so a warm start still compiles its small programs and reads the same count run after run; a run
that reads more lost entries of the cache, or is a cold one."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    return float(sum(part["misses"] for part in s["stages"].values())) if s else None
