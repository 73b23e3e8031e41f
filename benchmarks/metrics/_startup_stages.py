"""What the readers of a start's stages share: LLMServer.stats()["startup"] with, beside the stamps and durations
that `_startup.startup` holds it to, `ctor_began` (the constructor's first statement, on the stamps' clock) and
`stages`: what JAX reported of tracing (`trace_s`), lowering (`lower_s`) and its backend (`backend_s`: a compile, or
the compile cache's read and its load; `misses` counts the executables compiled) `before` `init_began`, inside
`engine_init` and inside `warmup` (the program's accel/device.compile_stages, read at those three points). A
program without them (the parent of the PR that added them) reads as None: the seven come in together or not at
all."""
from metrics._startup import startup

STAGES = ("trace_s", "lower_s", "backend_s")


def staged(ctx):
    s = startup(ctx)
    return s if s and s.get("stages") and s.get("ctor_began") is not None else None
