"""engine_host_ms_per_step, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: the stepping thread's wall time a step outside its wait for the device. The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("engine_host_ms_per_step")
