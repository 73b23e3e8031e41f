"""engine_host_cpu_ms_per_step, under a name of its own: in these cells it moves another
end-to-end metric than in the cell where it has its plain name."""


def read(ctx):
    return ctx.same_as("engine_host_cpu_ms_per_step")
