"""pages_per_grid_step, under a name of its own: in these cells it moves another
end-to-end metric than in the cell where it has its plain name."""


def read(ctx):
    return ctx.same_as("pages_per_grid_step")
