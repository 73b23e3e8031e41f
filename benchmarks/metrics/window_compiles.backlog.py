"""window_compiles, under a name of its own: in these cells it moves another
end-to-end metric than in the cells where it has its plain name."""


def read(ctx):
    return ctx.same_as("window_compiles")
