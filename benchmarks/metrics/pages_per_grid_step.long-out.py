"""pages_per_grid_step, under a name of its own: the lists of its `.backlog` twin
are held to their members by a test, and this cell cannot join them. The latent
kernel's walk: 1.0 while it took a page a step (and an empty slot a step), the
pages of a sequence's group since it walks `page_groups`."""


def read(ctx):
    return ctx.same_as("pages_per_grid_step")
