"""kv_pages_reserved_share, under a name of its own: in these cells it moves another
end-to-end metric than in the cell where it has its plain name."""


def read(ctx):
    return ctx.same_as("kv_pages_reserved_share")
