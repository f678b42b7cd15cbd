"""Wall time of ``KNNIndex.build`` in this run (compile cache warm after
the first run): REORDER, epsilon selection, grid and pyramid."""


def read(ctx):
    return ctx.build_s
