"""Set-up time: from process start to the measured window (loading JAX,
making the data, ``KNNIndex.build``, warm-up and any compilation)."""


def read(ctx):
    return ctx.setup_s
