"""Epsilon selection's share of the build: ``index.t_select_eps``, in
seconds."""


def read(ctx):
    return ctx.select_eps_s if ctx.build_s else None
