"""Queries answered per second: every query of the window's calls, all of
which completed, over the window's wall time (first call's start to last
call's end).  Self-join rows count as queries."""


def read(ctx):
    if not ctx.calls:
        return None
    return sum(c.n_queries for c in ctx.calls) / ctx.window_s
