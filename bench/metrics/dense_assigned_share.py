"""Share of the window's queries that the density split assigned to the
dense engine (``stats.n_dense`` over queries), in percent."""


def read(ctx):
    rows = [c for c in ctx.calls if c.stats is not None]
    n_q = sum(c.n_queries for c in rows)
    if not n_q:
        return None
    return 100.0 * sum(c.stats.n_dense for c in rows) / n_q
