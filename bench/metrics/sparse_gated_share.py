"""Share of the window's queries that the sparse engine's capacity gate
sent straight to the brute lane (``stats.n_sparse_gated`` over queries),
in percent: rows whose finest-level 3^m neighbourhood holds more than
the sparse budget, which the engine could never certify.  Nothing to
read when the program keeps no such counter."""


def read(ctx):
    rows = [c for c in ctx.calls if hasattr(c.stats, "n_sparse_gated")]
    n_q = sum(c.n_queries for c in rows)
    if not n_q:
        return None
    return 100.0 * sum(c.stats.n_sparse_gated for c in rows) / n_q
