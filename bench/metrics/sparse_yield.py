"""Share of the rows the sparse engine saw that it certified:
(``n_sparse_engine_total`` - ``n_uncertified``) / ``n_sparse_engine_total``,
in percent.  Nothing to read when the sparse engine saw no row."""


def read(ctx):
    rows = [c for c in ctx.calls if c.stats is not None]
    seen = sum(c.stats.n_sparse_engine_total for c in rows)
    if not seen:
        return None
    unc = sum(c.stats.n_uncertified for c in rows)
    return 100.0 * (seen - unc) / seen
