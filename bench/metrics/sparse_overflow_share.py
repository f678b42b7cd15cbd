"""Share of the rows the sparse engine saw that it left uncertified with
an overflowed candidate budget at the pyramid level they ended on
(``stats.n_sparse_overflow``, counted on the device, over
``stats.n_sparse_engine_total``), in percent.  With ``sparse_yield`` it
splits the uncertified rows by cause: the rest found their k-th distance
beyond the level's certified radius.  Nothing to read when the sparse
engine saw no row, or when the program does not count overflow."""


def read(ctx):
    stats = [c.stats for c in ctx.calls
             if hasattr(c.stats, "n_sparse_overflow")]
    seen = sum(s.n_sparse_engine_total for s in stats)
    if not seen:
        return None
    return 100.0 * sum(s.n_sparse_overflow for s in stats) / seen
