"""Share of the rows run through the dense engine that it failed for
candidate-budget overflow (``stats.n_dense_overflow``, counted on the
device, over ``sum(stats.batch_sizes)``), in percent.  With
``dense_yield`` it splits the engine's failures by cause: the rest of the
failed rows found fewer than k neighbours within ε.  Nothing to read when
no row ran there, or when the program does not count overflow."""


def read(ctx):
    stats = [c.stats for c in ctx.calls
             if hasattr(c.stats, "n_dense_overflow")]
    sent = sum(sum(s.batch_sizes) for s in stats)
    if not sent:
        return None
    return 100.0 * sum(s.n_dense_overflow for s in stats) / sent
