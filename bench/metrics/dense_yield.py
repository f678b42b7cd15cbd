"""Share of the rows run through the dense engine that it answered
(``source == 0``), in percent.  Nothing to read when no row ran there."""


def read(ctx):
    rows = [c for c in ctx.calls if c.stats is not None]
    sent = sum(sum(c.stats.batch_sizes) for c in rows)
    if not sent:
        return None
    return 100.0 * sum(int((c.source == 0).sum()) for c in rows) / sent
