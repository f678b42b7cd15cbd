"""Engine compilations inside the measured window: the sum of
``stats.n_engine_compiles`` over its calls.  Should be 0."""


def read(ctx):
    rows = [c for c in ctx.calls if c.stats is not None]
    if not rows:
        return None
    return sum(c.stats.n_engine_compiles for c in rows)
