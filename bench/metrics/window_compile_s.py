"""Seconds that the window's calls spent compiling programs, or loading
them from JAX's compile cache, helpers and engines alike: the sum of
``stats.t_compile`` (the program's own tally, by JAX's compile event,
over each ``index.query`` call).  0 where ``window_jit_compiles`` is 0;
``stats.compiles_by_span`` names the span that asked for each program.
Nothing to read when the program keeps no such tally."""


def read(ctx):
    stats = [c.stats for c in ctx.calls if hasattr(c.stats, "t_compile")]
    if not stats:
        return None
    return sum(s.t_compile for s in stats)
