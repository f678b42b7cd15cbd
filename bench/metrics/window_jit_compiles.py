"""Programs that JAX compiled, or loaded from its compile cache, inside
the measured window: every jitted function the program ran at a shape the
process had not run before, its engines included.  Should be 0; the
engines' own counter (``window_compiles``) does not see the helper programs
that data-dependent shapes bring."""


def read(ctx):
    return ctx.window_programs
