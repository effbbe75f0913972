"""Programs built, or loaded from the persistent compile cache, during
the timed rounds (``jax.monitoring`` compile requests): the round
executor meeting a program the set-up did not warm."""


def read(ctx):
    return float(ctx.window_compiles)
