"""Programs the set-up built or loaded from the persistent compile cache
(``jax.monitoring`` compile requests): one per jitted function and shape
the warm-up rounds and the window's bucket shapes need.  A round whose
sampled clients bring a new bucket shape adds its local-training
programs here; a change that keeps the shapes steady lowers it."""


def read(ctx):
    return float(ctx.setup_programs)
