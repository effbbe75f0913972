"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``)
call ``enable_compile_cache()`` first thing in ``main``, never at import:
a process that compiled a program once then finds it again on its next
start, which on a TPU saves most of a cold run's set-up time.
"""
from __future__ import annotations

import os

import jax

# fixed, inside the checkout (and listed in .gitignore): the directory is
# part of what a later process must match to find an entry, so it is never
# built from a temp name, a pid or the time
CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing is set here; otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
