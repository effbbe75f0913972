import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def shrink(cell: dict) -> dict:
    """The cell cut to the size its family gives for the CPU."""
    import harness
    family = harness.family_of(cell["config"])
    if not hasattr(family, "shrink"):
        raise AttributeError(f"the family module {family.__file__} has no "
                             "shrink(cell) for the benchmark's tests")
    return family.shrink(cell)


def token_cell(pins=None) -> dict:
    """The token family's cell at its CPU size."""
    import cells
    return cells.token_cell(pins, shrink)


@pytest.fixture(scope="module")
def families():
    """The token family beside the benchmark's, for this module."""
    import cells
    with cells.token_family():
        yield


@pytest.fixture(scope="module")
def own_cache(tmp_path_factory):
    """The program's compile cache in a directory of the tests' own."""
    from repro.launch import compile_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        mp.setattr(compile_cache, "CACHE_DIR",
                   str(tmp_path_factory.mktemp("jax")))
        yield
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
