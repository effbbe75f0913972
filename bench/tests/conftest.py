import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def shrink(cell: dict) -> dict:
    """A cell cut to a size the CPU runs in seconds: ResNet-8, ten
    clients of a few dozen images, three KD steps."""
    cell = copy.deepcopy(cell)
    cell["config"].update(depth=8, num_train=512, num_server=64,
                          distill_steps=3)
    pop = cell["mix"]["population"]
    if pop["partition"] == "dirichlet":
        pop.update(num_clients=10, alpha=1.0, min_shard=16)
    else:
        pop.update(num_clients=8)
    cell["mix"]["job"].update(server_batch=32, client_batch=16)
    return cell


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
