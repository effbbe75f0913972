"""The main path's Pallas kernels compile for a TPU v5e.

Each case compiles one kernel at the FedSDD rounds' shapes (ResNet-20:
10 classes, server batch 256, K=4 groups) or at an LM width the repo
supports, for a v5e chip that is described, not attached: the TPU
compiler refuses a block that breaks the (8, 128) tiling or a kernel
that needs more VMEM than it may use, neither of which interpret mode
on the CPU can see.  Nothing runs, so these say nothing about results
or times.

The topology is described only inside the fixtures below: describing it
loads the TPU library, which one process at a time may hold.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kd_loss import flash, kernel
from repro.kernels.weight_avg import kernel as wavg

TAU = 4.0
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


# name -> (kernel call, [(shape, dtype), ...])
CASES = {
    # Eq. 2 for K=4 groups of 5 clients over one 16K-column tile
    "weight_avg_multi": (
        lambda x, w: wavg.multi_weighted_average(x, w, interpret=False),
        [((4, 5, 16384), jnp.float32), ((4, 5), jnp.float32)]),
    # dense KD: 10 classes lane-padded to 128
    "ensemble_softmax": (
        lambda t: kernel.ensemble_softmax(t, TAU, interpret=False),
        [((4, 256, 128), jnp.float32)]),
    "kd_loss_fwd": (
        lambda s, t: kernel.kd_loss_fwd(s, t, TAU, interpret=False),
        [((256, 128), jnp.float32), ((256, 128), jnp.float32)]),
    "kd_loss_bwd": (
        lambda s, t, g: kernel.kd_loss_bwd(s, t, g, TAU, interpret=False),
        [((256, 128), jnp.float32), ((256, 128), jnp.float32),
         ((), jnp.float32)]),
    # flash KD on the bf16 mean-logit cache with its lse residual
    "flash_fwd_v10": (
        lambda s, t, l: flash.flash_kd_fwd(s, t, TAU, interpret=False,
                                           teacher_lse=l),
        [((256, 10), jnp.float32), ((256, 10), BF16), ((256,), jnp.float32)]),
    "flash_bwd_v10": (
        lambda s, t, a, b, g: flash.flash_kd_bwd(s, t, a, b, g, TAU,
                                                 interpret=False),
        [((256, 10), jnp.float32), ((256, 10), BF16), ((256,), jnp.float32),
         ((256,), jnp.float32), ((), jnp.float32)]),
    # an LM vocabulary (llava-next-mistral-7b), ragged against the tile
    "flash_fwd_v32000": (
        lambda s, t: flash.flash_kd_fwd(s, t, TAU, interpret=False),
        [((256, 32000), jnp.float32), ((256, 32000), BF16)]),
    # head-fused backward at qwen2.5-14b's width: the resident (B, D)
    # blocks are its VMEM risk
    "flash_head_bwd_qwen": (
        lambda h, w, t, a, b, g: flash.flash_kd_head_bwd(
            h, w, None, t, a, b, g, TAU, interpret=False),
        [((256, 5120), jnp.float32), ((5120, 152064), BF16),
         ((256, 152064), BF16), ((256,), jnp.float32), ((256,), jnp.float32),
         ((), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
