"""Per-kernel validation: shape/dtype sweeps, kernel (interpret mode) vs
pure-jnp oracle (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.usefixtures("force_pallas")


@pytest.fixture()
def force_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")


# ---------------------------------------------------------------- kd_loss
@pytest.mark.parametrize("K,B,V", [(1, 4, 128), (4, 8, 1000), (8, 4, 257),
                                   (2, 16, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ensemble_softmax_sweep(K, B, V, dtype):
    from repro.kernels.kd_loss import ops, ref
    key = jax.random.PRNGKey(K * B + V)
    tl = (jax.random.normal(key, (K, B, V)) * 3).astype(dtype)
    got = ops.ensemble_softmax(tl, 4.0)
    want = ref.ensemble_softmax_ref(tl, 4.0)
    tol = 1e-6 if dtype == jnp.float32 else 2e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


@pytest.mark.parametrize("B,V,temp", [(4, 128, 1.0), (8, 1000, 4.0),
                                      (4, 257, 2.0), (16, 4096, 4.0)])
def test_kd_loss_and_grad_sweep(B, V, temp):
    from repro.kernels.kd_loss import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(B + V), 2)
    sl = jax.random.normal(ks[0], (B, V)) * 3
    tp = jax.nn.softmax(jax.random.normal(ks[1], (B, V)) * 2, -1)
    np.testing.assert_allclose(float(ops.kd_loss(sl, tp, temp)),
                               float(ref.kd_loss_ref(sl, tp, temp)), rtol=1e-4)
    g_got = jax.grad(lambda s: ops.kd_loss(s, tp, temp))(sl)
    g_want = jax.grad(lambda s: ref.kd_loss_ref(s, tp, temp))(sl)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               atol=1e-6)


def test_kd_loss_zero_when_student_equals_teacher():
    from repro.kernels.kd_loss import ops
    sl = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
    tp = jax.nn.softmax(sl / 4.0, -1)
    assert float(ops.kd_loss(sl, tp, 4.0)) < 1e-5


@pytest.mark.parametrize("M,nB,B,V", [(2, 3, 4, 128), (8, 2, 4, 257)])
def test_ensemble_softmax_many_matches_per_batch(M, nB, B, V):
    """The KD pipeline's whole-set precompute (merged batch dims, one
    kernel sweep) must equal per-batch ensemble_softmax calls."""
    from repro.kernels.kd_loss import ops
    tl = jax.random.normal(jax.random.PRNGKey(M + V), (M, nB, B, V)) * 3
    got = ops.ensemble_softmax_many(tl, 4.0)
    assert got.shape == (nB, B, V)
    for i in range(nB):
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(ops.ensemble_softmax(tl[:, i], 4.0)),
            atol=1e-6)


# ---------------------------------------------------------------- weight_avg
@pytest.mark.parametrize("N,D", [(2, 128), (8, 1000), (16, 65536), (3, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_weight_avg_sweep(N, D, dtype):
    from repro.kernels.weight_avg import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(N * D), 2)
    x = jax.random.normal(ks[0], (N, D)).astype(dtype)
    w = jax.random.uniform(ks[1], (N,)) + 0.1
    got = ops.weighted_average(x, w)
    want = ref.weighted_average_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_weight_avg_uniform_weights_is_mean():
    from repro.kernels.weight_avg import ops
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 300))
    got = ops.weighted_average(x, jnp.ones((4,)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(x.mean(0)), atol=1e-5)


@pytest.mark.parametrize("grouped", [False, True])
def test_weight_avg_pytree_compiles_once(grouped):
    """A second call at the same shapes reuses the compiled program: a
    pallas_call made outside jit compiled again on every call, once per
    model leaf per round."""
    from repro.analysis import TraceGuard
    from repro.kernels.weight_avg import ops
    lead = (2, 3) if grouped else (3,)
    tree = {"a": jnp.ones(lead + (5,)), "b": jnp.ones(lead + (7, 4))}
    w = jnp.arange(1.0, 1.0 + np.prod(lead)).reshape(lead)
    fn = (ops.group_weighted_average_pytree if grouped
          else ops.weighted_average_pytree)
    jax.block_until_ready(fn(tree, w))
    with TraceGuard("weight_avg pytree") as guard:
        jax.block_until_ready(fn(tree, w))
    guard.assert_steady_state()


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,S,H,Hkv,dh", [
    (2, 256, 4, 2, 64), (1, 128, 8, 1, 32), (2, 256, 4, 4, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(B, S, H, Hkv, dh, causal, window):
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(B * S + H + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    out = ops.flash_attention(q, k, v, causal, window)
    G = H // Hkv
    kb = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vb = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    want = ref.attention_ref(q.transpose(0, 2, 1, 3), kb, vb,
                             causal=causal, window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    out = ops.flash_attention(q, k, v, True, 0)
    want = ref.attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want.transpose(0, 2, 1, 3), np.float32),
                               atol=tol)


@pytest.mark.parametrize("B,S,H,Hkv,dh,clen", [
    (2, 1024, 4, 2, 64, 700), (1, 512, 8, 1, 32, 512), (2, 512, 4, 4, 128, 1),
])
def test_flash_decode_sweep(B, S, H, Hkv, dh, clen):
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(S + clen), 3)
    q = jax.random.normal(ks[0], (B, 1, H, dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, dh))
    out = ops.flash_decode(q, k, v, jnp.int32(clen))
    G = H // Hkv
    kb = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vb = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    want = ref.decode_attention_ref(q.reshape(B, H, dh), kb, vb, clen)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want), atol=2e-5)


def test_flash_attention_grads_match_ref():
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))

    def f_kernel(q, k, v):
        return (ops.flash_attention(q, k, v, True, 0) ** 2).sum()

    def f_ref(q, k, v):
        return (ref.attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3),
                                  causal=True).transpose(0, 2, 1, 3) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
