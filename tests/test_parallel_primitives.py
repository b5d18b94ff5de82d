"""Ring attention / Ulysses / SPMD pipeline on the 8-device CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu.distributed as dist
from paddle_tpu.parallel import ring_attention, ulysses_attention, spmd_pipeline
from paddle_tpu.parallel.ring_attention import _full_attention

rng = np.random.RandomState(0)


def _ref_attention(q, k, v, causal):
    return np.asarray(_full_attention(jnp.asarray(q), jnp.asarray(k),
                                      jnp.asarray(v), causal=causal))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    mesh = dist.make_mesh({"sp": 4})
    b, s, h, d = 2, 32, 4, 8  # s sharded 4-way -> 8 per device
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, h, d).astype("float32")
    v = rng.randn(b, s, h, d).astype("float32")

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))
    out = np.asarray(fn(q, k, v))
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_match():
    mesh = dist.make_mesh({"sp": 4})
    b, s, h, d = 1, 16, 2, 4
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, h, d).astype("float32")
    v = rng.randn(b, s, h, d).astype("float32")

    def ring_loss(q, k, v):
        out = jax.shard_map(
            lambda a, b_, c: ring_attention(a, b_, c, "sp", causal=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"))(q, k, v)
        return jnp.sum(out ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal=True) ** 2)

    # one jitted program each: an eager shard_map grad dispatches (and
    # compiles) op by op across the mesh, ~10x the wall time
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    mesh = dist.make_mesh({"sp": 4})
    b, s, h, d = 2, 32, 8, 4  # heads 8 divisible by sp=4
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, h, d).astype("float32")
    v = rng.randn(b, s, h, d).astype("float32")

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp")))
    out = np.asarray(fn(q, k, v))
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_spmd_pipeline_matches_sequential():
    mesh = dist.make_mesh({"pp": 4})
    n_stages, n_micro, mb, dim = 4, 8, 2, 16
    w = rng.randn(n_stages, dim, dim).astype("float32") * 0.1
    b = rng.randn(n_stages, dim).astype("float32") * 0.1
    x = rng.randn(n_micro, mb, dim).astype("float32")

    def stage_fn(params, h):
        wi, bi = params
        return jnp.tanh(h @ wi + bi)

    fn = jax.jit(jax.shard_map(
        lambda p, xx: spmd_pipeline(stage_fn, p, xx, "pp"),
        mesh=mesh, in_specs=((P("pp"), P("pp")), P(None)),
        out_specs=P(None)))
    out = np.asarray(fn((w, b), x))

    ref = x.copy()
    for s in range(n_stages):
        ref = np.tanh(ref @ w[s] + b[s])
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_spmd_pipeline_backward_trains():
    mesh = dist.make_mesh({"pp": 4})
    n_stages, n_micro, mb, dim = 4, 4, 2, 8
    w = (rng.randn(n_stages, dim, dim) * 0.3).astype("float32")
    x = rng.randn(n_micro, mb, dim).astype("float32")
    tgt = rng.randn(n_micro, mb, dim).astype("float32")

    def stage_fn(wi, h):
        return jnp.tanh(h @ wi)

    def loss_fn(w):
        out = jax.shard_map(
            lambda p, xx: spmd_pipeline(stage_fn, p, xx, "pp"),
            mesh=mesh, in_specs=(P("pp"), P(None)), out_specs=P(None))(w, x)
        return jnp.mean((out - tgt) ** 2)

    # gradient vs sequential reference
    def ref_loss(w):
        h = x
        for s in range(n_stages):
            h = jnp.tanh(h @ w[s])
        return jnp.mean((h - tgt) ** 2)

    g_pp = np.asarray(jax.jit(jax.grad(loss_fn))(w))
    g_ref = np.asarray(jax.jit(jax.grad(ref_loss))(w))
    np.testing.assert_allclose(g_pp, g_ref, rtol=1e-4, atol=1e-5)

    # and a few SGD steps reduce the loss inside one jit
    @jax.jit
    def train(w):
        for _ in range(5):
            l, g = jax.value_and_grad(loss_fn)(w)
            w = w - 0.5 * g
        return w, l

    w2, l_final = train(w)
    assert float(l_final) < float(ref_loss(w))
