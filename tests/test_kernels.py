"""Pallas kernel tests (interpret mode on CPU; compiled path covered by the
on-TPU bench). Reference model: operators/fused/ unit tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.flash_attention import flash_attention_bshd
from paddle_tpu.parallel.ring_attention import _full_attention

rng = np.random.RandomState(4)


def _mk(b, s, h, d):
    return (jnp.asarray(rng.randn(b, s, h, d).astype("float32")),
            jnp.asarray(rng.randn(b, s, h, d).astype("float32")),
            jnp.asarray(rng.randn(b, s, h, d).astype("float32")))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [128, 384, 200])
def test_flash_forward(causal, s):
    q, k, v = _mk(2, s, 2, 64)
    out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
    ref = _full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward(causal):
    q, k, v = _mk(1, 256, 2, 32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=causal,
                                            interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_full_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _mk(1, 256, 2, 64)
    out = flash_attention_bshd(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                               v.astype(jnp.bfloat16), causal=True,
                               interpret=True)
    ref = _full_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_flash_cross_attention_lengths():
    q = jnp.asarray(rng.randn(1, 128, 2, 32).astype("float32"))
    k = jnp.asarray(rng.randn(1, 320, 2, 32).astype("float32"))
    v = jnp.asarray(rng.randn(1, 320, 2, 32).astype("float32"))
    out = flash_attention_bshd(q, k, v, interpret=True)
    ref = _full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- bf16 calls (PR 26)

# (s_q, s_k, head_dim, causal): the cells' family (head_dim 128, causal,
# whole 512 blocks), BERT's (64, not causal), a padded tail on each block
# size, and a cross-attention length
BF16_CASES = [
    (1024, 1024, 128, True),
    (512, 512, 64, False),
    (600, 600, 128, True),
    (700, 700, 64, False),
    (256, 640, 64, False),
    (384, 1000, 128, False),
]


def _low(b, s, h, d):
    return jnp.asarray(rng.randn(b, s, h, d).astype("float32"), jnp.bfloat16)


def _worst(got, want):
    """Largest error over the reference's largest value: bf16 rounds
    the results themselves to 2^-9 of their size."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize(
    "s_q,s_k,d,causal", BF16_CASES,
    ids=[f"q{a}_k{b}_d{c}{'_causal' if e else ''}"
         for a, b, c, e in BF16_CASES])
def test_flash_bf16_parity(s_q, s_k, d, causal, direction):
    """bf16 operands on every product, float32 everywhere else: against
    the float32 reference on the same bf16-rounded inputs."""
    q, k, v = _low(1, s_q, 2, d), _low(1, s_k, 2, d), _low(1, s_k, 2, d)
    w = jnp.asarray(rng.randn(1, s_q, 2, d).astype("float32"))
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))

    def flash(q, k, v):
        return flash_attention_bshd(q, k, v, causal=causal, interpret=True)

    def ref(q, k, v):
        return _full_attention(q, k, v, causal=causal)

    if direction == "forward":
        out = flash(q, k, v)
        assert out.dtype == jnp.bfloat16 and out.shape == q.shape
        assert _worst(out, ref(q32, k32, v32)) < 1e-2
        return
    got = jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref(*a) * w),
                    argnums=(0, 1, 2))(q32, k32, v32)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == jnp.bfloat16, name
        assert _worst(g, r) < 1.5e-2, name


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _kernel_dot_operands(dtype):
    """{kernel name: [(lhs dtype, rhs dtype) of each dot_general]} over
    the pallas_calls of one causal forward + backward."""
    q = jnp.zeros((1, 256, 2, 64), dtype)

    def loss(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True, interpret=True
                                    ).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    found = {}
    for eqn in _walk(jaxpr.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kernel = eqn.params["jaxpr"]
        found[kernel.debug_info.func_name] = [
            tuple(v.aval.dtype for v in e.invars) for e in _walk(kernel)
            if e.primitive.name == "dot_general"]
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("kernel,products", [("_fwd_kernel", 2),
                                             ("_bwd_dq_kernel", 3),
                                             ("_bwd_dkv_kernel", 4)])
def test_flash_products_take_the_input_dtype(kernel, products, dtype):
    """No float32 operand reaches a product of a bf16 call, and no
    float32 call is demoted: the dtype a call multiplies in is the one
    its inputs arrive in."""
    operands = _kernel_dot_operands(dtype)[kernel]
    assert len(operands) == products
    assert all(pair == (dtype, dtype) for pair in operands), operands
