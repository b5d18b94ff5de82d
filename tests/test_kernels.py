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
                                             ("_bwd_kernel", 5)])
def test_flash_products_take_the_input_dtype(kernel, products, dtype):
    """No float32 operand reaches a product of a bf16 call, and no
    float32 call is demoted: the dtype a call multiplies in is the one
    its inputs arrive in. The backward is one kernel of five products:
    the scores, `dp`, and the three gradients from one `p` and `ds`."""
    found = _kernel_dot_operands(dtype)
    assert sorted(found) == ["_bwd_kernel", "_fwd_kernel"]
    operands = found[kernel]
    assert len(operands) == products
    assert all(pair == (dtype, dtype) for pair in operands), operands


# ------------------------------------- the fused backward (PR 36)

# (seq, query heads, key/value heads, d_qk, d_v): latent attention's
# unequal widths and LFM2's four query heads over one key/value head of
# 64; causal, and a tail the wrapper pads (600 -> 640: five 128 blocks)
FUSED_CASES = {"widths_192_128": (600, 2, 2, 192, 128),
               "grouped_4_over_1_of_64": (600, 4, 1, 64, 64)}


def _grouped_reference(q, k, v, causal=True):
    group = q.shape[2] // k.shape[2]
    return _full_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2), causal=causal)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", FUSED_CASES)
def test_fused_backward_parity(case, dtype):
    """dq, dk, dv of the one backward kernel against `_full_attention`
    on the same rounded inputs, at the tolerances of the bf16 and the
    float32 cases above."""
    s, h, h_kv, d, d_v = FUSED_CASES[case]
    q, k, v = (jnp.asarray(rng.randn(1, s, n, w).astype("float32"), dtype)
               for n, w in ((h, d), (h_kv, d), (h_kv, d_v)))
    w = jnp.asarray(rng.randn(1, s, h, d_v).astype("float32"))

    def flash(q, k, v):
        return flash_attention_bshd(q, k, v, causal=True, interpret=True)

    got = jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_grouped_reference(*a) * w),
                    argnums=(0, 1, 2))(*(x.astype(jnp.float32)
                                         for x in (q, k, v)))
    for name, x, g, r in zip(("dq", "dk", "dv"), (q, k, v), got, want):
        assert g.dtype == dtype and g.shape == x.shape, name
        if dtype == jnp.bfloat16:
            assert _worst(g, r) < 1.5e-2, name
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-4, atol=1e-4, err_msg=name)


def _poisoned(q, k, v, w, causal=True):
    """The gradients with every buffer the kernels do not write holding
    NaN, as the chip leaves VMEM scratch and output blocks holding
    whatever was there (the interpreter's zeros hide that)."""
    from jax.experimental.pallas import tpu as pltpu

    poison = pltpu.InterpretParams(uninitialized_memory="nan")
    return jax.grad(
        lambda *a: jnp.sum(flash_attention_bshd(
            *a, causal=causal, interpret=poison) * w),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("heads,kv_heads,causal",
                         [(2, 2, True), (4, 1, True), (4, 2, False)],
                         ids=["equal_heads", "grouped", "grouped_no_mask"])
def test_fused_backward_initialises_what_it_adds_into(heads, kv_heads,
                                                      causal):
    """The float32 `dq` of a query head (resident across its key blocks)
    and a group's `dk`, `dv` (resident across its query heads) are
    written by their first visit, not assumed zero."""
    s = 300  # padded to 384: three blocks, the last with a padded tail
    q = jnp.asarray(rng.randn(1, s, heads, 32).astype("float32"))
    k, v = (jnp.asarray(rng.randn(1, s, kv_heads, 32).astype("float32"))
            for _ in range(2))
    w = jnp.asarray(rng.randn(1, s, heads, 32).astype("float32"))
    got = _poisoned(q, k, v, w, causal)
    want = jax.grad(lambda *a: jnp.sum(_grouped_reference(*a, causal) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_backward_writes_the_rows_few_blocks_reach():
    """Causal, three blocks: the first query block meets one key block
    and the last key block one query block. Their rows are written all
    the same, and rows that nothing flows into read exactly zero: the
    first query block's `dq` with no cotangent on it, the last key
    block's `dk` and `dv` with none on the last query block."""
    s, block = 384, 128
    q, k, v = _mk(1, s, 2, 32)
    w = jnp.asarray(rng.randn(1, s, 2, 32).astype("float32"))
    rows = jnp.arange(s)[None, :, None, None]
    dq, _dk, _dv = _poisoned(q, k, v, jnp.where(rows < block, 0.0, w))
    assert not np.asarray(dq[:, :block]).any()
    assert np.isfinite(np.asarray(dq)).all() and np.asarray(dq).any()
    _dq, dk, dv = _poisoned(q, k, v, jnp.where(rows >= s - block, 0.0, w))
    assert not np.asarray(dk[:, -block:]).any()
    assert not np.asarray(dv[:, -block:]).any()
    for g in (dk, dv):
        assert np.isfinite(np.asarray(g)).all() and np.asarray(g).any()
