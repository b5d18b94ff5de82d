"""The exact GELU keeps its erfc factor (nn/functional/activation.py).

`F.gelu(x)` is `jax.nn.gelu(x, approximate=False)`, `x * erfc(-x / sqrt(2))
/ 2`, with the erfc factor pinned behind an `optimization_barrier` and
saved for the derivative, so that the TPU compiler evaluates the erfc
polynomial once a GELU and not once in each consumer's fusion
(tests/test_tpu_compile.py holds the compiler to that on a described
chip). Here, on the CPU: the arithmetic is jax's own. Forward values and
gradients are those of the same program built on `jax.nn.gelu` — bit for
bit in float32, within a bfloat16 ulp in bfloat16 — through the eager
tape, a compiled `to_static(scan_steps=2)` step, a recompute segment and
a rolled loop; the traced program holds one `erfc` and one barrier a
GELU; the build counts the factors it staged.
"""
import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import monitor, nn, ops
from paddle_tpu.nn.functional import activation

REFERENCE = functools.partial(jax.nn.gelu, approximate=False)
DTYPES = ["float32", "bfloat16"]
COUNTER = "jit_saved_activation_factors"


def values(shape, dtype, seed=0, scale=3.0):
    """Through GELU's whole range, the tails where erfc's branches
    change included."""
    x = np.random.RandomState(seed).standard_normal(shape) * scale
    return jnp.asarray(x, jnp.float32).astype(dtype)


def ulps_apart(a, b):
    """Steps of the dtype's grid between two arrays, elementwise."""
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]

    def ordered(v):
        i = np.asarray(v).view(bits).astype(np.int64)
        return np.where(i < 0, -(i & np.iinfo(bits).max), i)

    return np.abs(ordered(a) - ordered(b))


def assert_same(got, want, dtype):
    got, want = jnp.asarray(got), jnp.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    worst = int(ulps_apart(got, want).max())
    assert worst <= (0 if dtype == "float32" else 1), worst


class Block(nn.Layer):
    """A GPT block's FFN half: x + fc2(gelu(fc1(x)))."""

    def __init__(self, width=8, approximate=False):
        super().__init__()
        self.fc1 = nn.Linear(width, 4 * width)
        self.fc2 = nn.Linear(4 * width, width)
        self.approximate = approximate

    def forward(self, x):
        return x + self.fc2(F.gelu(self.fc1(x), self.approximate))


def blocks(n, dtype="float32", **kw):
    paddle.seed(11)
    made = [Block(**kw) for _ in range(n)]
    for b in made:
        b.to(dtype)
    return made


def grads_of(layers):
    return [p.grad._value for b in layers for p in b.parameters()]


# ------------------------------------------------------------- the function

@pytest.mark.parametrize("dtype", DTYPES)
def test_forward_is_jax_nn_gelu_bit_for_bit(dtype):
    x = values((64, 256), dtype)
    want = REFERENCE(x)
    recorded = F.gelu(paddle.to_tensor(x, stop_gradient=False))
    with paddle.no_grad():
        plain = F.gelu(paddle.to_tensor(x))
    for got in (recorded, plain):
        assert got._value.dtype == want.dtype
        assert int(ulps_apart(got._value, want).max()) == 0


@pytest.mark.parametrize("dtype", DTYPES)
def test_eager_gradient_is_jax_grads(dtype):
    x, g = values((64, 256), dtype), values((64, 256), dtype, 1, 1.0)
    want = jax.vjp(REFERENCE, x)[1](g)[0]
    xt = paddle.to_tensor(x, stop_gradient=False)
    F.gelu(xt).backward(paddle.to_tensor(g))
    assert_same(xt.grad._value, want, dtype)


# ----------------------------------------- the same program on jax.nn.gelu

def eager_tape(dtype):
    (block,) = blocks(1, dtype)
    x = paddle.to_tensor(values((4, 8), dtype, scale=1.0),
                         stop_gradient=False)
    y = block(x)
    ops.sum(y * y).backward()
    return [y._value, x.grad._value] + grads_of([block])


def compiled_step(dtype):
    """The user's step: autocast forward, backward, an update, two
    steps a call (float32: the same step without the autocast)."""
    stack = blocks(2)
    params = [p for b in stack for p in b.parameters()]
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)

    def one_step(x):
        with paddle.amp.auto_cast(enable=dtype == "bfloat16",
                                  dtype="bfloat16"):
            for b in stack:
                x = b(x)
            loss = ops.sum(x * x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one_step, scan_steps=2)
    losses = step(paddle.to_tensor(values((2, 4, 8), "float32", scale=1.0)))
    return [losses._value] + [p._value for p in params]


def recompute_segment(dtype):
    (block,) = blocks(1, dtype)
    block.enable_recompute("full")
    x = paddle.to_tensor(values((4, 8), dtype, scale=1.0),
                         stop_gradient=False)
    y = block(x)
    ops.sum(y * y).backward()
    return [y._value, x.grad._value] + grads_of([block])


def rolled_loop(dtype):
    (block,) = blocks(1, dtype)
    x = paddle.to_tensor(values((4, 8), dtype, scale=1.0),
                         stop_gradient=False)
    (ys,) = nn.fixed_loop(block, [x], 3, rolled=True)
    ops.sum(ys * ys).backward()
    return [ys._value, x.grad._value] + grads_of([block])


def first_order_of_create_graph(dtype):
    x = paddle.to_tensor(values((4, 8), dtype), stop_gradient=False)
    (gx,) = paddle.grad(ops.sum(F.gelu(x)), [x], create_graph=True)
    return [gx._value]


CONTEXTS = [eager_tape, compiled_step, recompute_segment, rolled_loop,
            first_order_of_create_graph]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("context", CONTEXTS, ids=lambda f: f.__name__)
def test_values_and_gradients_are_those_of_jax_nn_gelu(context, dtype,
                                                       monkeypatch):
    got = context(dtype)
    monkeypatch.setattr(activation, "_gelu_exact", REFERENCE)
    want = context(dtype)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert_same(a, b, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
def test_create_graph_differentiates_the_saved_factor_too(dtype):
    """The second derivative goes through the residual: d/dx of
    t(x) / 2 + x pdf(x) is (2 - x^2) pdf(x)."""
    x = values((4, 8), dtype)
    xt = paddle.to_tensor(x, stop_gradient=False)
    (gx,) = paddle.grad(ops.sum(F.gelu(xt)), [xt], create_graph=True)
    ops.sum(gx).backward()
    x64 = np.asarray(x, np.float64)
    want = (2 - x64 ** 2) * np.exp(-x64 ** 2 / 2) / np.sqrt(2 * np.pi)
    tol = 2e-6 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(xt.grad._value, np.float64), want,
                               rtol=tol, atol=tol)
    second = jax.vmap(jax.grad(jax.grad(REFERENCE)))(x.ravel())
    np.testing.assert_allclose(
        np.asarray(xt.grad._value, np.float64).ravel(),
        np.asarray(second, np.float64), rtol=tol, atol=tol)


# ------------------------------------------------ what the program stages

def primitives(jaxpr):
    """Counter of primitive names over a jaxpr and every jaxpr in it."""
    counts = collections.Counter(e.primitive.name for e in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            counts += primitives(sub)
    return counts


def forward_backward(approximate):
    def run(x, g):
        xt = paddle.to_tensor(x, stop_gradient=False)
        F.gelu(xt, approximate).backward(paddle.to_tensor(g))
        return xt.grad._value
    x = values((4, 8), "float32")
    return primitives(jax.make_jaxpr(run)(x, x).jaxpr)


def test_one_erfc_and_one_barrier_a_gelu_none_in_the_backward():
    staged = forward_backward(approximate=False)
    assert staged["erfc"] == 1 and staged["optimization_barrier"] == 1
    # the derivative's pdf is the backward's only transcendental
    assert staged["exp"] == 1 and "div" not in staged


def test_the_tanh_form_stages_no_barrier():
    staged = forward_backward(approximate=True)
    assert "optimization_barrier" not in staged and "erfc" not in staged
    assert staged["tanh"] >= 1


def _build(n, wrap=None, approximate=False, grad=True):
    """A step over `n` blocks, built once; what the build counted, the
    step's traced primitives and its compiled text."""
    stack = blocks(n, approximate=approximate)
    if wrap == "recompute":
        for b in stack:
            b.enable_recompute("full")

    def body(x):
        if wrap == "loop":
            (ys,) = nn.fixed_loop(stack[0], [x], 3)
            return ys[-1]
        for b in stack:
            x = b(x)
        return x

    def one_step(x):
        if not grad:
            with paddle.no_grad():
                return ops.sum(body(x))
        loss = ops.sum(body(x))
        loss.backward()
        return loss

    step = paddle.jit.to_static(one_step, scan_steps=2)
    before = monitor.stat_get(COUNTER)
    step(paddle.to_tensor(values((2, 4, 8), "float32", scale=1.0)))
    counted = monitor.stat_get(COUNTER) - before
    return counted, primitives(step._last_aux["traced_jaxpr"]().jaxpr), step


@pytest.mark.parametrize("case,want", [
    (dict(n=3), 3),
    # a segment's capture pass stages nothing: its operations are dropped
    (dict(n=3, wrap="recompute"), 3),
    # one block run three times in a rolled region is one GELU staged
    (dict(n=1, wrap="loop"), 1),
    (dict(n=3, approximate=True), 0),
    (dict(n=3, grad=False), 0),
], ids=["a_block_each", "recompute_segments", "rolled_loop", "tanh_form",
        "no_grad"])
def test_the_build_counts_the_factors_it_staged(case, want):
    counted, staged, _step = _build(**case)
    assert counted == want
    # every evaluation the trace holds is pinned (a segment's replay and
    # a rolled region's copies of the body hold their own)
    pinned = staged["optimization_barrier"]
    assert pinned == (staged["erfc"] if want else 0)
    if "wrap" not in case:
        assert pinned == want


def test_the_scope_keeps_its_name_forward_and_backward():
    _counted, _staged, step = _build(n=1)
    gelu = [name for name in re.findall(r'op_name="([^"]*)"', step.hlo_text())
            if "pt.gelu" in name]
    assert any("transpose(" not in name for name in gelu)
    assert any("transpose(" in name for name in gelu)
