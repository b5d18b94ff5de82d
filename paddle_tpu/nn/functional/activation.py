"""Activation functionals (reference: `paddle/fluid/operators/activation_op.cc`,
`python/paddle/nn/functional/activation.py`). Pure jnp lowerings; XLA fuses
them into adjacent matmuls/convs, replacing the reference's hand-fused CUDA.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import call_op
from ...ops.math import _unary


def relu(x):
    return _unary(jax.nn.relu, x, "relu")


def relu6(x):
    return _unary(jax.nn.relu6, x, "relu6")


def sigmoid(x):
    return _unary(jax.nn.sigmoid, x, "sigmoid")


def tanh(x):
    return _unary(jnp.tanh, x, "tanh")


def _sqrt_half(x):
    return np.sqrt(0.5).astype(x.dtype)


@jax.custom_vjp
def _gelu_exact(x):
    return jax.nn.gelu(x, approximate=False)


def _gelu_exact_fwd(x):
    """`jax.nn.gelu`'s own expression, `0.5 * x * erfc(-x * sqrt(0.5))`,
    with the erfc factor `t` pinned and saved. XLA:TPU stores no GELU
    output: left alone it recomputes the erfc polynomial (~110 float32
    vector operations an element) inside each consumer's fusion — the
    next matmul's forward, its weight gradient and its input gradient —
    and those matmuls wait for it. Behind the barrier the polynomial runs
    once; `x * t / 2` stays free to fuse anywhere (store `t`, not `y`:
    the derivative needs `t`)."""
    from ...jit.to_static import note_structure
    note_structure("saved_activation_factors")
    t = jax.lax.optimization_barrier(jax.lax.erfc(-x * _sqrt_half(x)))
    return 0.5 * x * t, (x, t)


def _gelu_exact_bwd(res, g):
    # jax's own rule for that expression, term for term and in its
    # dtype, reading the saved factor: g * (t / 2 + x * pdf(x))
    x, t = res
    c = _sqrt_half(x)
    du = (np.array(-2.0 / np.sqrt(np.pi), x.dtype) * (0.5 * x * g)
          * jnp.exp(-jnp.square(-x * c)))
    return (-(du * c) + 0.5 * (g * t),)


_gelu_exact.defvjp(_gelu_exact_fwd, _gelu_exact_bwd)


def gelu(x, approximate=False):
    if approximate:
        return call_op(lambda v: jax.nn.gelu(v, approximate=True), x,
                       op_name="gelu")
    return call_op(_gelu_exact, x, op_name="gelu")


def silu(x):
    return _unary(jax.nn.silu, x, "silu")


swish = silu


def mish(x):
    return call_op(lambda v: v * jnp.tanh(jax.nn.softplus(v)), x, op_name="mish")


def leaky_relu(x, negative_slope=0.01):
    return call_op(lambda v: jax.nn.leaky_relu(v, negative_slope), x,
                   op_name="leaky_relu")


def elu(x, alpha=1.0):
    return call_op(lambda v: jax.nn.elu(v, alpha), x, op_name="elu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return call_op(lambda v: scale * jnp.where(v > 0, v, alpha * jnp.expm1(v)),
                   x, op_name="selu")


def celu(x, alpha=1.0):
    return call_op(lambda v: jax.nn.celu(v, alpha), x, op_name="celu")


def hardshrink(x, threshold=0.5):
    return call_op(lambda v: jnp.where(jnp.abs(v) > threshold, v, 0.0), x,
                   op_name="hardshrink")


def softshrink(x, threshold=0.5):
    return call_op(
        lambda v: jnp.where(v > threshold, v - threshold,
                            jnp.where(v < -threshold, v + threshold, 0.0)),
        x, op_name="softshrink")


def tanhshrink(x):
    return call_op(lambda v: v - jnp.tanh(v), x, op_name="tanhshrink")


def hardtanh(x, min=-1.0, max=1.0):  # noqa: A002
    return call_op(lambda v: jnp.clip(v, min, max), x, op_name="hardtanh")


def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return call_op(lambda v: jnp.clip(v * slope + offset, 0.0, 1.0), x,
                   op_name="hardsigmoid")


def hardswish(x):
    return call_op(lambda v: v * jnp.clip(v + 3.0, 0.0, 6.0) / 6.0, x,
                   op_name="hardswish")


def softplus(x, beta=1.0, threshold=20.0):
    return call_op(
        lambda v: jnp.where(v * beta > threshold, v,
                            jnp.log1p(jnp.exp(beta * v)) / beta),
        x, op_name="softplus")


def softsign(x):
    return call_op(jax.nn.soft_sign, x, op_name="softsign")


def thresholded_relu(x, threshold=1.0):
    return call_op(lambda v: jnp.where(v > threshold, v, 0.0), x,
                   op_name="thresholded_relu")


def log_sigmoid(x):
    return call_op(jax.nn.log_sigmoid, x, op_name="log_sigmoid")


def softmax(x, axis=-1, dtype=None):
    def _softmax(v):
        if dtype is not None:
            v = v.astype(dtype)
        return jax.nn.softmax(v, axis=axis)
    return call_op(_softmax, x, op_name="softmax")


def log_softmax(x, axis=-1):
    return call_op(lambda v: jax.nn.log_softmax(v, axis=axis), x,
                   op_name="log_softmax")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    from ...core import random as core_random
    key = core_random.next_key()

    def _gs(v):
        g = jax.random.gumbel(key, v.shape, dtype=v.dtype)
        y = jax.nn.softmax((v + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y).at[
                tuple(jnp.indices(y.shape)[i] if i != (axis % y.ndim) else idx
                      for i in range(y.ndim))].set(1.0)
            y = jax.lax.stop_gradient(y_hard - y) + y
        return y
    return call_op(_gs, x, op_name="gumbel_softmax")


def prelu(x, weight):
    def _prelu(v, w):
        if w.size == 1:
            return jnp.where(v >= 0, v, w.reshape(()) * v)
        shape = [1] * v.ndim
        shape[1] = w.size  # channel dim, NCHW
        return jnp.where(v >= 0, v, w.reshape(shape) * v)
    return call_op(_prelu, x, weight, op_name="prelu")


def glu(x, axis=-1):
    def _glu(v):
        a, b = jnp.split(v, 2, axis=axis)
        return a * jax.nn.sigmoid(b)
    return call_op(_glu, x, op_name="glu")


def maxout(x, groups, axis=1):
    def _maxout(v):
        c = v.shape[axis]
        new_shape = list(v.shape)
        new_shape[axis] = c // groups
        new_shape.insert(axis + 1, groups)
        return jnp.max(v.reshape(new_shape), axis=axis + 1)
    return call_op(_maxout, x, op_name="maxout")
