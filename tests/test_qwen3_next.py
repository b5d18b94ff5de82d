"""Qwen3-Next on the normal path, at a small size on the CPU: the chunked
gated delta rule against its recurrence token by token, forward and
gradients; the convolution and the gated norm against their formulas;
the program's loss, every gradient leaf and two AdamW steps against the
plain reference (chipbench/reference/qwen3_next.py, which imports
nothing of paddle_tpu); the block's Gated DeltaNet and gated attention
against the reference's; softmax routing's shares tied to the uncut
layer with the shared expert counted once; what the configuration class
refuses; the compiled step's scopes and counters; the configuration
file tied to the model and the published config.json."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.incubate import moe as moe_mod  # noqa: E402
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.models.decoder import DecoderBlock  # noqa: E402
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,  # noqa: E402
                                          Qwen3NextForCausalLM)
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.nn.functional import delta_rule as dr  # noqa: E402
from paddle_tpu.parallel import moe as pmoe  # noqa: E402
from paddle_tpu.parallel.moe import held_experts_ffn  # noqa: E402

from chipbench.models import _common, qwen3_next as bench  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402
from chipbench.reference import qwen3_next as ref  # noqa: E402

SEED = 2_147_483_659
TRAINING = {"optimizer": "adamw", "learning_rate": 3e-4, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
            "param_dtype": "float32", "compute_dtype": "float32"}
CELL = {"batch": 2, "seq": 80}
CONFIG_FILE = os.path.join(ROOT, "chipbench", "configs",
                           "qwen3_next_ep16_d4.json")
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")
# the shape keys of that config.json as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
HIGHEST = jax.lax.Precision.HIGHEST


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny_cfg(ep_rank=1, ep_size=4, **more):
    """config.json's keys at test widths: the cell's four layers, 4
    query heads of 32 over 2 key/value heads, 2 key heads and 4 value
    heads of 16 in the Gated DeltaNet, 16 experts of which this share
    holds 4, 4 a token."""
    cfg = published()
    cfg.update(vocab_size=256, hidden_size=64, moe_intermediate_size=24,
               shared_expert_intermediate_size=24, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, num_experts=16 // ep_size,
               num_experts_per_tok=4, max_position_embeddings=128,
               training=TRAINING,
               deployment={"ep_size": ep_size, "ep_rank": ep_rank}, **more)
    return cfg


def program_config(cfg):
    share = cfg["deployment"]
    return Qwen3NextConfig(**{k: cfg[k] for k in bench._CONFIG_KEYS},
                           num_experts=cfg["num_experts"] * share["ep_size"],
                           ep_size=share["ep_size"], ep_rank=share["ep_rank"])


def build(cfg, recompute=None):
    """(model, float32 seeded weights by the harness's keys)."""
    model = Qwen3NextForCausalLM(program_config(cfg))
    weights = _common.init_weights(bench.weight_shapes(cfg), 0.02, SEED,
                                   "float32")
    # a router that spreads its scores, so that the choice is no tie, and
    # taps and a decay of sizes that leave each factor its say: values
    # that reach the rule at the scale of the keys, a state that lasts
    # a few chunks
    scaled = {"router": 8.0, "conv": 25.0, "A_log": 1.0, "dt_bias": 1.0}
    weights = {k: v * scaled.get(k.split("_", 1)[-1], 1.0)
               for k, v in weights.items()}
    weights.update({k: jnp.full_like(v, -4.0) for k, v in weights.items()
                    if k.endswith("dt_bias")})
    _common.set_program_weights(model, bench.program_names(cfg), weights)
    if recompute:
        model.enable_layer_recompute(recompute)
    return model, weights


def batch(cfg, step):
    return bench.make_batch(cfg, CELL, SEED, step)


def program_grads(model, cfg, step=0):
    ids, labels = batch(cfg, step)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
    loss.backward()
    names = bench.program_names(cfg)  # no key is stacked over layers
    grads = {names[pname][0]: np.asarray(p.grad.numpy())
             for pname, p in model.named_parameters()}
    model.clear_gradients()
    return float(loss), grads


def worst_gap(got, want):
    return max(float(np.abs(got[k] - np.asarray(want[k])).max()
                     / max(np.abs(np.asarray(want[k])).max(), 1e-30))
               for k in want)


# ------------------------------------------------------ the chunked rule

def _rule_inputs(seq, decay, batch=2, heads=3, dk=16, dv=8, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    k = f32(batch, seq, heads, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    low, high = {"near_zero": (0.0, 1e-3), "strong": (2.0, 8.0),
                 "mixed": (0.0, 3.0)}[decay]
    g = -jnp.asarray(rng.uniform(low, high, (batch, seq, heads)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (batch, seq, heads)),
                       jnp.float32)
    return (f32(batch, seq, heads, dk) / 4, k, f32(batch, seq, heads, dv), g,
            beta)


def _raw_recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule, over the batch rows."""
    return jax.vmap(functools.partial(ref.recurrence, "float32"))(
        q, k, v, g, beta)


def _recurrence(q, k, v, g, beta):
    """The same of q and k normalized as Gated DeltaNet reads them."""
    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return _raw_recurrence(unit(q) * q.shape[-1] ** -0.5, unit(k), v, g,
                           beta)


@pytest.mark.parametrize("seq", [64, 100, 7, 1], ids=lambda s: f"s{s}")
@pytest.mark.parametrize("decay", ["near_zero", "strong", "mixed"])
def test_the_chunked_rule_is_the_recurrence(seq, decay):
    """At a whole number of chunks (4 of 16) and not (100, 7, 1: the
    last chunk padded), with g near 0 (the state lasts the sequence), g
    strongly negative (each chunk nearly forgets the last) and both:
    o and the gradients of all five inputs."""
    _check_against_recurrence(_rule_inputs(seq, decay), 16)


def test_a_chunk_that_is_no_power_of_two_is_the_recurrence():
    """A chunk of 24: the inverse's last block of each level is cut short.
    o and the gradients of all five inputs, over 100 positions (the last
    chunk padded)."""
    _check_against_recurrence(_rule_inputs(100, "mixed"), 24)


def _check_against_recurrence(args, chunk):
    weights = jnp.asarray(np.random.default_rng(1).normal(
        size=args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = dr.chunked_delta_rule(*args, chunk=chunk)
        want = _recurrence(*args)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weights),
                          argnums=range(5))(*args)
                 for f in (functools.partial(dr.chunked_delta_rule,
                                             chunk=chunk), _recurrence)]
    seq = args[0].shape[1]
    for name, g_got, g_want in zip("q k v g beta".split(), *grads):
        scale = float(jnp.abs(g_want).max())
        # one position: the decay meets a zero state and has no gradient
        assert scale > 0 or (name, seq) == ("g", 1), name
        assert float(jnp.abs(g_got - g_want).max()) <= 3e-5 * scale, name


def _chunk_a(chunk, keys, dtype):
    """A = strictly_lower(diag(beta) K K^T * Gamma) of one chunk, as the
    rule forms it, for two heads: keys random, or sharing one direction
    at 4 x the noise with beta near 1 (A's entries then near 1)."""
    rng = np.random.default_rng(chunk)
    k = rng.normal(size=(2, chunk, 16))
    if keys == "correlated":
        k = k + 4.0 * rng.normal(size=(2, 1, 16))
        beta = rng.uniform(0.9, 1.0, (2, chunk))
    else:
        beta = rng.uniform(0.0, 1.0, (2, chunk))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    gc = np.cumsum(-rng.uniform(0.0, 0.1, (2, chunk)), axis=-1)
    gamma = np.exp(gc[:, :, None] - gc[:, None, :])
    a = np.tril(beta[:, :, None] * (k @ k.transpose(0, 2, 1)) * gamma, -1)
    return jnp.asarray(a, dtype)


@pytest.mark.parametrize("keys", ["random", "correlated"])
@pytest.mark.parametrize("chunk", [8, 16, 24, 64], ids=lambda c: f"c{c}")
def test_the_chunk_inverse_is_the_inverse_and_so_is_its_derivative(chunk,
                                                                   keys):
    """T = (I + A)^-1 by block doubling (at 24 the last block of each
    level is cut short) against numpy's inverse in float64, every
    product of it and of its derivative at HIGHEST; the derivative dT =
    -T dA T against finite differences, forward and reverse, of A's
    strictly lower part (the only part the rule ever gives it)."""
    a = _chunk_a(chunk, keys, jnp.float32)
    want = np.linalg.inv(np.eye(chunk) + np.asarray(a, np.float64))
    got = np.asarray(dr._unit_lower_inverse(a), np.float64)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())
    text = jax.jit(lambda a: jax.jvp(dr._unit_lower_inverse, (a,),
                                     (a,))).lower(a).as_text()
    products = [line for line in text.splitlines() if "dot_general" in line]
    assert len(products) == 2 * (chunk - 1).bit_length()
    assert all("precision = [HIGHEST, HIGHEST]" in p for p in products)
    with jax.enable_x64(True):
        check_grads(
            lambda a: dr._unit_lower_inverse(jnp.tril(a, -1)),
            (_chunk_a(chunk, keys, jnp.float64),), order=1,
            modes=("fwd", "rev"))


def test_the_rule_s_chunk_is_its_own_business():
    """The default chunk of 64 and a chunk of 8 give the same o."""
    args = _rule_inputs(130, "mixed")
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(dr.chunked_delta_rule(*args),
                                   dr.chunked_delta_rule(*args, chunk=8),
                                   rtol=2e-5, atol=2e-6)


def test_the_rule_keeps_batch_rows_and_the_future_apart():
    q, k, v, g, beta = _rule_inputs(48, "near_zero")
    base = dr.chunked_delta_rule(q, k, v, g, beta, chunk=16)
    # a later position and another row changed: the earlier positions of
    # row 0 are what they were
    v2 = v.at[0, 30:].add(1.0).at[1].add(1.0)
    moved = dr.chunked_delta_rule(q, k, v2, g, beta, chunk=16)
    np.testing.assert_array_equal(moved[0, :30], base[0, :30])
    assert float(jnp.abs(moved[0, 30:] - base[0, 30:]).max()) > 1e-3


def test_the_rule_in_bfloat16_keeps_its_inputs_and_its_output_type():
    args = _rule_inputs(64, "mixed")
    low = [a.astype(jnp.bfloat16) if i < 3 else a
           for i, a in enumerate(args)]
    out, vjp = jax.vjp(dr.chunked_delta_rule, *low)
    assert out.dtype == jnp.bfloat16
    grads = vjp(jnp.ones_like(out))
    assert [x.dtype for x in grads] == [x.dtype for x in low]
    with jax.default_matmul_precision("highest"):
        want = dr.chunked_delta_rule(*args)
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=0.05,
                               atol=0.05 * float(jnp.abs(want).max()))


def test_the_rule_reads_each_key_head_for_its_group_of_value_heads():
    """q and k at 3 key heads over v at 6 value heads are q and k
    repeated to 6 (value head i reads key head i // 2), forward and
    gradients, those of q and k summed over each group."""
    q, k, v, g, beta = _rule_inputs(40, "mixed", heads=6)
    q, k = q[:, :, ::2], k[:, :, ::2]
    weights = jnp.asarray(np.random.default_rng(3).normal(size=v.shape),
                          jnp.float32)

    def repeated(q, k, *rest):
        return dr.chunked_delta_rule(jnp.repeat(q, 2, axis=2),
                                     jnp.repeat(k, 2, axis=2), *rest,
                                     chunk=16)

    with jax.default_matmul_precision("highest"):
        grouped = functools.partial(dr.chunked_delta_rule, chunk=16)
        np.testing.assert_allclose(grouped(q, k, v, g, beta),
                                   repeated(q, k, v, g, beta),
                                   rtol=1e-5, atol=1e-6)
        grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weights),
                          argnums=range(5))(q, k, v, g, beta)
                 for f in (grouped, repeated)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(want).max()))
    with pytest.raises(ValueError, match="multiple of the key heads"):
        dr.chunked_delta_rule(q[:, :, :2], k[:, :, :2], v[:, :, :5],
                              g[:, :, :5], beta[:, :, :5])


def test_the_layer_s_gates_and_norms_are_the_rule_s_inputs():
    """`F.gated_delta_rule`: l2-normed q (over sqrt(d_k)) and k, a value
    head reading key head i // 2, beta = sigmoid(b), g = -exp(A_log)
    softplus(a + dt_bias)."""
    rng = np.random.default_rng(2)
    f32 = lambda *s: rng.normal(size=s).astype("float32")  # noqa: E731
    q, k, v = f32(2, 20, 2, 16), f32(2, 20, 2, 16), f32(2, 20, 4, 8)
    a, b, a_log, dt = f32(2, 20, 4), f32(2, 20, 4), f32(4), f32(4)
    got = F.gated_delta_rule(*(paddle.to_tensor(x) for x in
                               (q, k, v, a, b, a_log, dt))).numpy()
    qn = q / np.sqrt(np.sum(q * q, -1, keepdims=True) + 1e-6) / 4.0
    kn = k / np.sqrt(np.sum(k * k, -1, keepdims=True) + 1e-6)
    g = -np.exp(a_log) * np.log1p(np.exp(a + dt))
    beta = 1.0 / (1.0 + np.exp(-b))
    with jax.default_matmul_precision("highest"):
        want = _raw_recurrence(*(jnp.asarray(x) for x in (
            np.repeat(qn, 2, axis=2), np.repeat(kn, 2, axis=2), v, g, beta)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="multiple of the key heads"):
        F.gated_delta_rule(*(paddle.to_tensor(x) for x in (
            q, k, f32(2, 20, 3, 8), f32(2, 20, 3), f32(2, 20, 3), f32(3),
            f32(3))))


@pytest.mark.parametrize("seq,taps", [(9, 4), (2, 4), (1, 4), (7, 2)])
def test_the_convolution_is_its_loop_over_positions(seq, taps):
    rng = np.random.default_rng(seq)
    x = rng.normal(size=(2, seq, 12)).astype("float32")
    w = rng.normal(size=(12, taps)).astype("float32")
    got = F.causal_conv_silu(paddle.to_tensor(x), paddle.to_tensor(w))
    want = np.zeros_like(x)
    for t in range(seq):
        for j in range(taps):  # tap j meets position t - (taps - 1 - j)
            if t - (taps - 1 - j) >= 0:
                want[:, t] += w[:, j] * x[:, t - (taps - 1 - j)]
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-6)


SILU_KERNEL_SHAPES = [(2, 256, 128, 4), (1, 384, 1024, 4), (1, 128, 256, 2)]


@pytest.mark.parametrize("shape", SILU_KERNEL_SHAPES,
                         ids=["x".join(map(str, s))
                              for s in SILU_KERNEL_SHAPES])
def test_the_convolution_s_kernels_are_the_xla_form(shape, monkeypatch):
    """The SiLU form of the short convolution's pallas kernels,
    interpreted, values and both gradients, against the shifted
    multiply-adds XLA runs elsewhere: across block boundaries (the
    backward's sums just after a block), at the first and the last
    block, nothing across rows."""
    from paddle_tpu.kernels import short_conv as conv_kernel

    rows, seq, width, length = shape
    rng = np.random.default_rng(seq + width)
    x = rng.normal(size=(rows, seq, width)).astype("float32")
    taps = rng.normal(size=(width, length)).astype("float32")
    weight = rng.normal(size=(rows, seq, width)).astype("float32")
    assert conv_kernel.supports(x.shape, taps.shape)

    def run():
        px = paddle.to_tensor(x, stop_gradient=False)
        pt = paddle.to_tensor(taps, stop_gradient=False)
        out = F.causal_conv_silu(px, pt)
        (out * paddle.to_tensor(weight)).sum().backward()
        return out.numpy(), px.grad.numpy(), pt.grad.numpy()

    want = run()  # no TPU here: XLA's path
    monkeypatch.setattr(conv_kernel, "is_available", lambda: True)
    monkeypatch.setattr(conv_kernel, "silu_conv", functools.partial(
        conv_kernel.silu_conv, interpret=True))
    got = run()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-4, atol=2e-3)


def test_the_gated_norm_is_its_formula():
    rng = np.random.default_rng(4)
    x, z = (rng.normal(size=(3, 5, 16)).astype("float32") for _ in range(2))
    w = rng.normal(size=(16,)).astype("float32")
    got = F.gated_rms_norm(*(paddle.to_tensor(a) for a in (x, z, w)), 1e-6)
    want = (x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) * w
            * z / (1.0 + np.exp(-z)))
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-6)
    # its backward, written by hand, is the formula's
    weights = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def formula(x, z, w):
        return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
                * w * jax.nn.silu(z))

    grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * weights),
                      argnums=range(3))(*map(jnp.asarray, (x, z, w)))
             for f in (lambda *a: dr._gated_norm(*a, 1e-6), formula)]
    for got_d, want_d in zip(*grads):
        np.testing.assert_allclose(got_d, want_d, rtol=1e-5, atol=1e-5)


# ------------------------------------------- the program and the reference

def test_loss_and_every_gradient_leaf_match_the_reference():
    cfg = tiny_cfg()
    model, weights = build(cfg)
    assert set(bench.program_names(cfg)) == {
        n for n, _p in model.named_parameters()}
    loss, grads = program_grads(model, cfg)
    ids, labels = batch(cfg, 0)
    want_loss, want = jax.value_and_grad(ref.loss_fn)(
        weights, (jnp.asarray(ids), jnp.asarray(labels)), cfg=cfg)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    assert set(grads) == set(want)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in want.values())
    # float32 on both sides, summed in another order (the delta rule a
    # chunk at a time against a token at a time)
    assert worst_gap(grads, want) < 5e-5


def test_two_adamw_steps_match_the_reference():
    cfg = tiny_cfg()
    model, weights = build(cfg)
    opt = paddle.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=3e-4, beta1=0.9,
        beta2=0.95, epsilon=1e-8, weight_decay=0.1)
    losses = []
    for step in range(2):
        ids, labels = batch(cfg, step)
        loss = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    steps = [tuple(jnp.asarray(a) for a in batch(cfg, i)) for i in range(2)]
    out = ref_common.train(
        functools.partial(ref.loss_fn, cfg=cfg),
        lambda: {k: jnp.array(v) for k, v in weights.items()}, steps,
        TRAINING, lambda tree: tree)
    assert losses == pytest.approx(out["losses"], rel=2e-6)
    names = bench.program_names(cfg)
    for pname, p in model.named_parameters():
        key, _layer = names[pname]
        got = np.asarray(p.numpy()) - np.asarray(weights[key])
        want = np.asarray(out["change"][key])
        # two sign-like steps: an element whose gradient is near 0 moves
        # by the round-off's sign, so a leaf is held by its norm
        assert np.linalg.norm(got - want) < 5e-3 * np.linalg.norm(want), pname


@pytest.mark.parametrize("recompute", ["full", "kernels"])
def test_recomputing_a_layer_changes_nothing(recompute):
    cfg = tiny_cfg()
    plain = program_grads(build(cfg)[0], cfg)
    again = program_grads(build(cfg, recompute=recompute)[0], cfg)
    assert again[0] == pytest.approx(plain[0], rel=1e-6)
    assert worst_gap(again[1], plain[1]) < 5e-5


def _layer_input(cfg, model, i):
    """The input of layer i's mixer, row by row, and its weights."""
    x = np.random.default_rng(i).normal(
        size=(2, 40, cfg["hidden_size"])).astype("float32")
    layer = model.model.layers[i]
    names = bench.program_names(cfg)
    p = {names[f"model.layers.{i}.{n}"][0].split("_", 1)[1]:
         jnp.asarray(t.numpy()) for n, t in layer.named_parameters()}
    return x, layer, p


@pytest.mark.parametrize("i", [0, 3], ids=["gdn", "attention"])
def test_the_block_s_mixer_is_the_reference_s(i):
    cfg = tiny_cfg()
    model, _w = build(cfg)
    x, layer, p = _layer_input(cfg, model, i)
    got = layer._attention(layer.ln1(paddle.to_tensor(x)), 2, 40).numpy()
    mixer = ref.gated_delta_net if i == 0 else ref.gated_attention
    for row in range(2):
        u = ref.zrms(jnp.asarray(x[row]), p["input_norm"],
                     cfg["rms_norm_eps"])
        want = mixer("float32", u, p, cfg)
        np.testing.assert_allclose(got[row], want, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))


def test_rope_turns_the_first_quarter_of_each_head_alone():
    cfg = tiny_cfg()
    model, _w = build(cfg)
    layer = model.model.layers[3]
    x = np.random.default_rng(5).normal(size=(1, 12, 4, 32)).astype(
        "float32")
    got = layer._rope(paddle.to_tensor(x)).numpy()
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    want = ref.rope_halves(jnp.asarray(x[0, :, :, :8]), 1e7)
    np.testing.assert_allclose(got[0, :, :, :8], want, rtol=1e-5, atol=1e-6)
    assert float(np.abs(got[0, 1:, :, :8] - x[0, 1:, :, :8]).max()) > 1e-3


def test_the_head_is_untied_and_every_norm_starts_at_one():
    cfg = tiny_cfg()
    model, weights = build(cfg)
    assert model.lm_head.weight.shape == [64, 256]
    assert not np.array_equal(model.lm_head.weight.numpy().T,
                              model.model.embed_tokens.weight.numpy())
    fresh = Qwen3NextForCausalLM(program_config(cfg))
    for name, p in fresh.named_parameters():
        if name.endswith(("ln1.weight", "ln2.weight", "q_norm.weight",
                          "k_norm.weight", "model.norm.weight")):
            assert not np.abs(p.numpy()).any(), name  # 1 + w, w = 0
        if name.endswith(("gdn_norm", "dt_bias")):
            assert (p.numpy() == 1.0).all(), name


# ------------------------------------------------------------ the routing

def _moe_inputs(tokens=48, width=32, experts=16, hidden=24, seed=3):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {"x": f32(tokens, width), "router": f32(width, experts),
            "e_gate": f32(experts, width, hidden, scale=0.3),
            "e_up": f32(experts, width, hidden, scale=0.3),
            "e_down": f32(experts, hidden, width, scale=0.3),
            "s_gate": f32(width, hidden, scale=0.3),
            "s_up": f32(width, hidden, scale=0.3),
            "s_down": f32(hidden, width, scale=0.3),
            "s_expert_gate": f32(width, 1)}


def _uncut(t):
    """The reference's MoE layer holding all 16 experts, and its shared
    expert."""
    whole = {"num_experts": 16, "num_experts_per_tok": 4,
             "deployment": {"ep_size": 1, "ep_rank": 0}}
    return (ref.routed_experts("float32", t["x"], t, whole)
            + ref.shared_expert("float32", t["x"], t))


def _share(t, first, held):
    cut = slice(first, first + held)
    return held_experts_ffn(
        t["x"], t["router"], None, t["e_gate"][cut], t["e_up"][cut],
        t["e_down"][cut], top_k=4, first_expert=first, scale=1.0,
        norm_eps=0.0, scoring="softmax")


def test_the_four_softmax_shares_and_the_shared_expert_are_the_layer():
    """The share tied to the model, with E = 16 over
    ep_size 4: the routed results of the four shares summed, and the
    shared expert that every chip computes alike counted once, are the
    uncut reference's layer."""
    t = _moe_inputs()
    parts = [_share(t, first, 4) for first in range(0, 16, 4)]
    shared = ref.shared_expert("float32", t["x"], t)
    np.testing.assert_allclose(sum(p[0] for p in parts) + shared, _uncut(t),
                               rtol=2e-5, atol=2e-6)
    assert sum(int(p[1]) for p in parts) == 48 * 4
    # and through the layers: four programs' MoE blocks, each holding its
    # share, with one shared expert
    cfg = tiny_cfg()
    outs = []
    for rank in range(4):
        block = DecoderBlock(program_config(dict(cfg, deployment={
            "ep_size": 4, "ep_rank": rank})), attention="gdn")
        for name, key in (("moe.router_weight", "router"),
                          ("shared_expert.gate_proj.weight", "s_gate"),
                          ("shared_expert.up_proj.weight", "s_up"),
                          ("shared_expert.down_proj.weight", "s_down"),
                          ("shared_expert_gate.weight", "s_expert_gate")):
            dict(block.named_parameters())[name].set_value(np.asarray(
                _moe_inputs(width=64, hidden=24)[key]))
        m = _moe_inputs(width=64, hidden=24)
        cut = slice(4 * rank, 4 * rank + 4)
        block.moe.w_gate.set_value(np.asarray(m["e_gate"][cut]))
        block.moe.w_up.set_value(np.asarray(m["e_up"][cut]))
        block.moe.w_down.set_value(np.asarray(m["e_down"][cut]))
        outs.append(block._ffn(paddle.to_tensor(np.asarray(m["x"]))).numpy())
    m = _moe_inputs(width=64, hidden=24)
    shared = np.asarray(ref.shared_expert("float32", m["x"], m))
    np.testing.assert_allclose(sum(outs) - 3 * shared, _uncut(m), rtol=2e-4,
                               atol=2e-5)


def test_softmax_gates_are_the_chosen_scores_over_their_sum():
    t = _moe_inputs()
    # every score equal: a tie goes to experts 0..3, each gate 1/4
    t["router"] = jnp.tile(t["router"][:, :1], (1, 16))
    first, rest = _share(t, 0, 4), _share(t, 4, 12)
    assert (int(first[1]), int(rest[1])) == (48 * 4, 0)
    units = sum(0.25 * (jax.nn.silu(t["x"] @ t["e_gate"][e])
                        * (t["x"] @ t["e_up"][e])) @ t["e_down"][e]
                for e in range(4))
    np.testing.assert_allclose(first[0], units, rtol=2e-4, atol=2e-5)


def test_the_sigmoid_layer_keeps_its_bias_and_the_softmax_layer_has_none():
    sig = moe_mod.HeldExpertsLayer(32, 24, 16, 4, ep_size=4)
    soft = moe_mod.HeldExpertsLayer(32, 24, 16, 4, ep_size=4, norm_eps=0.0,
                                    scoring="softmax")
    assert "e_score_correction_bias" in dict(sig.named_buffers())
    assert "e_score_correction_bias" not in dict(soft.named_buffers())
    assert (sig.scoring, soft.scoring) == ("sigmoid", "softmax")
    with pytest.raises(ValueError, match="scoring"):
        moe_mod.HeldExpertsLayer(32, 24, 16, 4, scoring="topk")
    t = _moe_inputs()
    with pytest.raises(ValueError, match="scoring"):
        held_experts_ffn(t["x"], t["router"], None, t["e_gate"][:4],
                         t["e_up"][:4], t["e_down"][:4], top_k=4,
                         first_expert=0, scale=1.0, scoring="topk")


# ------------------------------------------------- what the config refuses

@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("model_type", "qwen3_moe"),
    ("hidden_act", "gelu"), ("rope_scaling", {"type": "yarn"}),
    ("shared_expert_intermediate_size", 768)])
def test_the_config_refuses_by_name_what_it_has_no_path_for(key, value):
    with pytest.raises(NotImplementedError, match=key):
        Qwen3NextConfig(**{key: value})


def test_the_config_s_defaults_are_the_published_model():
    cfg = Qwen3NextConfig()
    row = PUBLISHED
    assert cfg.layer_types == ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3 + ["full_attention"] \
        + ["linear_attention", "linear_attention", "linear_attention",
           "full_attention"] * 10
    assert (cfg.num_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.n_routed_experts, cfg.num_experts_per_tok) == (
        row["num_attention_heads"], row["num_key_value_heads"],
        row["head_dim"], row["num_experts"], row["num_experts_per_tok"])
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (cfg.rope_theta, cfg.partial_rotary_factor, cfg.norm_eps,
            cfg.vocab_size) == (1e7, 0.25, 1e-6, 151936)
    assert (cfg.router_scoring, cfg.router_norm_eps, cfg.norm) == (
        "softmax", 0.0, "zero_centred_rms_norm")
    small = program_config(tiny_cfg())
    small.linear_num_value_heads = 3
    with pytest.raises(NotImplementedError, match="multiple of its key"):
        DecoderBlock(small, attention="gdn")
    with pytest.raises(NotImplementedError, match="no output gate"):
        fused = program_config(tiny_cfg())
        fused.fused_qkv = True
        fused.num_key_value_heads = None
        DecoderBlock(fused, attention="mha")


# ------------------------------------------------------- the compiled step

SCOPES = ("gdn_proj", "in_proj_qkvz", "in_proj_ba", "out_proj", "short_conv",
          "delta_rule", "gated_norm", "q_proj", "k_proj", "v_proj",
          "q_norm", "k_norm", "proj", "attention", "flash", "rope",
          "rms_norm", "router", "dispatch", "experts", "combine",
          "shared_expert", "shared_expert_gate", "lm_head", "head", "loss",
          "optimizer", "cast")
BUILD_COUNTERS = ("jit_gdn_layers", "jit_gqa_attention_layers",
                  "jit_moe_layers", "jit_moe_experts_held",
                  "jit_recompute_segments", "jit_flash_fused_backwards")


@pytest.fixture(scope="module")
def compiled():
    """At the flash gate's sequence, the kernels interpreted: the chip's
    branch of the gate is the one the cell runs."""
    from paddle_tpu.nn.functional import attention

    cfg = tiny_cfg()
    model, _w = build(cfg, recompute="kernels")
    cell = {"batch": 2, "seq": 128, "k": 2}
    step, _opt = _common.build_train_step(
        model, lambda ids, labels: model(ids, labels),
        dict(TRAINING, compute_dtype="bfloat16"), cell)
    arrays = [paddle.to_tensor(a) for a in _common.stack_steps(
        bench.make_batch, cfg, cell, SEED, 0, 2)]
    before = {c: monitor.stat_get(c) for c in BUILD_COUNTERS}
    stats = moe_mod.routing_stats()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "is_available", lambda: True)
        patch.setattr(fa, "flash_attention_bshd", functools.partial(
            fa.flash_attention_bshd, interpret=True))
        patch.setattr(attention, "_FLASH_MIN_SEQ", 128)
        patch.setattr(pmoe, "_BLOCK", 128)
        losses = [step(*arrays).numpy().ravel() for _call in range(2)]
    return {"step": step, "losses": np.concatenate(losses),
            "built": {c: monitor.stat_get(c) - v for c, v in before.items()},
            "routed": {k: v - stats[k]
                       for k, v in moe_mod.routing_stats().items()}}


def test_the_compiled_step_names_its_device_work(compiled):
    assert np.isfinite(compiled["losses"]).all()
    table = compiled["step"].scope_table()
    assert not table["stale"]
    paths = {rec["path"] for rec in table["instructions"].values()}
    for kind in SCOPES:
        assert any(kind in path.split("/") for path in paths), kind
    # the zero-centred q and k norms are `rms_norm`'s under their names
    assert any("q_norm" in p.split("/") and "rms_norm" in p.split("/")
               for p in paths)
    backward = {rec["path"] for rec in table["instructions"].values()
                if rec["backward"]}
    for kind in ("delta_rule", "short_conv", "gdn_proj", "gated_norm"):
        assert any(kind in p.split("/") for p in backward), kind
    assert "rematted_computation" in compiled["step"].hlo_text()


def test_the_compiled_step_counts_its_layers_once(compiled):
    # three Gated DeltaNet layers, one grouped attention (its backward
    # the fused kernel, once), four expert layers of four held experts,
    # four layers recomputed; 2 calls x 2 steps
    assert compiled["built"] == {
        "jit_gdn_layers": 3, "jit_gqa_attention_layers": 1,
        "jit_moe_layers": 4, "jit_moe_experts_held": 16,
        "jit_recompute_segments": 4, "jit_flash_fused_backwards": 1}
    routed = compiled["routed"]
    assert routed["moe_steps"] == 4 * 4
    tokens = 2 * 128
    mean = routed["moe_routed_pairs"] / routed["moe_steps"]
    # 4 of 16 experts a token, 4 of them held: one pair a token expected
    assert 0.5 * tokens < mean < 1.6 * tokens
    assert routed["moe_rows_worked"] >= routed["moe_routed_pairs"]


# ------------------------------------------------- the configuration file

def test_the_configuration_file_ties_to_the_model_and_the_published_one():
    cfg = published()
    assert cfg["source"] == SOURCE
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    # every key of the published config, under its own name; the reduced
    # ones aside, at the published value (no width moved)
    for key, value in PUBLISHED.items():
        assert key in cfg, key
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    assert bench.layer_types(cfg) == ["linear_attention"] * 3 + [
        "full_attention"]
    pub, share = cfg["published"], cfg["deployment"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 512, 151936)
    assert cfg["num_experts"] * share["ep_size"] == 512
    assert cfg["vocab_size"] * share["vocab_shards"] == 151936
    # the counts the file states are the weight table's
    shapes = bench.weight_shapes(cfg)
    size = lambda *keys: sum(int(np.prod(shapes[k][0]))  # noqa: E731
                             for k in keys)
    per = pub["per_layer"]
    assert per["gated_deltanet"] == size(*(f"l0_{k}" for k in (
        "qkvz", "ba", "conv", "A_log", "dt_bias", "gdn_norm", "out")))
    assert per["attention"] == size(*(f"l3_{k}" for k in (
        "q", "k", "v", "q_norm", "k_norm", "o")))
    assert per["router"] == size("l1_router")
    assert per["shared_expert"] == size("l2_s_gate", "l2_s_up", "l2_s_down",
                                        "l2_s_expert_gate")
    assert per["routed_expert"] == size("l1_e_gate", "l1_e_up",
                                        "l1_e_down") // 32
    assert per["routed_experts_512"] == 512 * per["routed_expert"]
    assert pub["embedding"] == pub["head"] == 8 * size("embed") \
        == 8 * size("head")
    # the whole model, from the published counts: 80B-A3B
    layer = per["router"] + per["shared_expert"] + per["routed_experts_512"]
    whole = (36 * per["gated_deltanet"] + 12 * per["attention"] + 48 * layer
             + pub["embedding"] + pub["head"] + 97 * 2048)
    assert whole == pytest.approx(79.67e9, rel=1e-3)
    # the cut: 625.7 M parameters, 16 bytes each 9.32 GiB
    assert bench.parameter_count(cfg) == 625_667_136
    assert bench.parameter_count(cfg) * 16 / 2 ** 30 == pytest.approx(
        9.32, abs=0.01)
    assert bench.flops_per_token(cfg, 8192) == pytest.approx(1.3808e9,
                                                             rel=1e-4)
    calls = bench.attention_calls(cfg, {"batch": 4, "seq": 8192})
    assert (calls["calls_per_step"], calls["heads"], calls["head_dim"]) == (
        1, 16, 256)


def test_kernel_work_counts_by_hand_at_the_cell_s_shape():
    cfg = published()
    cell = {"batch": 4, "seq": 8192}
    work = bench.kernel_work(cfg, cell, 327680)
    tokens = 4 * 8192
    assert work["flash"]["flops"] == 3 * 2 * 2 * 4 * 16 * 8192 * 8192 * 256 \
        // 2
    # q, o, do, dq at 16 heads of 256 and k, v, dk, dv at 2, six passes
    assert work["flash"]["bytes"] == 6 * tokens * 256 * 2 * (16 + 2)
    assert work["experts"]["flops"] == 18 * 2048 * 512 * 327680
    # four layers: 32 experts' three matrices twice read and once written
    assert work["experts"]["bytes"] == (
        4 * 3 * 32 * 3 * 2048 * 512 * 2 + 327680 * 4 * 2048 * 2)
    # three layers: 18 x 128 x 128 a value head a token; q, k (2,048
    # each), v (4,096), a, b (32 each) in, out and back, o and do 4,096
    assert work["delta_rule"]["flops"] == 3 * tokens * 18 * 32 * 128 * 128
    assert work["delta_rule"]["bytes"] == 3 * tokens * 2 * (
        3 * (2048 + 2048 + 4096 + 64) + 2 * 4096)
    # 8,192 channels, 4 taps: 6 x 4 operations and 5 elements a channel
    assert work["short_conv"]["flops"] == 3 * tokens * 24 * 8192
    assert work["short_conv"]["bytes"] == 3 * tokens * 5 * 8192 * 2
    twice = bench.kernel_work(cfg, cell, 2 * 327680)
    assert twice["experts"]["flops"] == 2 * work["experts"]["flops"]
    assert (twice["flash"], twice["delta_rule"]) == (work["flash"],
                                                     work["delta_rule"])


def test_the_tiny_model_has_the_table_s_parameters():
    cfg = tiny_cfg()
    model, _w = build(cfg)
    shapes = bench.weight_shapes(cfg)
    names = bench.program_names(cfg)
    for pname, p in model.named_parameters():
        assert tuple(p.shape) == shapes[names[pname][0]][0], pname
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) == \
        bench.parameter_count(cfg)
