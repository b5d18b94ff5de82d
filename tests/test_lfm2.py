"""LFM2-24B-A2B on the normal path, at a small size on the CPU: the
program's loss, every gradient leaf and two AdamW steps against the
plain reference (chipbench/reference/lfm2.py, which imports nothing of
paddle_tpu); the gated short convolution against a literal loop over
positions; grouped-query heads on XLA's path and in the interpreted
kernels; the router under a selection bias and at a tie; the eight
expert shares tied to the uncut layer; what the configuration class
refuses; the compiled step's scopes and counters; the configuration
file tied to the model and the catalog."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.incubate import moe as moe_mod  # noqa: E402
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.kernels import short_conv as conv_kernel  # noqa: E402
from paddle_tpu.models.decoder import DecoderBlock  # noqa: E402
from paddle_tpu.models.lfm2 import (Lfm2MoeConfig,  # noqa: E402
                                    Lfm2MoeForCausalLM)
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.parallel import moe as pmoe  # noqa: E402
from paddle_tpu.parallel.moe import held_experts_ffn  # noqa: E402

from chipbench.models import _common, lfm2 as bench  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402
from chipbench.reference import lfm2 as ref  # noqa: E402

SEED = 2_147_483_659
TRAINING = {"optimizer": "adamw", "learning_rate": 3e-4, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
            "param_dtype": "float32", "compute_dtype": "float32"}
CELL = {"batch": 2, "seq": 32}
CONFIG_FILE = os.path.join(ROOT, "chipbench", "configs",
                           "lfm2_24b_ep8_d5.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny_cfg(ep_rank=1, ep_size=4, **more):
    """config.json's keys at test widths: the cell's five layers, 8
    query heads of 8 over 2 key/value heads, 16 experts of which this
    share holds 4, 4 a token."""
    cfg = published()
    cfg.update(vocab_size=512, hidden_size=64, intermediate_size=176,
               moe_intermediate_size=48, num_attention_heads=8,
               num_key_value_heads=2, num_experts=16 // ep_size,
               max_position_embeddings=64, training=TRAINING,
               deployment={"ep_size": ep_size, "ep_rank": ep_rank}, **more)
    return cfg


def build(cfg, recompute=None, bias=None):
    """(model, float32 seeded weights by the harness's keys)."""
    share = cfg["deployment"]
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        **{k: cfg[k] for k in bench._CONFIG_KEYS},
        num_experts=cfg["num_experts"] * share["ep_size"],
        ep_size=share["ep_size"], ep_rank=share["ep_rank"]))
    weights = _common.init_weights(bench.weight_shapes(cfg), 0.02, SEED,
                                   "float32")
    # a router that spreads its scores, so that the choice is no tie,
    # and taps of the other factors' size
    weights = {k: v * 8.0 if k.endswith(("router", "conv_taps")) else v
               for k, v in weights.items()}
    _common.set_program_weights(model, bench.program_names(cfg), weights)
    if bias is not None:
        for layer in model.sublayers():
            if isinstance(layer, moe_mod.HeldExpertsLayer):
                layer.e_score_correction_bias.set_value(
                    np.asarray(bias, "float32"))
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


# ------------------------------------------- the program and the reference

SPREAD = [0.3 * ((7 * i) % 16 - 8) / 8 for i in range(16)]


@pytest.mark.parametrize("bias", [None, SPREAD], ids=["b0", "b_nonzero"])
def test_loss_and_every_gradient_leaf_match_the_reference(bias):
    cfg = tiny_cfg(**({} if bias is None else {"expert_bias": bias}))
    model, weights = build(cfg, bias=bias)
    assert set(bench.program_names(cfg)) == {
        n for n, _p in model.named_parameters()}
    loss, grads = program_grads(model, cfg)
    ids, labels = batch(cfg, 0)
    want_loss, want = jax.value_and_grad(ref.loss_fn)(
        weights, (jnp.asarray(ids), jnp.asarray(labels)), cfg=cfg)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    assert set(grads) == set(want)
    assert all(np.abs(np.asarray(g)).max() > 0 for g in want.values())
    # float32 on both sides, summed in another order
    assert worst_gap(grads, want) < 2e-5


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


def test_the_logits_are_the_tied_embedding_s():
    cfg = tiny_cfg()
    model, weights = build(cfg)
    ids, _labels = batch(cfg, 0)
    logits = model(paddle.to_tensor(ids)).numpy()
    assert logits.shape == (2, 32, 512)
    assert "lm_head.weight" not in dict(model.named_parameters())
    # the loss is the mean cross-entropy of the shifted positions
    logp = jax.nn.log_softmax(jnp.asarray(logits[:, :-1]), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.asarray(ids[:, 1:])[..., None],
                                 axis=-1)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(ids))
    assert float(loss) == pytest.approx(float(-picked.mean()), rel=1e-5)


# ------------------------------------------------ the gated short convolution

def _conv_by_positions(u, taps):
    """The equations, a position and a batch row at a time."""
    u, taps = np.asarray(u, np.float64), np.asarray(taps, np.float64)
    rows, seq, h = u.shape[0], u.shape[1], taps.shape[0]
    length = taps.shape[1]
    out = np.zeros((rows, seq, h))
    for r in range(rows):
        g = u[r, :, :h] * u[r, :, 2 * h:]
        for t in range(seq):
            mixed = np.zeros(h)
            for j in range(length):
                back = length - 1 - j
                if t - back >= 0:
                    mixed += taps[:, j] * g[t - back]
            out[r, t] = u[r, t, h:2 * h] * mixed
    return out


@pytest.mark.parametrize("seq,length", [(9, 3), (2, 3), (1, 3), (7, 4)])
def test_short_conv_is_its_loop_over_positions(seq, length):
    rng = np.random.default_rng(seq * 10 + length)
    u = rng.normal(size=(3, seq, 3 * 8)).astype("float32")
    taps = rng.normal(size=(8, length)).astype("float32")
    got = F.gated_short_conv(paddle.to_tensor(u),
                             paddle.to_tensor(taps)).numpy()
    want = _conv_by_positions(u, taps)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the first positions see no past: position 0 is the last tap alone
    np.testing.assert_allclose(
        got[:, 0], u[:, 0, 8:16] * taps[:, -1] * u[:, 0, :8] * u[:, 0, 16:],
        rtol=1e-5, atol=1e-6)


def test_short_conv_keeps_batch_rows_and_the_future_apart():
    rng = np.random.default_rng(5)
    u = rng.normal(size=(2, 6, 12)).astype("float32")
    taps = rng.normal(size=(4, 3)).astype("float32")
    run = lambda v: F.gated_short_conv(  # noqa: E731
        paddle.to_tensor(v), paddle.to_tensor(taps)).numpy()
    base = run(u)
    other = u.copy()
    other[1] += 1.0  # another second row: the first must not move
    np.testing.assert_array_equal(run(other)[0], base[0])
    later = u.copy()
    later[:, 4:] += 1.0  # another future: positions 0..3 must not move
    np.testing.assert_array_equal(run(later)[:, :4], base[:, :4])
    assert np.abs(run(later)[:, 4:] - base[:, 4:]).max() > 0.1


def test_short_conv_gradients_and_dtypes():
    rng = np.random.default_rng(6)
    u = jnp.asarray(rng.normal(size=(2, 10, 24)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(8, 3)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(2, 10, 8)), jnp.float32)
    pu = paddle.to_tensor(np.asarray(u), stop_gradient=False)
    pt = paddle.to_tensor(np.asarray(taps), stop_gradient=False)
    (F.gated_short_conv(pu, pt) * paddle.to_tensor(np.asarray(weight))
     ).sum().backward()

    def by_rows(u, taps):  # the reference's form, without its projections
        eye = jnp.eye(8, dtype=jnp.float32)
        p = {"conv_in": jnp.eye(24, dtype=jnp.float32), "conv_taps": taps,
             "conv_out": eye}
        return jnp.sum(jax.vmap(lambda r: ref.short_conv("float32", r, p))(u)
                       * weight)

    du, dtaps = jax.grad(by_rows, argnums=(0, 1))(u, taps)
    np.testing.assert_allclose(pu.grad.numpy(), du, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(pt.grad.numpy(), dtaps, rtol=2e-5, atol=2e-5)
    # the sums are float32 whatever the input is; the result is the input's
    low = F.gated_short_conv(paddle.to_tensor(np.asarray(u)).astype(
        "bfloat16"), paddle.to_tensor(np.asarray(taps)).astype("bfloat16"))
    assert low.dtype == paddle.bfloat16
    rounded = _conv_by_positions(
        np.asarray(u.astype(jnp.bfloat16).astype(jnp.float32)),
        np.asarray(taps.astype(jnp.bfloat16).astype(jnp.float32)))
    np.testing.assert_allclose(low.astype("float32").numpy(), rounded,
                               rtol=1e-2, atol=1e-2)
    with pytest.raises(ValueError, match="three"):
        F.gated_short_conv(paddle.to_tensor(np.zeros((1, 4, 16), "float32")),
                           paddle.to_tensor(np.zeros((8, 3), "float32")))


# (batch, seq, channels, taps): two blocks of positions, channels in
# chunks of 128 (640) and of 512 (1024), a fourth tap, a single tap
KERNEL_SHAPES = [(2, 256, 128, 3), (1, 128, 640, 3), (1, 256, 1024, 3),
                 (2, 384, 128, 4), (1, 128, 128, 1)]


@pytest.mark.parametrize("shape", KERNEL_SHAPES,
                         ids=["x".join(map(str, s)) for s in KERNEL_SHAPES])
def test_short_conv_kernels_are_the_xla_form(shape, monkeypatch):
    """The interpreted pallas kernels, values and both gradients,
    against the shifted multiply-adds XLA runs elsewhere: across block
    boundaries, at the first and the last block, nothing across rows."""
    rows, seq, width, length = shape
    rng = np.random.default_rng(seq + width)
    u = rng.normal(size=(rows, seq, 3 * width)).astype("float32")
    taps = rng.normal(size=(width, length)).astype("float32")
    weight = rng.normal(size=(rows, seq, width)).astype("float32")
    assert conv_kernel.supports(u.shape, taps.shape)

    def run():
        pu = paddle.to_tensor(u, stop_gradient=False)
        pt = paddle.to_tensor(taps, stop_gradient=False)
        out = F.gated_short_conv(pu, pt)
        (out * paddle.to_tensor(weight)).sum().backward()
        return out.numpy(), pu.grad.numpy(), pt.grad.numpy()

    want = run()  # no TPU here: XLA's path
    monkeypatch.setattr(conv_kernel, "is_available", lambda: True)
    monkeypatch.setattr(conv_kernel, "short_conv", functools.partial(
        conv_kernel.short_conv, interpret=True))
    got = run()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0], _conv_by_positions(u, taps),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(got[2], want[2], rtol=2e-4, atol=2e-3)
    assert not conv_kernel.supports((1, 100, 384), (128, 3))
    assert not conv_kernel.supports((1, 128, 192), (64, 3))


# ----------------------------------------------------- grouped-query heads

def _attention_by_heads(q, k, v, causal):
    """Query head i against key/value head i // group, written with K
    and V copied out to the query heads."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        keep = jnp.tril(jnp.ones(scores.shape[-2:], bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _qkv(seq, heads, kv_heads, width, batch=1):
    rng = np.random.default_rng(seq + heads)
    make = lambda n: jnp.asarray(  # noqa: E731
        rng.normal(size=(batch, seq, n, width)), jnp.float32)
    return make(heads), make(kv_heads), make(kv_heads), make(heads)


# (seq, query heads, key/value heads, causal): LFM2's 4 : 1 at width 64,
# a padded tail, one key/value head for all, and no mask at all
GROUPED = [(256, 8, 2, True), (200, 4, 1, True), (1024, 8, 2, True),
           (128, 8, 4, False)]


@pytest.mark.parametrize("seq,heads,kv_heads,causal", GROUPED)
def test_flash_kernels_with_grouped_query_heads(seq, heads, kv_heads, causal):
    q, k, v, weight = _qkv(seq, heads, kv_heads, 64)
    flash = functools.partial(fa.flash_attention_bshd, causal=causal,
                              interpret=True)
    plain = functools.partial(_attention_by_heads, causal=causal)
    out = flash(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, plain(q, k, v), rtol=2e-4, atol=2e-5)
    loss = lambda fn, *a: jnp.sum(fn(*a) * weight)  # noqa: E731
    got = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape  # dk, dv at the key/value heads
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


def test_the_kernels_never_see_k_and_v_at_the_query_heads():
    """Every operand of the two `pallas_call`s (the forward, the one
    backward kernel) that is a K, a V or one of their gradients has 2
    heads' rows, not 8's."""
    q, k, v, _w = _qkv(256, 8, 2, 64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_bshd(*a, causal=True).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    calls = [line for line in text.splitlines() if "pallas_call[" in line]
    assert len(calls) == 2
    assert "repeat" not in text and "broadcast_in_dim[shape=(1, 256, 8" \
        not in text
    # the backward: grid (key/value heads, the group, key blocks), dk and
    # dv out at 2 heads
    assert any("f32[2,256,64]" in line for line in calls)
    assert "GridMapping(grid=(2, 4, 1)" in text


@pytest.mark.parametrize("mask", [None, "bool"])
@pytest.mark.parametrize("causal", [True, False])
def test_xla_attention_with_grouped_query_heads(causal, mask):
    q, k, v, weight = _qkv(24, 8, 2, 16, batch=2)
    attn_mask = None
    if mask:
        attn_mask = np.random.default_rng(1).random((2, 8, 24, 24)) > 0.2
        attn_mask |= np.eye(24, dtype=bool)
    tensors = [paddle.to_tensor(np.asarray(t), stop_gradient=False)
               for t in (q, k, v)]
    out = F.scaled_dot_product_attention(
        *tensors, is_causal=causal,
        attn_mask=None if mask is None else paddle.to_tensor(attn_mask))
    (out * paddle.to_tensor(np.asarray(weight))).sum().backward()

    def plain(q, k, v):
        group = q.shape[2] // k.shape[2]
        kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
        keep = jnp.ones(scores.shape, bool)
        if causal:
            keep &= jnp.tril(jnp.ones(scores.shape[-2:], bool))
        if attn_mask is not None:
            keep &= jnp.asarray(attn_mask)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)

    np.testing.assert_allclose(out.numpy(), plain(q, k, v), rtol=2e-5,
                               atol=2e-6)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight),
                    argnums=(0, 1, 2))(q, k, v)
    for t, w in zip(tensors, want):
        assert tuple(t.grad.shape) == w.shape
        np.testing.assert_allclose(t.grad.numpy(), w, rtol=2e-4, atol=2e-5)


def test_the_block_s_attention_is_the_reference_s():
    """q and k normed a head, rotated by halves, four query heads to a
    key/value head: one attention block against the reference's row."""
    cfg = tiny_cfg()
    model, weights = build(cfg)
    block = model.model.layers[1]
    x = np.random.default_rng(2).normal(size=(2, 32, 64)).astype("float32")
    got = block._attention(block.ln1(paddle.to_tensor(x)), 2, 32).numpy()
    p = {k: jnp.asarray(weights[f"l1_{k}"]) for k in ref.ATTENTION}
    for row in range(2):
        u = ref.rms(jnp.asarray(x[row]), p["operator_norm"], cfg["norm_eps"])
        want = ref.grouped_attention("float32", u, p, cfg)
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-6)


# ------------------------------------------------------------ the routing

def _layer_inputs(tokens=48, width=32, experts=16, hidden=24, seed=3):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {"x": f32(tokens, width), "router": f32(width, experts),
            "e_gate": f32(experts, width, hidden, scale=0.3),
            "e_up": f32(experts, width, hidden, scale=0.3),
            "e_down": f32(experts, hidden, width, scale=0.3)}


def _uncut_layer(t, bias):
    """The reference's expert layer holding all 16 experts."""
    whole = {"num_experts": 16, "num_experts_per_tok": 4,
             "routed_scaling_factor": 1, "expert_bias": bias,
             "deployment": {"ep_size": 1, "ep_rank": 0}}
    return ref.routed_experts("float32", t["x"], t, whole)


def _share(t, bias, first, held):
    cut = slice(first, first + held)
    return held_experts_ffn(
        t["x"], t["router"], jnp.asarray(bias, jnp.float32),
        t["e_gate"][cut], t["e_up"][cut], t["e_down"][cut], top_k=4,
        first_expert=first, scale=1.0, norm_eps=1e-6)


@pytest.mark.parametrize("bias", [[0.0] * 16, SPREAD],
                         ids=["b0", "b_nonzero"])
def test_the_eight_shares_add_up_to_the_uncut_layer(bias):
    """The guide's tie of the share to the model: the routed results of
    all eight shares summed — there is no shared expert, nothing every
    chip computes alike, so nothing is counted twice — are the uncut
    reference's expert layer."""
    t = _layer_inputs()
    parts = [_share(t, bias, first, 2) for first in range(0, 16, 2)]
    np.testing.assert_allclose(sum(p[0] for p in parts),
                               _uncut_layer(t, bias), rtol=2e-5, atol=2e-6)
    # every pair was routed to exactly one share
    assert sum(int(p[1]) for p in parts) == 48 * 4
    if any(bias):  # the bias moved the selection, and only the selection
        plain = [_share(t, [0.0] * 16, first, 2) for first in range(0, 16, 2)]
        assert [int(p[1]) for p in parts] != [int(p[1]) for p in plain]


def test_the_norm_epsilon_is_the_layer_s_argument():
    t = _layer_inputs()
    # every score 1e-6: the chosen four's sum is of the epsilon's size
    t["x"] = jnp.ones_like(t["x"])
    t["router"] = jnp.full_like(t["router"], np.log(1e-6) / 32)
    lfm2 = _share(t, [0.0] * 16, 0, 4)[0]
    joyai = held_experts_ffn(
        t["x"], t["router"], jnp.zeros(16), t["e_gate"][:4], t["e_up"][:4],
        t["e_down"][:4], top_k=4, first_expert=0, scale=1.0)[0]
    assert float(jnp.abs(joyai).max()) > 1e-3
    # gates of 1e-6 / 4e-6 against 1e-6 / (4e-6 + 1e-6)
    np.testing.assert_allclose(lfm2, joyai * 0.8, rtol=1e-3, atol=1e-7)
    layer = moe_mod.HeldExpertsLayer(32, 24, 16, 4, ep_size=4, norm_eps=1e-6)
    assert layer.norm_eps == 1e-6
    assert moe_mod.HeldExpertsLayer(32, 24, 16, 4).norm_eps == 1e-20


def test_a_tie_goes_to_the_lower_index():
    """Every expert's score equal: each token's four are experts 0..3,
    in the program as in the reference."""
    t = _layer_inputs()
    t["router"] = jnp.tile(t["router"][:, :1], (1, 16))
    first, rest = _share(t, [0.0] * 16, 0, 4), _share(t, [0.0] * 16, 4, 12)
    assert (int(first[1]), int(rest[1])) == (48 * 4, 0)
    assert float(jnp.abs(rest[0]).max()) == 0.0
    np.testing.assert_allclose(first[0], _uncut_layer(t, [0.0] * 16),
                               rtol=2e-5, atol=2e-6)
    score = jax.nn.sigmoid(t["x"] @ t["router"][:, :1])
    units = sum(score / (4 * score + 1e-6)
                * (jax.nn.silu(t["x"] @ t["e_gate"][e])
                   * (t["x"] @ t["e_up"][e])) @ t["e_down"][e]
                for e in range(4))
    np.testing.assert_allclose(first[0], units, rtol=2e-4, atol=2e-5)
    # a bias lifts experts 12..15 over the tie
    lifted = [0.0] * 12 + [0.1] * 4
    assert int(_share(t, lifted, 12, 4)[1]) == 48 * 4


def test_the_backward_stages_the_usual_routing_s_blocks_once():
    """16 blocks for JoyAI's share (8 usual), as before; LFM2's share
    has 16 usual blocks of 1,024 and stages 32: a layer application
    whose routing runs a few per cent over still forms its weight
    gradients once."""
    assert pmoe._staged_blocks(16384 * 8, 1024, 16, 256) == 16
    assert pmoe._staged_blocks(32768 * 4, 1024, 8, 64) == 32
    assert pmoe._staged_blocks(192, 192, 4, 16) == 1  # one block in all
    assert pmoe._staged_blocks(2048 * 4, 1024, 8, 64) == 8  # never more
    # than the slots' blocks


def test_a_block_without_a_shared_expert_builds_none():
    cfg = tiny_cfg()
    model, _w = build(cfg)
    for i, layer in enumerate(model.model.layers):
        names = set(layer._sub_layers)
        assert "shared_expert" not in names
        assert ("moe" in names) == (i >= 1) and ("gate_proj" in names) == (i < 1)
        assert ("conv_in" in names) == (i != 1) == ("q_proj" not in names)
    assert model.model.layers[1].moe.norm_eps == 1e-6


# ------------------------------------------------- what the config refuses

@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("norm_topk_prob", False),
    ("use_expert_bias", False), ("tie_word_embeddings", False),
    ("model_type", "lfm2"),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("layer_types", ["conv", "sliding_attention"] * 20),
    ("layer_types", ["conv"] * 39), ("num_dense_layers", 41)])
def test_the_config_refuses_by_name_what_it_has_no_path_for(key, value):
    name = "rope_type" if key == "rope_parameters" else key
    with pytest.raises(NotImplementedError, match=name):
        Lfm2MoeConfig(**{key: value})


def test_the_config_s_defaults_are_the_published_model():
    cfg = Lfm2MoeConfig()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg.layer_types == row["config"]["layer_types"]
    assert (cfg.num_heads, cfg.num_key_value_heads, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.n_shared_experts) == (32, 8, 64, 4, 0)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.router_norm_eps) == (
        1e6, 1e-5, 1e-6)
    block_cfg = tiny_cfg()
    grouped = Lfm2MoeConfig(**{k: block_cfg[k] for k in bench._CONFIG_KEYS},
                            num_experts=16, ep_size=4)
    grouped.fused_qkv = True
    with pytest.raises(NotImplementedError, match="grouped-query"):
        DecoderBlock(grouped, attention="mha")
    with pytest.raises(ValueError, match="unknown attention"):
        DecoderBlock(grouped, attention="window")


# ------------------------------------------------------- the compiled step

SCOPES = ("conv_in", "short_conv", "conv_out", "q_proj", "k_proj", "v_proj",
          "q_norm", "k_norm", "proj", "attention", "flash", "rope",
          "rms_norm", "gate_proj", "up_proj", "down_proj", "router",
          "dispatch", "experts", "combine", "head", "loss", "optimizer",
          "cast")
BUILD_COUNTERS = ("jit_short_conv_layers", "jit_gqa_attention_layers",
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
    # the q and k norms' device time is `rms_norm`'s, under their names
    assert any("q_norm" in p.split("/") and "rms_norm" in p.split("/")
               for p in paths)
    backward = {rec["path"] for rec in table["instructions"].values()
                if rec["backward"]}
    assert any("short_conv" in p.split("/") for p in backward)
    assert "rematted_computation" in compiled["step"].hlo_text()


def test_the_compiled_step_counts_its_layers_once(compiled):
    # four operators, one grouped attention (its backward the fused
    # kernel, once, recomputed segment or not), four expert layers of
    # four held experts, five layers recomputed; 2 calls x 2 steps
    assert compiled["built"] == {
        "jit_short_conv_layers": 4, "jit_gqa_attention_layers": 1,
        "jit_moe_layers": 4, "jit_moe_experts_held": 16,
        "jit_recompute_segments": 5, "jit_flash_fused_backwards": 1}
    routed = compiled["routed"]
    assert routed["moe_steps"] == 4 * 4
    tokens = 2 * 128
    mean = routed["moe_routed_pairs"] / routed["moe_steps"]
    assert 0.5 * tokens < mean < 1.6 * tokens  # expectation: 4 * 4 / 16 a token
    assert routed["moe_rows_worked"] >= routed["moe_routed_pairs"]


# ------------------------------------------------- the configuration file

def test_the_configuration_file_ties_to_the_model_and_the_catalog():
    cfg = published()
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "num_experts", "vocab_size"]
    # every key of the catalog's config, under its own name; the reduced
    # ones aside, at the published value (no width moved)
    for key, value in row["config"].items():
        assert key in cfg, key
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == row["config"]["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 8192)
    pub, share = cfg["published"], cfg["deployment"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (40, 2, 64, 65536)
    assert cfg["num_experts"] * share["ep_size"] == 64
    assert cfg["vocab_size"] * share["vocab_shards"] == 65536
    # the counts the file states are the weight table's
    shapes = bench.weight_shapes(cfg)
    size = lambda *keys: sum(int(np.prod(shapes[k][0]))  # noqa: E731
                             for k in keys)
    per = pub["per_layer"]
    assert per["short_conv_operator"] == size("l0_conv_in", "l0_conv_taps",
                                              "l0_conv_out")
    assert per["attention"] == size("l1_q", "l1_k", "l1_v", "l1_o",
                                    "l1_q_norm", "l1_k_norm")
    assert per["dense_ffn"] == size("l0_gate", "l0_up", "l0_down")
    assert per["router"] == size("l1_router")
    assert per["routed_expert"] == size("l1_e_gate", "l1_e_up",
                                        "l1_e_down") // 8
    assert per["routed_experts_64"] == 64 * per["routed_expert"]
    assert pub["embedding"] == 8 * size("embed")
    assert "l0_router" not in shapes and "l1_gate" not in shapes
    # the whole model, from the published counts: 24B-A2B
    whole = (30 * per["short_conv_operator"] + 10 * per["attention"]
             + 2 * per["dense_ffn"] + 38 * (per["router"]
                                            + per["routed_experts_64"])
             + pub["embedding"] + 81 * 2048)
    assert whole == pytest.approx(23.84e9, rel=1e-3)
    assert bench.parameter_count(cfg) == 469_284_992
    # 6 x 186.1 M matmul parameters a token and attention at 8,192
    assert bench.flops_per_token(cfg, 8192) == pytest.approx(1.2174e9,
                                                             rel=1e-4)
    calls = bench.attention_calls(cfg, {"batch": 4, "seq": 8192})
    assert (calls["calls_per_step"], calls["heads"], calls["head_dim"]) == (
        1, 32, 64)


def test_kernel_work_counts_by_hand_at_the_cell_s_shape():
    cfg = published()
    cell = {"batch": 4, "seq": 8192}
    work = bench.kernel_work(cfg, cell, 32768)
    b, s = 4, 8192
    assert work["flash"]["flops"] == 3 * 2 * 2 * b * 32 * s * s * 64 // 2
    # q, o, do, dq at 32 heads and k, v, dk, dv at 8, six passes each
    assert work["flash"]["bytes"] == 6 * b * s * 64 * 2 * (32 + 8)
    assert work["experts"]["flops"] == 18 * 2048 * 1536 * 32768
    assert work["experts"]["bytes"] == (
        4 * 3 * 8 * 3 * 2048 * 1536 * 2 + 32768 * 4 * 2048 * 2)
    # 11 x 2048 elements a token an application, four applications
    assert work["short_conv"]["bytes"] == 11 * 2048 * 2 * b * s * 4
    assert work["short_conv"]["bytes"] / 819e9 == pytest.approx(7.2e-3,
                                                                rel=0.01)
    twice = bench.kernel_work(cfg, cell, 65536)
    assert twice["experts"]["flops"] == 2 * work["experts"]["flops"]
    assert (twice["flash"], twice["short_conv"]) == (work["flash"],
                                                     work["short_conv"])


def test_the_tiny_model_has_the_table_s_parameters():
    cfg = tiny_cfg()
    model, _w = build(cfg)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == bench.parameter_count(cfg)
    with open(ref.__file__) as f:
        assert "paddle_tpu" not in f.read()
