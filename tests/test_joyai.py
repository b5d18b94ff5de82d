"""JoyAI-LLM-Flash on the normal path, at a small size on the CPU: the
program's loss, every gradient leaf and two AdamW steps against the
plain reference (chipbench/reference/joyai.py, which imports nothing of
paddle_tpu); the expert shares tied to the uncut layer; no pair dropped
at either extreme of routing or under a selection bias; the flash
kernels with values narrower than keys; the pair form of the rotary
embedding; the compiled step's scopes and counters; the configuration
file tied to the model."""
import functools
import json
import os
import re
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
from paddle_tpu.models.joyai import (JoyAIFlashConfig,  # noqa: E402
                                     JoyAIFlashForCausalLM)
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.parallel import moe as pmoe  # noqa: E402
from paddle_tpu.parallel.moe import held_experts_ffn  # noqa: E402

from chipbench.models import _common, joyai as bench  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402
from chipbench.reference import joyai as ref  # noqa: E402

SEED = 2_147_483_659
TRAINING = {"optimizer": "adamw", "learning_rate": 3e-4, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
            "param_dtype": "float32", "compute_dtype": "float32",
            "mtp_loss_weight": 0.3}
CELL = {"batch": 2, "seq": 32}
CONFIG_FILE = os.path.join(ROOT, "chipbench", "configs",
                           "joyai_flash_ep16_d5.json")


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def tiny_cfg(ep_rank=1, ep_size=4, **more):
    """config.json's keys at test widths: one dense layer and two expert
    layers, 16 experts of which this share holds 4, 4 a token."""
    cfg = published()
    cfg.update(vocab_size=512, hidden_size=64, intermediate_size=176,
               moe_intermediate_size=48, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, n_routed_experts=16 // ep_size,
               num_experts_per_tok=4, max_position_embeddings=64,
               training=TRAINING,
               deployment={"ep_size": ep_size, "ep_rank": ep_rank}, **more)
    return cfg


def build(cfg, recompute=None, bias=None):
    """(model, float32 seeded weights by the harness's keys)."""
    share = cfg["deployment"]
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig(
        **{k: cfg[k] for k in bench._CONFIG_KEYS},
        n_routed_experts=cfg["n_routed_experts"] * share["ep_size"],
        ep_size=share["ep_size"], ep_rank=share["ep_rank"]))
    weights = _common.init_weights(bench.weight_shapes(cfg), 0.02, SEED,
                                   "float32")
    # a router that spreads its scores, so that the choice is no tie
    weights = {k: v * 8.0 if k.endswith("router") else v
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
    cfg = tiny_cfg(**({} if bias is None
                      else {"e_score_correction_bias": bias}))
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


# --------------------------------------------- the share and the whole layer

def _layer_inputs(tokens=48, width=32, experts=16, hidden=24, seed=3):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=1.0: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {"x": f32(tokens, width), "router": f32(width, experts),
            "gate": f32(experts, width, hidden, scale=0.3),
            "up": f32(experts, width, hidden, scale=0.3),
            "down": f32(experts, hidden, width, scale=0.3)}


def _whole_layer(t, bias, top_k, scale, experts=range(16)):
    """The uncut routed sum, written from the equations: every chosen
    expert of every token (of `experts`: a share, written densely)."""
    s = jax.nn.sigmoid(jnp.dot(t["x"], t["router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s + bias, top_k)
    chosen = jax.nn.one_hot(sel, s.shape[1]).sum(1)
    g = scale * s * chosen / ((s * chosen).sum(-1, keepdims=True) + 1e-20)
    return sum(g[:, e:e + 1] * (
        (jax.nn.silu(t["x"] @ t["gate"][e]) * (t["x"] @ t["up"][e]))
        @ t["down"][e]) for e in experts)


def _share(t, bias, first, held, top_k=4, scale=2.5):
    cut = slice(first, first + held)
    return held_experts_ffn(
        t["x"], t["router"], bias, t["gate"][cut], t["up"][cut],
        t["down"][cut], top_k=top_k, first_expert=first, scale=scale)


def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's tie of the share to the model: the routed parts of
    all four shares (what every chip computes alike, a shared expert,
    would be counted once beside them) are the whole layer's sum."""
    t = _layer_inputs()
    bias = jnp.asarray(SPREAD, jnp.float32)
    parts = [_share(t, bias, first, 4) for first in (0, 4, 8, 12)]
    whole = _whole_layer(t, bias, 4, 2.5)
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, rtol=2e-5,
                               atol=2e-6)
    # every pair was routed to exactly one share
    assert sum(int(p[1]) for p in parts) == 48 * 4


@pytest.mark.parametrize("kind", ["all_held", "none_held", "spread"])
def test_no_pair_is_dropped_whatever_the_routing(kind):
    t = _layer_inputs()
    mine = (np.arange(16) >= 8) & (np.arange(16) < 12)
    bias = jnp.asarray({"all_held": np.where(mine, 10.0, 0.0),
                        "none_held": np.where(mine, -10.0, 0.0),
                        "spread": np.asarray(SPREAD)}[kind], jnp.float32)
    got, pairs, load, _folded = jax.jit(lambda: _share(t, bias, 8, 4))()
    units = _whole_layer(dict(t, gate=t["gate"].at[:8].set(0).at[12:].set(0)),
                         bias, 4, 2.5)  # the other experts' units give 0
    np.testing.assert_allclose(got, units, rtol=2e-5, atol=2e-6)
    expect = {"all_held": 48 * 4, "none_held": 0}.get(kind, int(pairs))
    assert int(pairs) == expect and int(load) <= int(pairs)
    if kind == "all_held":
        assert int(load) == 48  # every token chose each of the four


def _routing_bias(kind):
    """A selection bias for experts 8..11 held, and the pairs it routes
    there out of 48 tokens x 4 (None: whatever the scores give)."""
    mine = (np.arange(16) >= 8) & (np.arange(16) < 12)
    bias, pairs = {
        "all_held": (np.where(mine, 10.0, 0.0), 192),
        "none_held": (np.where(mine, -10.0, 0.0), 0),
        "spread": (np.asarray(SPREAD), None),
        # every token chooses expert 8 and three experts held elsewhere
        "one_block": (np.where(np.arange(16) == 8, 10.0,
                               np.where(mine, -10.0, 0.0)), 48),
        # ... experts 8 and 9: 96 pairs, not a multiple of 36
        "ragged_tail": (np.where((np.arange(16) == 8) | (np.arange(16) == 9),
                                 10.0, np.where(mine, -10.0, 0.0)), 96),
        # `FEW` tokens choose all four held experts and no other token
        # any (`_few_choose_every_held`): groups of five, so that a block
        # of 16 holds three or four groups of the same tokens
        "few_everywhere": (np.zeros(16), 4 * len(FEW)),
    }[kind]
    return jnp.asarray(bias, jnp.float32), pairs


FEW = (2, 11, 23, 30, 47)


def _folded_by_numpy(x, router, bias, first, held, top_k, block):
    """The routed pairs that share a block with an earlier pair of their
    token, counted from the routing in numpy: held pairs sorted by expert
    then token, in blocks of `block` slots."""
    score = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(
        router, np.float64))) + np.asarray(bias, np.float64)
    sel = np.argsort(-score, axis=1, kind="stable")[:, :top_k] - first
    token, expert = np.nonzero((sel[:, :, None] == np.arange(held)).any(1))
    slot = np.lexsort((token, expert))
    blocks = np.arange(len(slot)) // block
    return len(slot) - len(set(zip(blocks, token[slot])))


def _few_choose_every_held(t):
    """x's first coordinate +3 for the tokens `FEW` and -3 for the
    others, and held experts 8..11 scored by it alone, 8 times over:
    their scores are sigmoid(+-24), above or below every other's (the
    others' router columns scaled to width 32's spread)."""
    x0 = np.where(np.isin(np.arange(t["x"].shape[0]), FEW), 3.0, -3.0)
    held = np.zeros(t["router"].shape, np.float32)
    held[0, 8:12] = 8.0
    router = np.where(np.arange(16) // 4 == 2, held, t["router"] * np.sqrt(
        32 / t["x"].shape[1]))
    return dict(t, x=t["x"].at[:, 0].set(x0), router=jnp.asarray(router))


# (block, staged blocks): the 192 slots as 12 blocks of which the
# backward stages two at a time; as 4 blocks of 48 (the `one_block`
# routing fills exactly one) all staged at once; as blocks of 36, which
# do not divide them: one block, as at the module's own size
BLOCKINGS = {"b16x2": (16, 2), "b48x16": (48, 16), "b36_one": (36, 16)}


@pytest.mark.parametrize("blocking", list(BLOCKINGS))
@pytest.mark.parametrize("kind", ["all_held", "none_held", "spread",
                                  "one_block", "ragged_tail",
                                  "few_everywhere"])
def test_the_loops_gradients_are_the_dense_formulations(kind, blocking,
                                                        monkeypatch):
    """x, the router and the three weights: the hand-written backward
    over a run-time number of blocks against `jax.grad` of every token
    through every held expert, where a block's rows repeat tokens too
    (`all_held` as one block holds every token four times;
    `few_everywhere` in blocks of 16 three or four times)."""
    _loop_against_dense(kind, blocking, monkeypatch)


@pytest.mark.parametrize("kind,blocking", [
    ("all_held", "b36_one"), ("few_everywhere", "b16x2"),
    ("spread", "b48x16"), ("ragged_tail", "b16x2")])
def test_the_chip_s_row_kernel_adds_as_the_dense_formulations(
        kind, blocking, monkeypatch):
    """The same at width 128, where a row is a whole lane tile, on the
    chip's branch of the combine: the sums' rows gathered, added and
    written back by `kernels.put_rows` (interpreted)."""
    from paddle_tpu.kernels import put_rows

    monkeypatch.setattr(put_rows, "is_available", lambda: True)
    monkeypatch.setattr(put_rows, "put_rows", functools.partial(
        put_rows.put_rows, interpret=True))
    _loop_against_dense(kind, blocking, monkeypatch, width=128)


def _loop_against_dense(kind, blocking, monkeypatch, width=32):
    block, staged = BLOCKINGS[blocking]
    monkeypatch.setattr(pmoe, "_BLOCK", block)
    monkeypatch.setattr(pmoe, "_STAGED", staged)
    # the chip's grouped product leaves the rows past its groups
    # unwritten (XLA:CPU zeroes them): here they come back as NaN, so a
    # pass that let one into a sum would show it
    plain = jax.lax.ragged_dot

    def unwritten_past_the_groups(lhs, rhs, group_sizes, **kw):
        out = plain(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten_past_the_groups)
    t = _layer_inputs(width=width)
    if kind == "few_everywhere":
        t = _few_choose_every_held(t)
    bias, pairs = _routing_bias(kind)
    weight = jnp.asarray(np.random.default_rng(9).normal(size=t["x"].shape),
                         jnp.float32)
    keys = ("x", "router", "gate", "up", "down")

    def loop(*leaves):
        y, routed, _load, folded = _share(dict(zip(keys, leaves)), bias,
                                          8, 4)
        return jnp.sum(y * weight), (routed, folded)

    def dense(*leaves):
        return jnp.sum(_whole_layer(dict(zip(keys, leaves)), bias, 4, 2.5,
                                    experts=range(8, 12)) * weight)

    leaves = [t[k] for k in keys]
    (value, (routed, folded)), got = jax.jit(jax.value_and_grad(
        loop, argnums=range(5), has_aux=True))(*leaves)
    want_value, want = jax.value_and_grad(dense, argnums=range(5))(*leaves)
    if pairs is not None:
        assert int(routed) == pairs
    one = 192 if 192 % block else block
    assert int(pmoe.rows_worked(routed, 192)) == one * -(-int(routed) // one)
    assert int(folded) == _folded_by_numpy(t["x"], t["router"], bias, 8, 4,
                                           4, one)
    if (kind, blocking) in (("all_held", "b36_one"),
                            ("few_everywhere", "b16x2")):
        assert int(folded) == {"all_held": 144, "few_everywhere": 11}[kind]
    np.testing.assert_allclose(value, want_value, rtol=2e-5, atol=2e-5)
    for key, g, w in zip(keys, got, want):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.abs(w).max() + 1e-30),
            err_msg=key)
    if kind == "none_held":
        assert all(float(jnp.abs(g).max()) == 0 for g in got)


@pytest.mark.parametrize("block", [16, 1024], ids=["b16", "one_block"])
def test_the_layer_counts_on_the_device_and_routing_stats_fetches(
        block, monkeypatch):
    monkeypatch.setattr(pmoe, "_BLOCK", block)
    paddle.seed(5)
    layer = moe_mod.HeldExpertsLayer(32, 24, 16, 4, ep_size=4, ep_rank=2,
                                     routed_scaling_factor=2.5)
    before = moe_mod.routing_stats()
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 24, 32).astype(
        "float32"))
    for _ in range(3):
        layer(x)
    after = moe_mod.routing_stats()
    assert after["moe_steps"] - before["moe_steps"] == 3
    pairs = after["moe_routed_pairs"] - before["moe_routed_pairs"]
    assert pairs == int(layer.routed_pairs.numpy()) > 0
    assert monitor.stat_get("moe_routed_pairs") == after["moe_routed_pairs"]
    assert (after["moe_expert_load_max"] - before["moe_expert_load_max"]
            >= pairs / 4)
    # the same input three times: the live blocks' rows of one
    # application, 192 slots in blocks of 16 or (1,024 does not divide
    # them) in one
    one = 16 if block == 16 else 192
    worked = after["moe_rows_worked"] - before["moe_rows_worked"]
    assert worked == 3 * one * -(-(pairs // 3) // one)
    assert worked == int(layer.rows_worked.numpy())
    assert monitor.stat_get("moe_rows_worked") == after["moe_rows_worked"]
    # the pairs summed into another row of their token before a block's
    # add, as numpy counts them from the same routing; then every token
    # routed to the four held experts (one block: 192 rows of 48
    # tokens) and none
    folded = after["moe_rows_folded"] - before["moe_rows_folded"]
    flat, router = x.numpy().reshape(48, 32), layer.router_weight.numpy()
    assert folded == 3 * _folded_by_numpy(flat, router, np.zeros(16), 8, 4,
                                          4, one)
    assert folded == int(layer.rows_folded.numpy())
    assert monitor.stat_get("moe_rows_folded") == after["moe_rows_folded"]
    for bias, want in ((10.0, {16: 0, 1024: 144}[block]), (-10.0, 0)):
        held = np.where(np.arange(16) // 4 == 2, bias, 0.0)
        layer.e_score_correction_bias = paddle.to_tensor(
            held.astype("float32"))
        start = moe_mod.routing_stats()["moe_rows_folded"]
        layer(x)
        assert want == _folded_by_numpy(flat, router, held, 8, 4, 4, one)
        assert moe_mod.routing_stats()["moe_rows_folded"] - start == want
    with pytest.raises(ValueError, match="ep_size"):
        moe_mod.HeldExpertsLayer(32, 24, 16, 4, ep_size=3)


# ------------------------------------------------- kernels and the rotation

def _attention_xla(q, k, v, causal):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        keep = jnp.tril(jnp.ones(scores.shape[-2:], bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("seq,causal", [(256, True), (200, True),
                                        (128, False)])
def test_flash_kernels_with_values_narrower_than_keys(seq, causal):
    rng = np.random.default_rng(seq)
    q, k = (jnp.asarray(rng.normal(size=(1, seq, 2, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, seq, 2, 16)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(1, seq, 2, 16)), jnp.float32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * weight)

    flash = functools.partial(fa.flash_attention_bshd, causal=causal,
                              interpret=True)
    plain = functools.partial(_attention_xla, causal=causal)
    out = flash(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, plain(q, k, v), rtol=2e-4, atol=2e-5)
    got = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


def test_equal_widths_lower_to_what_they_lowered_to():
    """The GPT cells' call shape, forward and the three gradients: the
    traced kernels (grid, block shapes, every operation of the two
    bodies) are the recorded text, source lines aside: the forward as
    before the widths could differ, the backward the one kernel PR 36
    recorded at equal widths and head counts."""
    def loss(q, k, v):
        return fa.flash_attention_bshd(q, k, v, causal=True).astype(
            jnp.float32).sum()

    x = jax.ShapeDtypeStruct((2, 2048, 16, 128), jnp.bfloat16)
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(
        jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, x, x)))
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "flash_gpt_call_shape.jaxpr.txt")) as f:
        assert text == f.read()


def test_the_entry_refuses_by_name_what_it_cannot_do():
    q = jnp.zeros((1, 128, 4, 16))
    with pytest.raises(NotImplementedError, match="grouped-query"):
        fa.flash_attention_bshd(q, q[:, :, :3], q[:, :, :3], interpret=True)
    with pytest.raises(NotImplementedError, match="s_q == s_k"):
        fa.flash_attention_bshd(q, jnp.zeros((1, 256, 4, 16)),
                                jnp.zeros((1, 256, 4, 16)), causal=True,
                                interpret=True)
    with pytest.raises(ValueError, match="last axis"):
        fa.flash_attention_bshd(q, q[..., :8], q, interpret=True)


def test_interleaved_rope_is_its_formula():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 6, 3, 8)).astype("float32")
    theta = 32e6
    got = F.rotary_embedding(paddle.to_tensor(x), theta=theta,
                             interleaved=True).numpy()
    want = np.empty_like(x)
    for pos in range(6):
        for i in range(4):
            a = pos * theta ** (-2 * i / 8)
            x1, x2 = x[:, pos, :, 2 * i], x[:, pos, :, 2 * i + 1]
            want[:, pos, :, 2 * i] = x1 * np.cos(a) - x2 * np.sin(a)
            want[:, pos, :, 2 * i + 1] = x2 * np.cos(a) + x1 * np.sin(a)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.rope_pairs(jnp.asarray(x[0]), theta)), want[0],
        rtol=1e-5, atol=1e-6)
    # the rotate-half form is another function of the same input
    half = F.rotary_embedding(paddle.to_tensor(x), theta=theta).numpy()
    assert np.abs(half - want).max() > 0.1


# ------------------------------------------------------- the compiled step

SCOPES = ("q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm",
          "kv_b_proj", "o_proj", "attention", "rope", "router", "dispatch",
          "experts", "combine", "shared_expert", "mtp", "head", "loss",
          "optimizer", "cast")
BUILD_COUNTERS = ("jit_moe_layers", "jit_moe_experts_held",
                  "jit_recompute_segments")


@pytest.fixture(scope="module")
def compiled():
    cfg = tiny_cfg()
    model, _w = build(cfg, recompute="kernels")
    cell = dict(CELL, k=2)
    step, _opt = _common.build_train_step(
        model, lambda ids, labels: model(ids, labels),
        dict(TRAINING, compute_dtype="bfloat16"), cell)
    arrays = [paddle.to_tensor(a) for a in _common.stack_steps(
        bench.make_batch, cfg, cell, SEED, 0, 2)]
    before = {c: monitor.stat_get(c) for c in BUILD_COUNTERS}
    stats = moe_mod.routing_stats()
    # 64 tokens x 4: four blocks of 64 slots, staged two at a time
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pmoe, "_BLOCK", 64)
        patch.setattr(pmoe, "_STAGED", 2)
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
    assert any("/mtp/" in "/" + p + "/" and "head" in p.split("/")
               and p.split("/").index("mtp") < p.split("/").index("head")
               for p in paths)
    backward = {rec["path"] for rec in table["instructions"].values()
                if rec["backward"]}
    assert any("experts" in p.split("/") for p in backward)
    assert "rematted_computation" in compiled["step"].hlo_text()


def test_the_compiled_step_counts_once_a_step_whatever_is_replayed(compiled):
    # three expert layers (two of the stack, the MTP module's), four
    # layers recomputed; 2 calls x 2 steps
    assert compiled["built"] == {"jit_moe_layers": 3,
                                 "jit_moe_experts_held": 12,
                                 "jit_recompute_segments": 4}
    routed = compiled["routed"]
    assert routed["moe_steps"] == 3 * 4
    tokens = CELL["batch"] * CELL["seq"]
    mean = routed["moe_routed_pairs"] / routed["moe_steps"]
    assert 0.5 * tokens < mean < 1.6 * tokens  # expectation: 4 * 4 / 16 a token
    assert routed["moe_expert_load_max"] * 4 >= routed["moe_routed_pairs"]
    # the live blocks' rows (blocks of 64), once an application: replay
    # and backward run the loop again and count nothing
    worked = routed["moe_rows_worked"]
    assert worked % 64 == 0
    assert 0 <= worked - routed["moe_routed_pairs"] < 64 * routed["moe_steps"]


# ------------------------------------------------- the configuration file

def test_the_configuration_file_ties_to_the_model_and_the_catalog():
    cfg = published()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16160)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (40, 256, 129280)
    share = cfg["deployment"]
    assert cfg["n_routed_experts"] * share["ep_size"] == 256
    assert cfg["vocab_size"] * share["vocab_shards"] == 129280
    # no width moved: the published values, as config.json has them
    widths = {"hidden_size": 2048, "intermediate_size": 7168,
              "moe_intermediate_size": 768, "num_attention_heads": 32,
              "q_lora_rank": 1536, "kv_lora_rank": 512, "head_dim": 64,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "qk_head_dim": 192, "v_head_dim": 128,
              "num_experts_per_tok": 8, "n_shared_experts": 1,
              "routed_scaling_factor": 2.5, "ep_size": 1}
    assert {k: cfg[k] for k in widths} == widths
    # the counts the file states are the weight table's
    shapes = bench.weight_shapes(cfg)
    size = lambda key: int(np.prod(shapes[key][0]))  # noqa: E731
    per = pub["per_layer"]
    assert per["routed_expert"] == (size("mtp_e_gate") + size("mtp_e_up")
                                    + size("mtp_e_down")) // 16
    assert per["routed_experts_256"] == 256 * per["routed_expert"]
    assert per["latent_attention"] == sum(
        size("l0_" + k) for k in ("q_a", "q_a_norm", "q_b", "kv_a",
                                  "kv_a_norm", "kv_b", "o"))
    assert per["dense_layer_0_ffn"] == 3 * size("l0_gate")
    assert per["router"] == size("mtp_router")
    assert pub["embedding_and_head"] == 8 * (size("embed") + size("head"))
    assert bench.parameter_count(cfg) == 680_439_808
    # 6 x 314.7 M matmul parameters a token and attention at 4,096
    assert bench.flops_per_token(cfg, 4096) == pytest.approx(2.643e9,
                                                             rel=1e-3)
    calls = bench.attention_calls(cfg, {"batch": 4, "seq": 4096})
    assert (calls["calls_per_step"], calls["heads"], calls["head_dim"]) == (
        6, 32, 160)
    work = bench.kernel_work(cfg, {"batch": 4, "seq": 4096}, 40960)
    assert work["flash"]["flops"] == 6 * 3 * 2 * 4 * 32 * 4096 ** 2 * 160
    assert work["experts"]["flops"] == 18 * 2048 * 768 * 40960


def test_the_tiny_model_has_the_table_s_parameters():
    cfg = tiny_cfg()
    model, _w = build(cfg)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == bench.parameter_count(cfg)
    with pytest.raises(NotImplementedError, match="n_group"):
        JoyAIFlashConfig(n_group=8)
    with pytest.raises(NotImplementedError, match="scoring_func"):
        JoyAIFlashConfig(scoring_func="softmax")
