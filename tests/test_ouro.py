"""Ouro on the normal path, at a small size on the CPU: the program's
loss, every gradient leaf and two AdamW steps against the plain
reference (chipbench/reference/ouro.py, which imports nothing of
paddle_tpu); the rolled pass loop against the python loop; with and
without recomputation; T = 1 against the plain decoder; the decoder
block under GPTConfig unchanged; the depth cut tied to the model."""
import contextlib
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
from paddle_tpu import monitor, nn, ops  # noqa: E402
from paddle_tpu.models import gpt as gpt_mod  # noqa: E402
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402

from chipbench.models import _common, ouro as bench_ouro  # noqa: E402
from chipbench.reference import common as ref_common  # noqa: E402
from chipbench.reference import ouro as ref_ouro  # noqa: E402

SEED = 2_147_483_659
TRAINING = {"optimizer": "adamw", "learning_rate": 3e-4, "beta1": 0.9,
            "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1,
            "param_dtype": "float32", "compute_dtype": "float32",
            "exit_entropy_beta": 0.05}
CELL = {"batch": 2, "seq": 32}


def tiny_cfg(passes):
    """ISSUE 29's test size, under config.json's keys."""
    return {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 176,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 4, "head_dim": 16, "hidden_act": "silu",
            "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
            "rope_theta": 1000000, "tie_word_embeddings": False,
            "total_ut_steps": passes, "early_exit_threshold": 1,
            "initializer_range": 0.02, "training": TRAINING}


@contextlib.contextmanager
def pass_loop(rolled):
    """The model's pass loop forced rolled (one `lax.scan`, also
    eagerly) or unrolled (the python loop, also in a compiled step):
    the model itself rolls it exactly when a step is being compiled."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(nn, "fixed_loop",
                      functools.partial(nn.fixed_loop, rolled=rolled))
        yield


def build(passes, recompute=None):
    """(model, float32 seeded weights by the harness's keys, cfg)."""
    cfg = tiny_cfg(passes)
    model = OuroForCausalLM(OuroConfig(
        **{k: cfg[k] for k in bench_ouro._CONFIG_KEYS},
        exit_entropy_beta=TRAINING["exit_entropy_beta"]))
    weights = _common.init_weights(bench_ouro.weight_shapes(cfg), 0.02, SEED,
                                   "float32")
    # a gate that opens unevenly, so that its gradient is no accident
    weights["exit_w"] = weights["exit_w"] * 20.0
    weights["exit_b"] = weights["exit_b"] + 0.3
    _common.set_program_weights(model, bench_ouro.program_names(cfg), weights)
    if recompute:
        model.enable_layer_recompute(recompute)
    return model, weights, cfg


def batch(cfg, step):
    return bench_ouro.make_batch(cfg, CELL, SEED, step)


def program_grads(model, cfg, step=0):
    """Loss and {harness key: stacked gradient} of one eager step."""
    ids, labels = batch(cfg, step)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
    loss.backward()
    names = bench_ouro.program_names(cfg)
    grads = {}
    for pname, p in model.named_parameters():
        key, layer = names[pname]
        g = None if p.grad is None else np.asarray(p.grad.numpy())
        if layer is None:
            grads[key] = g
        else:
            grads.setdefault(key, {})[layer] = g
    model.clear_gradients()
    return float(loss), {k: (np.stack([v[i] for i in sorted(v)])
                             if isinstance(v, dict) else v)
                         for k, v in grads.items()}


def worst_gap(got, want):
    """The widest |got - want| over the largest |want| of its leaf. The
    gate's bias is left to its own line: one number, the sum over all
    tokens of terms that cancel (1.4e-4 beside its weights' 1.6e-2)."""
    return max(float(np.abs(got[k] - want[k]).max()
                     / max(np.abs(want[k]).max(), 1e-30))
               for k in want if k != "exit_b")


# ------------------------------------------- the program and the reference

@pytest.mark.parametrize("passes", [4, 1])
def test_loss_and_every_gradient_leaf_match_the_reference(passes):
    model, weights, cfg = build(passes)
    loss, grads = program_grads(model, cfg)
    ids, labels = batch(cfg, 0)
    want_loss, want = jax.value_and_grad(ref_ouro.loss_fn)(
        weights, (jnp.asarray(ids), jnp.asarray(labels)), cfg=cfg)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    assert set(grads) == set(want)
    if passes == 1:
        # one exit: the gate has no say
        for key in ("exit_w", "exit_b"):
            assert not grads.pop(key).any()
            assert not np.asarray(want.pop(key)).any()
    assert all(np.abs(np.asarray(g)).max() > 0 for g in want.values())
    # float32 on both sides, summed in another order
    assert worst_gap(grads, {k: np.asarray(v) for k, v in want.items()}) < 2e-5
    if passes > 1:
        assert grads["exit_b"] == pytest.approx(np.asarray(want["exit_b"]),
                                                rel=2e-3)


@pytest.mark.parametrize("passes", [4, 1])
def test_two_adamw_steps_match_the_reference(passes):
    model, weights, cfg = build(passes)
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
    # `train` keeps `reduce` of each tree: the identity keeps them whole
    out = ref_common.train(
        functools.partial(ref_ouro.loss_fn, cfg=cfg),
        lambda: {k: jnp.array(v) for k, v in weights.items()}, steps,
        TRAINING, lambda tree: tree)
    assert losses == pytest.approx(out["losses"], rel=2e-6)
    names = bench_ouro.program_names(cfg)
    change = out["change"]  # the weights after both steps minus the start
    for pname, p in model.named_parameters():
        key, layer = names[pname]
        start = np.asarray(weights[key] if layer is None
                           else weights[key][layer])
        want = np.asarray(change[key] if layer is None
                          else change[key][layer])
        got = np.asarray(p.numpy()) - start
        if passes == 1 and key in ("exit_w", "exit_b"):
            # a zero gradient: only the decay moves it
            np.testing.assert_allclose(got, want, atol=1e-9, err_msg=pname)
            continue
        # two sign-like steps of 3e-4: an element moves by ~6e-4, and one
        # whose gradient is near 0 (two of 11,264 in a leaf) by the
        # round-off's sign, so the leaf is held by its norm
        assert np.linalg.norm(got - want) < 3e-3 * np.linalg.norm(want), pname
        assert np.abs(want).max() > 1e-4, pname


# ----------------------------------------- rolled, unrolled and recomputed

@pytest.fixture(scope="module")
def unrolled():
    """The eager model: its pass loop is the python loop."""
    model, _w, cfg = build(4)
    return program_grads(model, cfg)


@pytest.mark.parametrize("recompute", [None, "full", "selective", "kernels"])
def test_the_rolled_loop_is_the_python_loop_to_round_off(recompute, unrolled):
    model, _w, cfg = build(4, recompute=recompute)
    with pass_loop(rolled=True):
        loss, grads = program_grads(model, cfg)
    assert loss == pytest.approx(unrolled[0], rel=1e-6)
    assert worst_gap(grads, unrolled[1]) < 5e-5


def test_recompute_in_the_python_loop_changes_nothing(unrolled):
    model, _w, cfg = build(4, recompute="full")
    loss, grads = program_grads(model, cfg)
    assert loss == pytest.approx(unrolled[0], rel=1e-6)
    assert worst_gap(grads, unrolled[1]) < 5e-5


def _compiled_step(recompute, k=2):
    model, _w, cfg = build(4, recompute=recompute)
    cell = dict(CELL, k=k)
    step, _opt = _common.build_train_step(
        model, lambda ids, labels: model(ids, labels),
        dict(TRAINING, compute_dtype="bfloat16"), cell)
    arrays = _common.stack_steps(bench_ouro.make_batch, cfg, cell, SEED, 0, k)
    before = {c: monitor.stat_get(c) for c in
              ("jit_rolled_loop_trips", "jit_recompute_segments")}
    losses = step(*[paddle.to_tensor(a) for a in arrays]).numpy().ravel()
    counted = {c: monitor.stat_get(c) - v for c, v in before.items()}
    return step, losses, counted


@pytest.fixture(scope="module")
def compiled():
    """The user's step (`build_train_step`: AMP, tape, AdamW,
    `to_static(scan_steps=2)`), rolled with a remat segment a layer, and
    its python-loop control without."""
    rolled = _compiled_step("full")
    with pass_loop(rolled=False):
        return {"rolled": rolled, "unrolled": _compiled_step(None)}


def test_the_compiled_step_rolls_the_passes_once(compiled):
    _step, losses, counted = compiled["rolled"]
    assert counted == {"jit_rolled_loop_trips": 4,
                       "jit_recompute_segments": 2}
    _step, control, counted = compiled["unrolled"]
    assert counted == {"jit_rolled_loop_trips": 0,
                       "jit_recompute_segments": 0}
    assert np.isfinite(losses).all()
    # bf16 compute on both sides, fused differently
    np.testing.assert_allclose(losses, control, rtol=2e-3)


def test_the_compiled_step_names_its_device_work(compiled):
    step = compiled["rolled"][0]
    hlo = step.hlo_text()
    for name in ("pt.loop", "pt.fixed_loop", "pt.rope", "pt.exit_gate",
                 "pt.exit_loss", "pt.head", "pt.loss", "pt.rms_norm",
                 "pt.attention", "pt.optimizer"):
        assert name + "/" in hlo or name + '"' in hlo, name
    # the replayed forward carries jax's mark, inside the loop's backward
    assert "rematted_computation" in hlo
    assert "rematted_computation" not in compiled["unrolled"][0].hlo_text()
    table = step.scope_table()
    assert not table["stale"]
    paths = {i["path"] for i in table["instructions"].values()}
    for kind in ("loop", "rope", "exit_gate", "exit_loss", "rms_norm"):
        assert any(kind in path.split("/") for path in paths), kind


# ----------------------------------------------- T = 1 is a plain decoder

def test_one_pass_is_the_plain_decoders_loss():
    model, _w, cfg = build(1)
    model.exit_gate.weight.set_value(
        np.zeros(model.exit_gate.weight.shape, "float32"))
    ids, labels = (paddle.to_tensor(a) for a in batch(cfg, 0))
    h = model.model.embed_tokens(ids)
    for layer in model.model.layers:
        h = layer(h)
    logits = model.lm_head(model.model.norm(h))
    plain = F.cross_entropy(ops.reshape(logits[:, :-1], [-1, 512]),
                            ops.reshape(labels[:, 1:], [-1]))
    assert float(model(ids, labels)) == pytest.approx(float(plain), rel=1e-6)
    z, g = model(ids)
    assert len(z) == len(g) == 1 and z[0].shape == [2, 32, 512]


def test_exit_probabilities_sum_to_one():
    gates = [paddle.to_tensor(np.random.RandomState(i).randn(7).astype(
        "float32") * 3) for i in range(4)]
    p = sum(np.exp(lp.numpy()) for lp in OuroForCausalLM.exit_log_probs(gates))
    np.testing.assert_allclose(p, np.ones(7), rtol=1e-6)
    lam = 1 / (1 + np.exp(-np.stack([g.numpy() for g in gates])))
    want = np.asarray(ref_ouro.exit_distribution(jnp.asarray(lam)))
    got = np.stack([np.exp(lp.numpy())
                    for lp in OuroForCausalLM.exit_log_probs(gates)])
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_grouped_query_heads_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="grouped-query"):
        OuroConfig(num_attention_heads=16, num_key_value_heads=4)


# ------------------------------------- the decoder block under GPTConfig

def test_the_gpt_block_keeps_its_names_and_its_operations():
    cfg = gpt_mod.GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=32, hidden_dropout=0.0,
                            attention_dropout=0.0)
    model = gpt_mod.GPTForCausalLM(cfg)
    block = ["ln1.weight", "ln1.bias", "qkv.weight", "qkv.bias",
             "proj.weight", "proj.bias", "ln2.weight", "ln2.bias",
             "fc1.weight", "fc1.bias", "fc2.weight", "fc2.bias"]
    assert [n for n, _p in model.named_parameters()] == (
        ["gpt.wte.weight", "gpt.wpe.weight"]
        + [f"gpt.blocks.{i}.{n}" for i in range(2) for n in block]
        + ["gpt.ln_f.weight", "gpt.ln_f.bias"])
    blk = model.gpt.blocks[0]
    assert type(blk) is gpt_mod.GPTBlock
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 32, 64).astype(
        "float32"))
    # the block as models/gpt.py wrote it before the decoder was shared
    h = blk.ln1(x)
    q, k, v = ops.unstack(ops.reshape(blk.qkv(h), [2, 32, 3, 4, 16]), axis=2)
    ctx = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    y = x + blk.proj(ops.reshape(ctx, [2, 32, 64]))
    y = y + blk.fc2(F.gelu(blk.fc1(blk.ln2(y))))
    assert (blk(x).numpy() == y.numpy()).all()


# ------------------------------------------------------ rotary positions

def test_rotary_embedding_against_the_formula():
    rng = np.random.RandomState(3)
    x = rng.randn(2, 6, 3, 8).astype("float32")
    got = F.rotary_embedding(paddle.to_tensor(x), theta=100.0).numpy()
    angle = np.arange(6)[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8)
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    want = np.concatenate([x[..., :4] * cos - x[..., 4:] * sin,
                           x[..., 4:] * cos + x[..., :4] * sin], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert (got[:, 0] == x[:, 0]).all()  # position 0 turns nothing
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref_ouro.rope(jnp.asarray(x), 100.0)), want, rtol=1e-5,
        atol=1e-6)
    # a score depends on the distance between positions alone
    shifted = F.rotary_embedding(paddle.to_tensor(x), theta=100.0,
                                 positions=paddle.to_tensor(
                                     np.arange(6, dtype="int32") + 11)).numpy()
    np.testing.assert_allclose(
        np.einsum("bqnd,bknd->bnqk", got, got),
        np.einsum("bqnd,bknd->bnqk", shifted, shifted), rtol=1e-4, atol=1e-4)
    bf = F.rotary_embedding(paddle.to_tensor(x).astype("bfloat16"),
                            theta=100.0)
    assert bf.dtype == paddle.bfloat16
    with pytest.raises(ValueError, match="even"):
        F.rotary_embedding(paddle.to_tensor(x[..., :7]))


# ------------------------------------------------------------ fixed_loop

def _loop_case(rolled):
    lin = nn.Linear(4, 4)
    lin.weight.set_value(np.eye(4, dtype="float32") * 0.5
                         + np.arange(16, dtype="float32").reshape(4, 4) / 50)
    x = paddle.to_tensor(np.arange(8, dtype="float32").reshape(2, 4) / 8,
                         stop_gradient=False)
    (ys,) = nn.fixed_loop(lambda h: F.tanh(lin(h)), [x], 3, rolled=rolled)
    ops.sum(ys * ys).backward()
    return ys.numpy(), x.grad.numpy(), lin.weight.grad.numpy(), \
        lin.bias.grad.numpy()


def test_fixed_loop_stacks_every_trip_and_sums_captured_gradients():
    eager, rolled = _loop_case(False), _loop_case(True)
    assert eager[0].shape == (3, 2, 4)
    for a, b in zip(eager, rolled):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # against jax, end to end
    w = jnp.eye(4) * 0.5 + jnp.arange(16.0).reshape(4, 4) / 50
    x = jnp.arange(8.0).reshape(2, 4) / 8

    def f(x, w, b):
        ys = []
        for _ in range(3):
            x = jnp.tanh(x @ w + b)
            ys.append(x)
        return jnp.sum(jnp.stack(ys) ** 2)

    want = jax.grad(f, argnums=(0, 1, 2))(x, w, jnp.zeros(4))
    for got, ref in zip(rolled[1:], want):
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-7)


def test_fixed_loop_refuses_what_it_cannot_roll():
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    with pytest.raises(TypeError, match="as it got them"):
        nn.fixed_loop(lambda h: ops.concat([h, h], axis=0), [x], 2,
                      rolled=True)
    with pytest.raises(TypeError, match="as it got them"):
        nn.fixed_loop(lambda h: ops.concat([h, h], axis=0), [x], 2)
    with pytest.raises(ValueError, match="trips"):
        nn.fixed_loop(lambda h: h, [x], 0)
    drop = nn.Dropout(0.5)
    with pytest.raises(RuntimeError, match="side effects"):
        nn.fixed_loop(lambda h: drop(h), [x], 2, rolled=True)
    (ys,) = nn.fixed_loop(lambda h: drop(h), [x], 2)  # the python loop may
    assert ys.shape == [2, 2, 4]
    with paddle.no_grad():
        (ys,) = nn.fixed_loop(lambda h: h * 2.0, [x], 3, rolled=True)
    assert (ys.numpy()[:, 0, 0] == [2.0, 4.0, 8.0]).all()


# ------------------------------------------- the depth cut and the model

def test_the_depth_cut_is_tied_to_the_model():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro_2p6b_d8.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert per_layer == 51_380_224 == bench_ouro.layer_matmul_params(cfg)
    assert bench_ouro.parameter_count(cfg) == (
        8 * (per_layer + 4 * 2048)      # the published layer, 8 of 48
        + 2 * 49_152 * 2048             # embedding and the untied head
        + 2048 + 2048 + 1)              # final norm, the gate and its bias
    assert bench_ouro.flops_per_token(cfg, 2048) == (
        6 * (4 * 8 * per_layer + 4 * 49_152 * 2048)
        + 6 * 32 * 2048 * 2048) == 13_086_228_480
    calls = bench_ouro.attention_calls(cfg, {"batch": 2, "seq": 2048})
    assert (calls["calls_per_step"], calls["heads"], calls["head_dim"]) == (
        32, 16, 128)
    # and the count is the program's, layer for layer, at the test size
    model, _w, tiny = build(4)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == bench_ouro.parameter_count(tiny)
    shapes = bench_ouro.weight_shapes(tiny)
    assert sum(int(np.prod(s)) for s, _kind in shapes.values()) \
        == bench_ouro.parameter_count(tiny)
