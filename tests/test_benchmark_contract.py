"""The names the benchmark's per-layer metrics take from the program.

A metric's data file under ``chipbench/metrics/`` (read here, never
changed) names a scope, a counter or an ``op_name`` mark; a renamed one
makes the metric read ``null`` on the chip and nothing fail here. One
case a file: a tiny step of each family its ``workloads`` name
(``BENCHMARK.json``), built through the public API, must still carry
that name. And the reader ``host_stat``'s three answers, a case each.
"""
import glob
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from chipbench.readers import host_stat
from paddle_tpu import monitor
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.joyai import JoyAIFlashConfig, JoyAIFlashForCausalLM
from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.models.ouro import OuroConfig, OuroForCausalLM
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                          Qwen3NextForCausalLM)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ, K = 128, 16, 2
# XLA:TPU lands GELU's fusions under the matmuls' scopes (PERF.md
# section 5), so a metric may name it and find nothing rooted there
MAY_ROOT_NONE = {"gelu"}


def _metric_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w["config"] for w in manifest["workloads"]}
    families = {e["name"]: sorted({cells[w].split("_")[0] for w in
                                   e.get("workloads", cells)})
                for e in manifest["per_layer"]}
    out = {}
    for path in sorted(glob.glob(
            os.path.join(REPO, "chipbench", "metrics", "*.json"))):
        with open(path) as f:
            data = json.load(f)
        name = os.path.basename(path)[:-len(".json")]
        args = data.get("args", {})
        if name in families and (
                data["reader"] in ("program_stat", "host_stat")
                or args.get("scopes") or args.get("marks")):
            out[name] = (args, families[name], data["reader"])
    return out


METRICS = _metric_files()
COUNTER_KEYS = ("counter", "per", "times", "witness")
COUNTERS = sorted({a[key] for a, _f, _r in METRICS.values()
                   for key in COUNTER_KEYS if key in a})


def _held_to_its_value(counter):
    """What it reads, not its change over a fixture's calls: a counter
    written once a process (the import) or before the calls (the eager
    programs of building a model), or a largest value seen, which need
    not rise in a later fixture of this module."""
    return (counter == "import_ns" or "_max_ns" in counter
            or 'program="eager"' in counter)


def _compiled(model, forward_loss, seq=SEQ):
    """The step a user's loop calls, called twice: autocast, backward,
    AdamW with fp32 masters, `to_static(scan_steps=2)`."""
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-3, multi_precision=True)

    def one_step(ids, labels):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            loss = forward_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one_step, scan_steps=K)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, VOCAB, (K, 2, seq)).astype("int32"))
    before = monitor.stats()
    # the build, a call the counters keep, and a third for the maxima
    # (jax may make a program under the second: no steady call)
    for _call in range(3):
        assert np.isfinite(step(ids, ids).numpy()).all()
    table = step.scope_table()
    assert not table["stale"]
    after = monitor.stats()  # what the program wrote, and nothing a
    # reading here brought into being
    return {"components": {c for rec in table["instructions"].values()
                           for c in rec["path"].split("/") if c},
            "op_names": re.findall(r'op_name="([^"]*)"', step.hlo_text()),
            "written": set(after),
            "counted": {c: after.get(c, 0) - (
                            0 if _held_to_its_value(c) else before.get(c, 0))
                        for c in COUNTERS}}


@pytest.fixture(scope="module")
def gpt3():
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=SEQ, hidden_dropout=0.0, attention_dropout=0.0))
    return _compiled(model,
                     lambda ids, labels: model.loss(model(ids), labels))


@pytest.fixture(scope="module")
def ouro():
    paddle.seed(7)
    model = OuroForCausalLM(OuroConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, total_ut_steps=2,
        max_position_embeddings=SEQ)).enable_layer_recompute("kernels")
    return _compiled(model, lambda ids, labels: model(ids, labels))


def _compiled_at_the_flash_gate(model):
    """At the flash gate's sequence, the kernels interpreted: the chip's
    branch of the gate is the one the cell's metrics read."""
    import functools

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.functional import attention

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "is_available", lambda: True)
        patch.setattr(fa, "flash_attention_bshd", functools.partial(
            fa.flash_attention_bshd, interpret=True))
        patch.setattr(attention, "_FLASH_MIN_SEQ", 128)
        return _compiled(model, lambda ids, labels: model(ids, labels),
                         seq=128)


@pytest.fixture(scope="module")
def joyai():
    paddle.seed(7)
    return _compiled_at_the_flash_gate(JoyAIFlashForCausalLM(JoyAIFlashConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=24, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, num_experts_per_tok=2, ep_size=2, ep_rank=1,
        max_position_embeddings=128)).enable_layer_recompute("kernels"))


@pytest.fixture(scope="module")
def lfm2():
    paddle.seed(7)
    return _compiled_at_the_flash_gate(Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=24, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"],
        num_attention_heads=4, num_key_value_heads=2, num_dense_layers=1,
        num_experts=8, num_experts_per_tok=2, ep_size=2, ep_rank=1,
        max_position_embeddings=128)).enable_layer_recompute("kernels"))


@pytest.fixture(scope="module")
def qwen3():
    paddle.seed(7)
    return _compiled_at_the_flash_gate(Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=VOCAB, hidden_size=32, moe_intermediate_size=24,
        shared_expert_intermediate_size=24, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, num_experts=8,
        num_experts_per_tok=2, ep_size=2, ep_rank=1,
        max_position_embeddings=128)).enable_layer_recompute("kernels"))


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_program_still_carries_what_the_metric_reads(metric, request):
    args, families, reader = METRICS[metric]
    for family in families:
        program = request.getfixturevalue(family)
        for name in set(args.get("scopes", ())) - MAY_ROOT_NONE:
            assert name in program["components"], (family, name)
        for key in COUNTER_KEYS:
            if key not in args:
                continue
            if reader == "host_stat" and key == "counter":
                # 0 is a reading: the program must have written it
                assert args[key] in program["written"], (family, args[key])
            else:
                assert program["counted"][args[key]] > 0, (family, args[key])
        for mark in args.get("marks", ()):
            assert any(mark in n for n in program["op_names"]), (family, mark)
        if reader == "host_stat":
            assert host_stat.read(None, args) is not None, family


@pytest.mark.parametrize("counted, witnessed, answer", [
    (3_000_000, 5, 3.0), (0, 5, 0.0), (0, 0, None)],
    ids=["a-number", "zero-is-a-reading", "a-commit-without-the-counter"])
def test_host_stat_answers(counted, witnessed, answer):
    args = {"counter": f"contract_counted_{counted}_{witnessed}",
            "witness": f"contract_witness_{counted}_{witnessed}",
            "scale": 1e-6}
    monitor.stat_add(args["counter"], counted)
    monitor.stat_add(args["witness"], witnessed)
    assert host_stat.read(None, args) == answer
