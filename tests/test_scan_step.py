"""Scan-compiled step program (to_static(fn, scan_steps=k)), persistent
XLA compile cache, and device-prefetch dataloading — the PR-2 perf stack.

The scan program must be OBSERVABLY identical to the python-unrolled
control: same per-inner-step losses from the same seed, same final
params, same @GRAD survival semantics through the carry.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor, nn
from paddle_tpu.io import DataLoader, Dataset

rng = np.random.RandomState(11)


def _adamw_linear(seed=42):
    paddle.seed(seed)
    m = nn.Linear(4, 2)
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=0.1)
    return m, opt


def test_scan_matches_unrolled_linear():
    k = 3
    xs = rng.rand(k, 8, 4).astype("float32")
    ys = rng.rand(k, 8, 2).astype("float32")

    m1, opt1 = _adamw_linear()

    @paddle.jit.to_static
    def unrolled(xb, yb):
        losses = []
        for i in range(k):
            loss = nn.functional.mse_loss(m1(xb[i]), yb[i])
            loss.backward()
            opt1.step()
            opt1.clear_grad()
            losses.append(loss)
        return losses

    ref = [float(l.numpy()) for l in
           unrolled(paddle.to_tensor(xs), paddle.to_tensor(ys))]

    m2, opt2 = _adamw_linear()

    def one(xb, yb):
        loss = nn.functional.mse_loss(m2(xb), yb)
        loss.backward()
        opt2.step()
        opt2.clear_grad()
        return loss

    sstep = paddle.jit.to_static(one, scan_steps=k)
    got = sstep(paddle.to_tensor(xs), paddle.to_tensor(ys)).numpy()
    assert got.shape == (k,)  # per-inner-step losses, [k]-stacked
    np.testing.assert_allclose(ref, got, rtol=1e-5)
    np.testing.assert_allclose(m1.weight.numpy(), m2.weight.numpy(),
                               rtol=1e-5)
    # the compiled program carries params + both AdamW moments (+ lr/beta
    # accumulators); the scan partition must say so
    assert sstep._last_partition["scan_steps"] == k
    assert len(sstep._last_partition["donated"]) >= 6


@pytest.mark.slow  # ~22 s (the k=2 UNROLL compile dominates); scan
# equivalence itself is tier-1-covered at toy scale in this file
def test_scan_matches_unrolled_bert_cpu_small():
    """Acceptance: scan-vs-unrolled loss equivalence on the CPU-small
    BERT config (k=2, same seed, allclose) — chip_smoke.py's program
    structure A/B in miniature."""
    import jax.lax as lax
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   synthetic_mlm_batch)

    k, batch, seq = 2, 2, 64
    cfg_kw = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
                  intermediate_size=128, max_position_embeddings=seq,
                  hidden_dropout=0.0, attention_dropout=0.0)
    ids, tok, labels, nsp = synthetic_mlm_batch(batch, seq, vocab_size=512)
    stack = lambda a: np.broadcast_to(a, (k,) + a.shape).copy()

    def build():
        paddle.seed(0)
        model = BertForPretraining(BertConfig(**cfg_kw))
        opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                     learning_rate=1e-3)
        params = list(model.parameters())

        def one_step(i, t, l, n):
            with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
                logits, nsp_logits = model(i, t)
                loss = model.loss(logits, nsp_logits, l, n)
            loss.backward()
            withg = [p for p in params if p._grad is not None]
            barred = lax.optimization_barrier(
                tuple(p._grad for p in withg))
            for p, v in zip(withg, barred):
                p._grad = v
            opt.step()
            opt.clear_grad()
            return loss

        return model, one_step

    model_u, one_u = build()

    @paddle.jit.to_static
    def unrolled(i, t, l, n):
        return [one_u(i, t, l, n) for _ in range(k)]

    ref = [float(x.numpy()) for x in unrolled(
        *(paddle.to_tensor(a) for a in (ids, tok, labels, nsp)))]

    model_s, one_s = build()
    sstep = paddle.jit.to_static(one_s, scan_steps=k)
    got = sstep(*(paddle.to_tensor(stack(a))
                  for a in (ids, tok, labels, nsp))).numpy()
    np.testing.assert_allclose(ref, got, rtol=2e-3)
    for pu, ps in zip(model_u.parameters(), model_s.parameters()):
        np.testing.assert_allclose(np.asarray(pu.numpy(), np.float32),
                                   np.asarray(ps.numpy(), np.float32),
                                   rtol=2e-3, atol=1e-5)


def test_scan_grad_accumulation_survives_carry():
    """@GRAD survival: a grad accumulated (not consumed) inside the body
    threads through the scan carry and keeps accumulating across
    program calls — the persistable-@GRAD semantics of the reference."""
    k = 4
    xs = rng.rand(k, 5, 3).astype("float32")

    paddle.seed(1)
    m1 = nn.Linear(3, 2)
    for i in range(k):
        m1(paddle.to_tensor(xs[i])).mean().backward()
    g_eager = m1.weight.grad.numpy()

    paddle.seed(1)
    m2 = nn.Linear(3, 2)

    def one(xb):
        loss = m2(xb).mean()
        loss.backward()
        return loss

    sstep = paddle.jit.to_static(one, scan_steps=k)
    sstep(paddle.to_tensor(xs))
    np.testing.assert_allclose(g_eager, m2.weight.grad.numpy(), rtol=1e-5)
    # grads live across program calls: a second scan doubles them
    sstep(paddle.to_tensor(xs))
    np.testing.assert_allclose(2 * g_eager, m2.weight.grad.numpy(),
                               rtol=1e-5)


def test_scan_rng_advances_per_inner_step():
    paddle.seed(3)
    drop = nn.Dropout(0.5)
    k = 4
    d = paddle.jit.to_static(lambda xb: drop(xb), scan_steps=k)
    outs = d(paddle.to_tensor(np.ones((k, 2, 16), np.float32))).numpy()
    masks = {tuple((outs[i] != 0).ravel()) for i in range(k)}
    assert len(masks) > 1, "dropout masks identical across inner steps"


def test_scan_rejects_unstacked_inputs():
    m = nn.Linear(4, 2)
    step = paddle.jit.to_static(lambda x: m(x).mean(), scan_steps=3)
    with pytest.raises(ValueError, match=r"stacked \[k, \.\.\.\]"):
        step(paddle.to_tensor(rng.rand(8, 4).astype("float32")))


def test_scan_steps_validation():
    with pytest.raises(ValueError, match="scan_steps"):
        paddle.jit.to_static(lambda x: x, scan_steps=0)


# -- persistent compile cache ----------------------------------------------

def test_persistent_cache_warm_start(tmp_path, monkeypatch):
    """Acceptance: with the persistent cache on, a second StaticFunction
    over the same fn hits the disk cache instead of re-running the
    backend compile (restart-shaped workload, one process)."""
    from paddle_tpu.jit import compile_cache

    # the test places its own cache; an ambient placement would refuse
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def fn(x):
        return (x * 2.0 + 1.0).sum()

    x = paddle.to_tensor(rng.rand(16, 16).astype("float32") + 7.0)
    compile_cache.enable(str(tmp_path / "xla"), min_compile_time_secs=0)
    try:
        for c in ("jit_persistent_cache_hits",
                  "jit_persistent_cache_misses"):
            monitor.stat_reset(c)
        cold = paddle.jit.to_static(fn)
        cold(x)
        assert monitor.stat_get("jit_persistent_cache_misses") >= 1
        misses_after_cold = monitor.stat_get("jit_persistent_cache_misses")
        warm = paddle.jit.to_static(fn)  # fresh StaticFunction + jax.jit
        warm(x)
        assert monitor.stat_get("jit_persistent_cache_hits") >= 1
        # the warm build added no new backend compiles to the cache
        assert (monitor.stat_get("jit_persistent_cache_misses")
                == misses_after_cold)
        assert compile_cache.is_enabled()
        assert compile_cache.cache_dir() == str(tmp_path / "xla")
    finally:
        compile_cache.disable()


def test_compile_cache_env_policy(monkeypatch, tmp_path):
    """The switch is recorded at import; placement follows one rule:
    JAX_COMPILATION_CACHE_DIR set -> jax's own setting stands and no
    directory is set in code; unset -> the fixed in-checkout path."""
    import jax

    from paddle_tpu.jit import compile_cache

    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "off")
    assert compile_cache.configure_from_env() is False
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "1")
    assert compile_cache.configure_from_env() is True
    monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE")
    compile_cache._state["policy"] = None  # the suite's ambient policy

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        compile_cache.enable()
        assert jax.config.jax_compilation_cache_dir == before
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            compile_cache.enable(str(tmp_path / "elsewhere"))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == compile_cache.DEFAULT_CACHE_DIR
        assert compile_cache.DEFAULT_CACHE_DIR == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
    finally:
        compile_cache.disable()
        jax.config.update("jax_compilation_cache_dir", before)


# -- stacked-batch device prefetch -----------------------------------------

class _PairDataset(Dataset):
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return (np.full((3, 2), i, np.float32),
                np.int64(i))


def test_stacked_prefetch_to_device_round_trip():
    """Acceptance: DataLoader(stack_steps=k, prefetch_to_device=True)
    yields [k, batch, ...] device-resident batches whose shapes, dtypes
    and values round-trip exactly."""
    import jax

    k, bs, n = 3, 2, 14
    loader = DataLoader(_PairDataset(n), batch_size=bs, shuffle=False,
                        stack_steps=k, prefetch_to_device=True)
    assert len(loader) == (n // bs) // k  # incomplete k-groups drop
    seen = 0
    idx = 0
    for feats, labels in loader:
        assert tuple(feats.shape) == (k, bs, 3, 2)
        assert tuple(labels.shape) == (k, bs)
        assert str(feats.dtype) in ("float32", "paddle.float32")
        # device-resident: the leaf value is a committed jax array
        assert isinstance(feats._value, jax.Array)
        for s in range(k):
            for b in range(bs):
                assert float(feats.numpy()[s, b, 0, 0]) == idx
                assert int(labels.numpy()[s, b]) == idx
                idx += 1
        seen += 1
    assert seen == len(loader)


def test_stack_steps_without_device_prefetch():
    k, bs, n = 2, 2, 8
    loader = DataLoader(_PairDataset(n), batch_size=bs, stack_steps=k)
    batches = list(loader)
    assert len(batches) == 2
    feats, labels = batches[0]
    assert tuple(feats.shape) == (k, bs, 3, 2)
    np.testing.assert_array_equal(labels.numpy(), [[0, 1], [2, 3]])


def test_stack_steps_implies_drop_last():
    """A smaller trailing batch must never land inside a k-group: 10
    samples / batch 4 leaves a 2-sample tail that would break np.stack —
    stack_steps forces drop_last so stacking always sees uniform
    shapes."""
    loader = DataLoader(_PairDataset(10), batch_size=4, stack_steps=2)
    assert loader.drop_last
    (batches,) = list(loader)  # [4,4] stack; the 2-sample tail dropped
    assert tuple(batches[0].shape) == (2, 4, 3, 2)


class _DictDataset(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        return {"x": np.full((2,), i, np.float32), "y": np.int64(i)}


def test_stack_steps_nested_containers():
    loader = DataLoader(_DictDataset(), batch_size=2, stack_steps=2)
    batch = next(iter(loader))
    assert tuple(batch["x"].shape) == (2, 2, 2)
    assert tuple(batch["y"].shape) == (2, 2)
    np.testing.assert_array_equal(batch["y"].numpy(), [[0, 1], [2, 3]])


def test_scan_program_consumes_dataloader_stacks():
    """End-to-end: stacked loader batches feed a scan-compiled step."""
    k, bs = 2, 2
    paddle.seed(5)
    m = nn.Linear(6, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=m.parameters())

    def one(feats, labels):
        loss = nn.functional.mse_loss(
            m(feats.reshape([bs, 6])),
            paddle.cast(labels, "float32").reshape([bs, 1]).expand([bs, 2]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one, scan_steps=k)
    loader = DataLoader(_PairDataset(8), batch_size=bs, stack_steps=k,
                        prefetch_to_device=True)
    losses = []
    for feats, labels in loader:
        losses.extend(step(feats, labels).numpy().tolist())
    assert len(losses) == 4 and all(np.isfinite(losses))
