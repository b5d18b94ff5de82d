"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: cache child, then
                                     # train / kernel / serve in this process
    python chip_smoke.py --chips 4   # four chips, one process: the dp4
                                     # ZeRO-3 phase and its control, only

Drives the main path once through the entry points a user calls
(`paddle.jit.to_static`, `paddle.optimizer.AdamW`, `paddle.amp.auto_cast`,
`F.scaled_dot_product_attention`, `paddle.jit.save`, `serving.Engine`) at
the full width of BERT-base, with weights and data made from a seed, and
checks each result by the repo's own means. One JSON object per phase;
the last line is `{"ok": true, "device": {...}}` with the device as jax
reports it. Any phase that raises fails the run, and without a TPU it
exits non-zero before doing any work and prints no result. What it
prints are observations of one run — not a benchmark.

One process holds a chip. The cache phase needs a second process, so it
runs FIRST, before this one has imported jax: a child builds and calls
the train phase's scan program once and exits, and the parent's own
build of that program must then hit the persistent compile cache.
"""
import argparse
import gc
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 0
LR = 1e-4
# BertConfig's defaults ARE the published base widths (12 x 768, 12
# heads, FFN 3072); vocab padded to a multiple of 128, dropout off so
# two program structures can be compared from one seed
BERT = dict(vocab_size=30720, hidden_dropout=0.0, attention_dropout=0.0)
BATCH, SEQ = 16, 512
SCAN_K, UNROLL_K, TRAIN_STEPS = 4, 2, 12
# Two programs of the same math in bf16 (eps 2^-8) round differently
# wherever they fuse differently, and this cell — seeded weights, loss
# ~100, AdamW's first sign-like steps — amplifies that: on the chip (PR
# 21) fp32 scan and unroll agreed to 4.3e-5 over all 12 steps and scan
# k=4 equalled scan k=2 bit for bit, while bf16 strayed from fp32 OF THE
# SAME STRUCTURE by up to 9.5e-2 a step, and bf16 scan from bf16 unroll
# by 5.6e-4 over the first scan call, up to 1.6e-1 later. So: held
# tightly over the first scan call (in-program carry, and the unrolled
# program's cross-call state), to bf16's own worth after it.
EARLY_STEPS, EARLY_RTOL, LATE_RTOL = SCAN_K, 2e-2, 0.25
# kernel outputs and gradients are bf16: a few eps of the largest value
KERNEL_TOL = 2e-2
# (batch, seq, heads, head_dim, causal): gpt3_1p3b's head geometry past
# the dispatch gate, and BERT-base's exactly at it
KERNEL_CASES = ((2, 2048, 16, 128, True), (2, 1024, 12, 64, False))
SERVE = dict(feat=256, hidden=1024, ladder=(1, 4, 16, 64))
SERVE_ROWS = (1, 2, 3, 5, 8, 16, 33, 64)
# fp32 matmuls at the TPU's default precision round their inputs to bf16
# on the MXU (eps 2^-8), and a row served in a padded bucket may take
# another path than the same row alone: a few eps of the largest output
# (on the chip, PR 21: 1.6e-3; on a CPU the two are bitwise equal, which
# tests/test_serving.py holds)
SERVE_TOL = 1e-2
CHILD_TIMEOUT_S = 700


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu():
    """The first JAX call of a process: take the chip or refuse to run."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; jax found platform "
            f"{dev.platform!r} ({dev.device_kind}). Nothing was run.")
    return dev


def device_record():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def memory_stat(dev, key):
    """`device.memory_stats()[key]`; only a CPU may report none."""
    stats = dev.memory_stats()
    if stats is None:
        if dev.platform == "tpu":
            raise RuntimeError(f"{dev} reports no memory_stats()")
        return None
    return stats[key]


def cache_counters():
    from paddle_tpu import monitor
    return {"hits": monitor.stat_get("jit_persistent_cache_hits"),
            "misses": monitor.stat_get("jit_persistent_cache_misses")}


# ------------------------------------------------------------------ train

def mlm_batches(seeds, batch, seq, vocab):
    """One seeded batch per step, stacked [k, ...]. Every sequence masks
    the same number of positions, so a mean of per-rank means equals the
    global mean and a dp program can be held to a single-device one."""
    import numpy as np
    n_mask = max(1, round(0.15 * seq))
    ids, labels, nsp = [], [], []
    for s in seeds:
        rng = np.random.RandomState(1000 + s)
        i = rng.randint(0, vocab, (batch, seq)).astype("int32")
        lab = np.full((batch, seq), -100, "int32")
        for row in range(batch):
            pos = rng.permutation(seq)[:n_mask]
            lab[row, pos] = i[row, pos]
        ids.append(i)
        labels.append(lab)
        nsp.append(rng.randint(0, 2, (batch,)).astype("int32"))
    ids = np.stack(ids)
    return ids, np.zeros_like(ids), np.stack(labels), np.stack(nsp)


def build_bert_step(structure, k, cfg_kw, dp_axis=None, zero_stage=0):
    """The BERT-base cell: pure-bf16 params, fp32 masters in AdamW, bf16
    autocast, `optimization_barrier` between backward and update — as a
    scan step (`scan_steps=k`) or as k python-unrolled steps, both over
    [k, ...]-stacked batches."""
    import jax.lax as lax

    import paddle_tpu as paddle
    from paddle_tpu.models import BertConfig, BertForPretraining

    paddle.seed(SEED)
    model = BertForPretraining(BertConfig(**cfg_kw))
    model.to("bfloat16")
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=LR, multi_precision=True)
    if zero_stage:
        opt._zero_enable(axis=dp_axis, stage=zero_stage)
    params = list(model.parameters())

    def one_step(ids, tok, labels, nsp_labels):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            logits, nsp = model(ids, tok)
            loss = model.loss(logits, nsp, labels, nsp_labels)
        loss.backward()
        withg = [p for p in params if p._grad is not None]
        barred = lax.optimization_barrier(tuple(p._grad for p in withg))
        for p, v in zip(withg, barred):
            p._grad = v
        opt.step()
        opt.clear_grad()
        return loss

    if structure == "scan":
        step = paddle.jit.to_static(one_step, scan_steps=k, dp_axis=dp_axis)
    else:
        def k_steps(ids, tok, labels, nsp_labels):
            return [one_step(ids[i], tok[i], labels[i], nsp_labels[i])
                    for i in range(k)]
        step = paddle.jit.to_static(k_steps)
    return step, model, opt


def run_steps(step, k, n_steps, batch, seq, vocab):
    """Call `step` on fresh seeded batches; returns per-step losses, the
    first call's wall time (trace + compile + run) and the later calls'
    wall times around `block_until_ready`, in ms."""
    import jax
    import numpy as np

    import paddle_tpu as paddle

    losses, first_call_s, call_ms = [], None, []
    for c in range(n_steps // k):
        args = [paddle.to_tensor(a) for a in
                mlm_batches(range(c * k, (c + 1) * k), batch, seq, vocab)]
        t0 = time.perf_counter()
        out = step(*args)
        out = out if isinstance(out, list) else [out]
        jax.block_until_ready([o._value for o in out])
        dt = time.perf_counter() - t0
        if first_call_s is None:
            first_call_s = dt
        else:
            call_ms.append(dt * 1e3)
        for o in out:
            losses.extend(np.asarray(o.numpy(), np.float64).ravel().tolist())
    return losses, first_call_s, call_ms


def check_losses(losses, what):
    import numpy as np
    arr = np.asarray(losses)
    if not np.all(np.isfinite(arr)):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    third = max(1, len(arr) // 3)
    if not arr[-third:].mean() < arr[:third].mean():
        raise AssertionError(f"{what}: loss is not falling: {losses}")


def check_provenance(prov, dev):
    """On the chip the requested compiler options ran or there were
    none: a fallback is never a pass."""
    if dev.platform == "tpu" and (
            prov["fallback_error"] is not None
            or (prov["flags"] and prov["applied"] is not True)):
        raise AssertionError(f"xla_flags did not apply on the chip: {prov}")


def train_structure(structure, k, cfg_kw=BERT, batch=BATCH, seq=SEQ,
                    n_steps=TRAIN_STEPS):
    """One program structure: build, run, check, report, free."""
    import jax

    dev = jax.devices()[0]
    step, model, opt = build_bert_step(structure, k, cfg_kw)
    losses, first_call_s, call_ms = run_steps(
        step, k, n_steps, batch, seq, cfg_kw["vocab_size"])
    check_losses(losses, structure)
    prov = step.xla_flags()
    check_provenance(prov, dev)
    steady_ms = statistics.median(call_ms)
    emit("train", structure=structure, k=k, batch=batch, seq=seq,
         layers=model.config.num_layers, hidden=model.config.hidden_size,
         losses=[round(x, 4) for x in losses],
         first_call_s=round(first_call_s, 2),
         # trace + compile: the first call less one steady call
         compile_s=round(first_call_s - steady_ms / 1e3, 2),
         step_ms=round(steady_ms / k, 3),
         xla_flags=prov,
         peak_bytes_in_use=memory_stat(dev, "peak_bytes_in_use"),
         persistent_cache=cache_counters())
    del step, model, opt
    gc.collect()
    return losses


def compare_losses(a, b, what):
    """Largest relative loss difference over the first scan call and
    over every step; each held to its tolerance (see EARLY_RTOL)."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    rel = np.abs(a - b) / np.abs(b)
    early, late = float(rel[:EARLY_STEPS].max()), float(rel.max())
    if not (early <= EARLY_RTOL and late <= LATE_RTOL):
        raise AssertionError(
            f"{what}: losses differ by {early:.3e} over the first "
            f"{EARLY_STEPS} steps (tolerance {EARLY_RTOL}), {late:.3e} "
            f"over all (tolerance {LATE_RTOL}): {a.tolist()} vs "
            f"{b.tolist()}")
    return {"first_call_max_rel": early, "all_steps_max_rel": late,
            "first_call_rtol": EARLY_RTOL, "all_steps_rtol": LATE_RTOL}


def phase_train(**sizes):
    """Both program structures the repo ships, from one seed."""
    from paddle_tpu.jit import compile_cache

    scan = train_structure("scan", SCAN_K, **sizes)
    cache = cache_counters()
    unroll = train_structure("unroll", UNROLL_K, **sizes)
    emit("train_agreement",
         **compare_losses(scan, unroll, "scan vs unroll"))
    return cache, compile_cache.cache_dir()


def warm_cache():
    """The cache phase's child: build and call the train phase's scan
    program once, so that the next process finds it compiled."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.jit import compile_cache

    require_tpu()
    step, _model, _opt = build_bert_step("scan", SCAN_K, BERT)
    args = [paddle.to_tensor(a) for a in
            mlm_batches(range(SCAN_K), BATCH, SEQ, BERT["vocab_size"])]
    t0 = time.perf_counter()
    jax.block_until_ready(step(*args)._value)
    emit("cache_child", first_call_s=round(time.perf_counter() - t0, 2),
         cache_dir=compile_cache.cache_dir(),
         persistent_cache=cache_counters())


def check_cache(parent_after_scan, cache_dir):
    """The second process hit, and the files are where they were put."""
    from paddle_tpu.jit import compile_cache
    placed = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
              or compile_cache.DEFAULT_CACHE_DIR)
    files = os.listdir(cache_dir)
    emit("cache", cache_dir=cache_dir, files=len(files),
         parent_scan=parent_after_scan)
    if os.path.realpath(cache_dir) != os.path.realpath(placed):
        raise AssertionError(f"cache at {cache_dir}, placed at {placed}")
    if not files or parent_after_scan["hits"] < 1:
        raise AssertionError(
            f"the second process did not hit the compile cache: "
            f"{parent_after_scan}, {len(files)} files in {cache_dir}")


# ----------------------------------------------------------------- kernel

def kernel_case(b, s, h, d, causal):
    """`F.scaled_dot_product_attention` forward and backward in bf16
    through the normal dispatch (one `to_static` program) against the
    `_sdpa` path in float32 — a mask, even all-zero, keeps attention off
    the kernel — at full matmul precision. Returns the count of Mosaic
    calls in the compiled program and each result's largest error as a
    share of the reference's largest value."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.RandomState(SEED + s)
    q, k, v, w = (rng.randn(b, s, h, d).astype("float32") for _ in range(4))

    def fwd_bwd(q, k, v, w, mask=None):
        for t in (q, k, v):
            t.stop_gradient = False
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=mask,
                                             is_causal=causal)
        loss = (out.astype("float32") * w).sum()
        return [out] + list(paddle.grad(loss, [q, k, v]))

    step = paddle.jit.to_static(fwd_bwd)
    low = [paddle.to_tensor(a).astype("bfloat16") for a in (q, k, v)]
    got = step(*low, paddle.to_tensor(w))
    jax.block_until_ready([g._value for g in got])
    custom_calls = step.hlo_text().count("tpu_custom_call")

    # the same bf16-rounded inputs, so the error is the kernel's own
    ref_in = [t.astype("float32") for t in low]
    zero_mask = paddle.to_tensor(np.zeros((1, 1, s, s), "float32"))
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(*ref_in, paddle.to_tensor(w), mask=zero_mask)

    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        g = np.asarray(g.astype("float32").numpy())
        r = np.asarray(r.numpy())
        if g.shape != r.shape or not np.all(np.isfinite(g)):
            raise AssertionError(f"{name}: bad kernel result {g.shape}")
        errs[name] = float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
    return custom_calls, errs


def phase_kernel():
    for b, s, h, d, causal in KERNEL_CASES:
        custom_calls, errs = kernel_case(b, s, h, d, causal)
        emit("kernel", shape=[b, s, h, d], causal=causal, dtype="bfloat16",
             tpu_custom_calls=custom_calls,
             max_err_over_max_ref=errs, tolerance=KERNEL_TOL)
        # the forward and the one backward kernel: chosen, not XLA
        if custom_calls < 2:
            raise AssertionError(
                f"flash kernel not in the compiled program "
                f"({custom_calls} tpu_custom_call) at {(b, s, h, d)}")
        bad = {n: e for n, e in errs.items() if not e <= KERNEL_TOL}
        if bad:
            raise AssertionError(f"kernel disagrees with _sdpa: {bad}")


# ------------------------------------------------------------------ serve

def phase_serve(feat=SERVE["feat"], hidden=SERVE["hidden"],
                ladder=SERVE["ladder"], rows=SERVE_ROWS):
    """Export the MLP the serving bench serves, load it in the bucketed
    AOT engine, answer ragged requests — alone and in one concurrent
    burst — and hold every answer to the unbatched Predictor's."""
    import threading

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.observability as obs
    import paddle_tpu.serving as serving
    from paddle_tpu import monitor
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.jit.to_static import InputSpec

    paddle.seed(SEED)
    model = nn.Sequential(nn.Linear(feat, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, 8))
    model.eval()
    rng = np.random.RandomState(SEED)
    reqs = [rng.randn(r, feat).astype(np.float32) for r in rows]

    def compiles():
        c = cache_counters()
        return (monitor.stats().get("jit_backend_compiles", 0)
                + c["hits"] + c["misses"])

    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "mlp")
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([None, feat], "float32")])
        pred = create_predictor(Config(prefix + ".pdmodel",
                                       prefix + ".pdiparams"))
        want = [pred.run([x])[0] for x in reqs]
        obs.enable()
        try:
            aot0 = monitor.stat_get("serving_aot_compiles")
            with serving.Engine(prefix, bucket_ladder=ladder) as eng:
                aot = monitor.stat_get("serving_aot_compiles") - aot0
                before = compiles()
                got = [eng.predict(x)[0] for x in reqs]
                burst = [None] * len(reqs)

                def client(i):
                    burst[i] = eng.predict(reqs[i])[0]

                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(len(reqs))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=120)
                request_path_compiles = compiles() - before
                stats = eng.stats()
        finally:
            obs.disable()

    if any(b is None for b in burst):
        raise AssertionError("a concurrent request was not answered")
    scale = max(float(np.max(np.abs(w))) for w in want)
    diff = max(float(np.max(np.abs(g - w)))
               for g, w in zip(got + burst, want + want))
    bitwise = all(np.array_equal(g, w)
                  for g, w in zip(got + burst, want + want))
    emit("serve", ladder=list(ladder), rows=list(rows),
         serving_aot_compiles=aot,
         request_path_compiles=request_path_compiles,
         batches=stats["batches"],
         multi_request_batches=stats["multi_request_batches"],
         bitwise_equal_predictor=bitwise, max_abs_diff=diff,
         max_abs_ref=scale, tolerance=SERVE_TOL)
    if aot != len(ladder) or request_path_compiles != 0:
        raise AssertionError(
            f"{aot} AOT compiles for a ladder of {len(ladder)}, "
            f"{request_path_compiles} compiles on the request path")
    if not diff <= SERVE_TOL * scale:
        raise AssertionError(
            f"engine and Predictor differ by {diff} (scale {scale})")


# ------------------------------------------------------------- four chips

def phase_zero3_dp4(cfg_kw=BERT, batch=BATCH, seq=SEQ, n_steps=2 * SCAN_K,
                    state_share_max=0.275):
    """ZeRO-3 over a {"dp": 4} mesh in one process against the unsharded
    single-device program, same seed and batches: same losses, state
    spread over the four devices, a quarter of it on each (every tensor
    pads to whole 1024-lane rows, which only adds: at BERT-base's size
    well under a tenth over the quarter). The fp32 gradient-window
    store ZeRO >= 2 always keeps (`gacc`) has no counterpart in the
    control and is reported apart."""
    import jax

    from paddle_tpu.distributed import parallel_env

    devs = jax.devices()[:4]
    parallel_env.set_mesh(parallel_env.make_mesh({"dp": 4}, devices=devs))
    try:
        step, model, opt = build_bert_step("scan", SCAN_K, cfg_kw,
                                           dp_axis="dp", zero_stage=3)
        sharded, first_call_s, call_ms = run_steps(
            step, SCAN_K, n_steps, batch, seq, cfg_kw["vocab_size"])
        check_losses(sharded, "zero3 dp4")
        prov = step.xla_flags()
        check_provenance(prov, devs[0])
        stores = [(slot, sd.tensor._value)
                  for sdict in opt._zero["stores"]
                  for slot, sd in sdict.items()]
        spans = [len(arr.sharding.device_set) for _slot, arr in stores]
        in_use = [memory_stat(d, "bytes_in_use") for d in devs]
        shard_bytes = opt._zero_state_bytes()
        gacc_bytes = sum(arr.addressable_shards[0].data.nbytes
                         for slot, arr in stores if slot == "gacc")
        collectives = step.collective_stats(per_execution=True)
    finally:
        parallel_env.set_mesh(None)
    del step, model, opt
    gc.collect()

    step, model, opt = build_bert_step("scan", SCAN_K, cfg_kw)
    control, _first, _ms = run_steps(
        step, SCAN_K, n_steps, batch, seq, cfg_kw["vocab_size"])
    check_losses(control, "single-device control")
    full_bytes = opt._zero_state_bytes()
    # the control keeps its bf16 params outside the optimizer; ZeRO-3's
    # stores hold them too — compare like with like
    full_bytes += sum(int(p.size) * 2 for p in model.parameters())
    del step, model, opt
    gc.collect()

    agreement = compare_losses(sharded, control,
                               "zero3 dp4 vs single device")
    emit("zero3_dp4", losses=[round(x, 4) for x in sharded],
         control_losses=[round(x, 4) for x in control], **agreement,
         first_call_s=round(first_call_s, 2),
         step_ms=round(statistics.median(call_ms) / SCAN_K, 3),
         xla_flags=prov, store_device_spans=sorted(set(spans)),
         bytes_in_use_per_device=in_use,
         state_bytes_per_chip=shard_bytes,
         of_which_grad_window_store=gacc_bytes,
         control_state_bytes=full_bytes,
         collectives_per_execution=collectives)
    if set(spans) != {4}:
        raise AssertionError(f"a sharded store spans {set(spans)} devices")
    if in_use[0] is not None and \
            (max(in_use) - min(in_use)) > 0.05 * max(in_use):
        raise AssertionError(f"device memory is not balanced: {in_use}")
    if not 0.25 <= (shard_bytes - gacc_bytes) / full_bytes \
            < state_share_max:
        raise AssertionError(
            f"state per chip {shard_bytes} (grad-window store "
            f"{gacc_bytes}) is not ~1/4 of {full_bytes}")


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the dp4 ZeRO-3 phase and its control only "
                         "(one process over all four chips)")
    args = ap.parse_args(argv)

    if args.chips == 1:
        # BEFORE this process imports jax: the chip is free for a child
        child = subprocess.run(
            [sys.executable, "-c",
             "import chip_smoke; chip_smoke.warm_cache()"],
            cwd=REPO, stdout=subprocess.PIPE, text=True,
            timeout=CHILD_TIMEOUT_S)
        sys.stdout.write(child.stdout)
        sys.stdout.flush()
        if child.returncode != 0:
            raise SystemExit(
                f"chip_smoke: the cache child failed (exit code "
                f"{child.returncode}); nothing else was run")

    import jax

    require_tpu()
    if jax.device_count() != args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax "
                         f"reports {jax.device_count()} devices")
    from paddle_tpu import _native
    emit("native", AVAILABLE=_native.AVAILABLE)
    if not _native.AVAILABLE:
        raise SystemExit("chip_smoke: the C++ runtime did not build from "
                         "paddle_tpu/_native/src/*.cc")
    emit("device", **device_record(), jax=jax.__version__)

    if args.chips == 4:
        phase_zero3_dp4()
    else:
        after_scan, cache_dir = phase_train()
        check_cache(after_scan, cache_dir)
        phase_kernel()
        phase_serve()
    print(json.dumps({"ok": True, "device": device_record()}), flush=True)


if __name__ == "__main__":
    main()
