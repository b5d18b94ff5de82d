"""BASELINE.md config ladder — measured, not aspirational.

Configs (BASELINE.md / SURVEY.md §6):
  1. LeNet/MNIST dygraph smoke        — covered by tests/test_training_e2e.py
  2. ResNet-50 @to_static             — img/s/chip               (here)
  3. BERT-base pretraining            — bench.py (the headline; driver-run)
  4. GPT-1.3B sharding + pipeline     — hybrid dryrun step time  (here)
  5. detection variable-shape path    — img/s, shape buckets   (here)

Run: `python benchmarks/run_all.py [--configs resnet,gpt,allreduce,detection]`
Prints one JSON line per config. On a host without TPU the numbers are
CPU-smoke only (marked "backend": "cpu").

Perf-regression gate (observability/gate.py):
  python benchmarks/run_all.py --gate                        # vs BASELINE_PERF.json
  python benchmarks/run_all.py --out results.json            # record a run
  python benchmarks/run_all.py --write-baseline BASELINE     # pin a baseline
  python benchmarks/run_all.py --gate BASELINE [--tolerance 0.1]
  python benchmarks/run_all.py --results results.json --gate BASELINE
`--gate` without a path gates against the pinned repo baseline
(BASELINE_PERF.json, TPU-captured): on a TPU host values are compared
with the noise tolerance; on a CPU host the backend tags differ so the
gate checks metric PRESENCE only (the bench must still run and produce a
usable value) — except the bert row: bench.py on a CPU is a dry-run that
records no device metric, so a CPU gate reports that row MISSING. The
`--results` form gates a previously recorded results
file without re-running the ladder (CI can bench once and gate many
baselines). Exit codes: 0 ok, 1 a bench errored, 2 gate regression.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def _sync(x):
    import jax
    jax.block_until_ready(getattr(x, "_value", x))


def bench_resnet50():
    """Config 2: ResNet-50 training step, @to_static, bf16 AMP."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    bs, iters, warmup = (64, 10, 3) if on_tpu else (2, 2, 1)
    size = 224 if on_tpu else 32

    paddle.seed(0)
    model = resnet50(num_classes=1000 if on_tpu else 10)
    opt = paddle.optimizer.Momentum(parameters=model.parameters(),
                                    learning_rate=0.1, momentum=0.9)

    def train_step(x, y):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            logits = model(x)
            loss = nn.functional.cross_entropy(logits, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(bs, 3, size, size).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 10, (bs,)).astype("int64"))
    for _ in range(warmup):
        loss = step(x, y)
    _sync(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    _sync(loss)
    dt = time.perf_counter() - t0
    img_s = bs * iters / dt
    return {"metric": "resnet50_train_img_per_s_per_chip",
            "value": round(img_s, 1), "unit": "img/s",
            "backend": backend, "batch": bs}


def _run_json_subprocess(cmd, what, env=None, timeout=1800,
                         all_records=False):
    """Run a bench subprocess and parse the LAST JSON line it prints
    (both bench.py and this ladder emit one record per line on stdout);
    ``all_records`` returns EVERY JSON line instead (multi-row benches)
    and refuses a non-zero exit — a crashed child may still have
    printed SOME records, and partial output must not pass as a
    successful multi-row bench."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(cmd, capture_output=True, text=True, cwd=repo,
                       timeout=timeout, env=env)
    if all_records and r.returncode != 0:
        raise RuntimeError(
            f"{what} failed (rc={r.returncode}): "
            f"{(r.stderr or r.stdout)[-300:]}")
    records = []
    bad_last = False
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                records.append(json.loads(line))
                bad_last = False
            except json.JSONDecodeError:
                bad_last = True  # stray log line — or a truncated record
    if records and not (bad_last and not all_records):
        # single-record mode must NOT skip an unparseable FINAL line: a
        # child killed mid-write of its last record would otherwise pass
        # a stale intermediate record as the bench result (all_records
        # mode catches that crash through the returncode check above)
        return records if all_records else records[-1]
    raise RuntimeError(
        f"{what} produced no usable JSON record (rc={r.returncode}): "
        f"{(r.stderr or r.stdout)[-300:]}")


def _reexec_bench(name, n_virtual, all_records=False):
    """Run one bench in a subprocess with a virtual n-device CPU mesh
    (XLA's host device count is fixed at backend init, so the flag can't
    be applied in-process once jax is up). ``all_records`` collects
    EVERY JSON line the bench prints (multi-row benches) instead of the
    last one."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{n_virtual}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return _run_json_subprocess(
        [sys.executable, os.path.abspath(__file__), "--configs", name],
        f"virtual-mesh re-exec of bench {name!r}", env=env,
        all_records=all_records)


def bench_gpt_sharding_pp(n_virtual=8):
    """Config 4: GPT-1.3B-config hybrid dp x sharding(ZeRO) + 1F1B pipeline.

    Schedule correctness + step time on an n-device mesh (virtual CPU mesh
    when no multi-chip TPU is attached, the driver's dryrun strategy). Model
    dims are scaled down; the partitioning logic (1.3B's layer/stage/shard
    structure) is what executes.
    """
    import jax
    if jax.device_count() < n_virtual:
        if jax.default_backend() == "cpu":
            # the host can virtualize the mesh — re-exec just this bench
            # with the device-count flag so the default `--gate` ladder
            # stays self-sufficient on CPU smoke hosts
            return _reexec_bench("gpt", n_virtual)
        return {"metric": "gpt13b_hybrid_dryrun_step_ms", "value": -1.0,
                "unit": "ms", "backend": jax.default_backend(),
                "note": f"needs {n_virtual} devices (have "
                        f"{jax.device_count()}); on CPU set "
                        f"XLA_FLAGS=--xla_force_host_platform_device_count="
                        f"{n_virtual}"}
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       build_gpt_1f1b_step)

    devs = jax.devices()[:n_virtual]
    pp, dp = 4, 2
    mesh = dist.make_mesh({"dp": dp, "pp": pp}, devices=devs)

    on_tpu = jax.default_backend() not in ("cpu",)
    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                        vocab_size=50304, max_seq_len=1024,
                        hidden_dropout=0.0, attention_dropout=0.0)  # 1.3B
        M, mb, T = 8, dp, 1024  # per-microbatch dim must shard over dp
    else:
        # 1.3B structure (24 layers, 6/stage over pp=4), scaled dims for
        # the host-simulated dryrun
        cfg = GPTConfig(hidden_size=64, num_layers=24, num_heads=4,
                        vocab_size=512, max_seq_len=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
        M, mb, T = 8, 2, 16
    model = GPTForCausalLM(cfg)
    model.eval()
    step, _ = build_gpt_1f1b_step(model, mesh, axis_dp="dp")
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (M, mb, T)).astype(np.int32)

    loss, grads = step(ids, ids)
    assert np.isfinite(float(np.asarray(loss)))
    t0 = time.perf_counter()
    for _ in range(3):
        loss, grads = step(ids, ids)
    _ = float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / 3
    return {"metric": "gpt13b_hybrid_dryrun_step_ms",
            "value": round(dt * 1000, 2), "unit": "ms",
            "backend": jax.default_backend(),
            "model": {"layers": cfg.num_layers, "hidden": cfg.hidden_size},
            "mesh": {"dp": dp, "pp": pp}, "microbatches": M,
            "loss": round(float(np.asarray(loss)), 4)}


def bench_allreduce():
    """Fleet allreduce bus bandwidth (BASELINE.md metric 3) across the
    attached devices (1 device → memcpy-bound upper bound, reported as
    such)."""
    import jax
    import jax.numpy as jnp

    n = jax.device_count()
    nbytes = 64 * 1024 * 1024
    x = jnp.ones((nbytes // 4,), jnp.float32)
    if n == 1:
        # one compiled scan of K copies: measures HBM r/w, not dispatch
        K = 50

        def body(v, _):
            return v + 1.0, None

        f = jax.jit(lambda v: jax.lax.scan(body, v, None, length=K)[0])
        float(f(x)[0])
        t0 = time.perf_counter()
        float(f(x)[0])
        dt = (time.perf_counter() - t0) / K
        bw = 2 * nbytes / dt / 1e9
        # honest name: on one chip this measures HBM read+write, NOT the
        # interconnect bus bandwidth BASELINE.md's metric refers to
        return {"metric": "allreduce_1chip_hbm_GBps", "value": round(bw, 1),
                "unit": "GB/s", "backend": jax.default_backend(),
                "devices": 1, "note": "single device: HBM r/w bound; not "
                "comparable to the multi-chip allreduce_bus_bw_GBps metric"}
    from jax.sharding import PartitionSpec as P
    import paddle_tpu.distributed as dist
    mesh = dist.make_mesh({"dp": n})
    f = jax.jit(jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                              in_specs=P("dp"), out_specs=P("dp")))
    y = f(x)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(10):
        y = f(x)
    jax.block_until_ready(y)
    dt = (time.perf_counter() - t0) / 10
    # ring allreduce bus bytes: 2 * (n-1)/n * payload
    bus = 2 * (n - 1) / n * nbytes / dt / 1e9
    return {"metric": "allreduce_bus_bw_GBps", "value": round(bus, 1),
            "unit": "GB/s", "backend": jax.default_backend(), "devices": n}


def bench_detection():
    """Config 5: variable-shape detection training (PP-YOLOE/Faster-RCNN
    class of workload). Images arrive in mixed resolutions; the
    LoDTensor-era variable-shape story on TPU is shape BUCKETING — each
    bucket compiles once (to_static cache) and steps reuse the executable.
    Measures img/s across mixed-bucket traffic with ragged gt boxes padded
    per batch, trained through yolov3_loss."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet18
    from paddle_tpu.vision.ops import yolov3_loss

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    if on_tpu:
        buckets, bs, iters, warmup = [320, 416, 512], 8, 4, 1
    else:
        buckets, bs, iters, warmup = [64, 96], 2, 1, 1
    class_num, max_boxes = 80, 50
    anchors = [116, 90, 156, 198, 373, 326]
    mask = [0, 1, 2]

    paddle.seed(0)
    backbone = resnet18(num_classes=0, with_pool=False)  # trunk only
    head = nn.Conv2D(512, len(mask) * (5 + class_num), 1)
    params = backbone.parameters() + head.parameters()
    opt = paddle.optimizer.Momentum(parameters=params, learning_rate=0.01,
                                    momentum=0.9)

    def train_step(img, gtb, gtl):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            feat = backbone(img)
            pred = head(feat)
            loss = yolov3_loss(pred, gtb, gtl, anchors, mask, class_num,
                               ignore_thresh=0.7, downsample_ratio=32).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    rng = np.random.RandomState(0)

    def batch(size):
        img = paddle.to_tensor(rng.rand(bs, 3, size, size).astype("float32"))
        # ragged gt: random box count per image, padded to max_boxes with
        # zero-wh (invalid) boxes — the reference's LoD ragged layout
        gtb = np.zeros((bs, max_boxes, 4), np.float32)
        for i in range(bs):
            k = rng.randint(1, 20)
            cxy = rng.rand(k, 2) * 0.8 + 0.1
            wh = rng.rand(k, 2) * 0.2 + 0.05
            gtb[i, :k] = np.concatenate([cxy, wh], 1)
        gtl = rng.randint(0, class_num, (bs, max_boxes)).astype("int64")
        return img, paddle.to_tensor(gtb), paddle.to_tensor(gtl)

    data = {s: batch(s) for s in buckets}
    for s in buckets:  # one compile per bucket
        for _ in range(warmup):
            loss = step(*data[s])
    _sync(loss)
    order = [buckets[i % len(buckets)] for i in range(iters * len(buckets))]
    t0 = time.perf_counter()
    for s in order:
        loss = step(*data[s])
    _sync(loss)
    dt = time.perf_counter() - t0
    img_s = bs * len(order) / dt
    return {"metric": "detection_varshape_img_per_s_per_chip",
            "value": round(img_s, 1), "unit": "img/s", "backend": backend,
            "batch": bs, "shape_buckets": buckets,
            "compiles": len(step._cache),
            "loss": round(float(np.asarray(loss.numpy())), 3)}


def bench_hbm_cache():
    """HBM-resident embedding cache vs per-batch PS TCP pull/push
    (reference: the GPUPS speedup story, ps_gpu_wrapper.cc — device
    tables vs per-batch brpc round-trips). Same CTR lookup+sgd-update
    workload through both paths; reports the measured speedup."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.ps import (HbmEmbeddingCache, PsClient,
                                           PsServer, TableConfig)

    VOCAB, DIM, BATCH, STEPS = 200_000, 64, 4096, 30
    srv = PsServer([TableConfig(1000, "sparse", DIM, "sgd", lr=0.1,
                                init_range=0.1, seed=1000),
                    TableConfig(1001, "sparse", DIM, "sgd", lr=0.1,
                                init_range=0.1, seed=1000)], port=0)
    port = srv.start()
    cli = PsClient([f"127.0.0.1:{port}"])
    cli.register_sparse(1000, DIM)
    cli.register_sparse(1001, DIM)
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, VOCAB, BATCH).astype(np.int64)
               for _ in range(STEPS)]
    try:
        # direct path: pull rows, sgd on host-pulled slice, push grads —
        # one TCP round-trip pair per batch (the Downpour per-batch cost)
        t0 = time.perf_counter()
        for ids in batches:
            keys = np.unique(ids).astype(np.uint64)
            rows = cli.pull_sparse(1000, keys)
            g = np.ones_like(rows)
            cli.push_sparse_grad(1000, keys, g)
        direct_s = time.perf_counter() - t0

        import jax.numpy as jnp
        cache = HbmEmbeddingCache(cli, 1001, DIM, 1 << 18,
                                  optimizer="sgd", lr=0.1)
        cache.build_pass(np.concatenate(batches))  # BuildGPUPSTask

        def emb_loss(e):
            return jnp.sum(e)

        # compile warmup (program is keyed on (fn, K, shapes) — warm with
        # the same pass shape the timed run uses)
        cache.run_fused_pass(batches, emb_loss)
        t0 = time.perf_counter()
        # run_fused_pass transfers the per-batch losses out, which is a
        # true sync on the one program that did all the work
        losses = cache.run_fused_pass(batches, emb_loss)
        cached_s = time.perf_counter() - t0
        assert np.isfinite(losses).all()
        cache.end_pass()
        s = cache.stats
        return {"metric": "hbm_cache_speedup_vs_tcp", "value":
                round(direct_s / cached_s, 2), "unit": "x",
                "direct_ms_per_batch": round(direct_s / STEPS * 1e3, 2),
                "cached_ms_per_batch": round(cached_s / STEPS * 1e3, 2),
                "hit_rate": round(s["hit"] / max(1, s["hit"] + s["miss"]),
                                  4),
                "rows_per_batch": int(np.unique(batches[0]).size),
                "dim": DIM, "note": "cached = fused-pass lax.scan (one "
                "dispatch for all batches); direct = per-batch TCP "
                "pull+push on loopback"}
    finally:
        cli.stop_servers()
        srv.stop()


def bench_ctr():
    """CTR wide-and-deep through the async pipelined embedding cache
    (reference: the heter_ps overlap story, ps_gpu_wrapper.cc — pull
    next pass's rows while training the current one). Trains scan
    windows (to_static(scan_steps=k)) with a CachePrefetcher planning
    window N+1 during window N's compute and a WriteBackQueue pushing
    deltas behind it. TWO rows: sparse lookups/s/chip, and the overlap
    efficiency = pull time hidden behind compute / total pull time."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import ps
    from paddle_tpu.distributed.ps import (PsClient, PsServer, TableConfig,
                                           WriteBackQueue)
    from paddle_tpu.distributed.ps.communicator import SyncCommunicator
    from paddle_tpu.distributed.ps.embedding import reset_registry
    from paddle_tpu.models.ctr import (WideAndDeep, synthetic_ctr_batches,
                                       train_ctr_windows)

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    if on_tpu:
        vocab, dim, slots, batch, hidden = 2_000_000, 64, 16, 1024, (512, 256)
        k, windows, capacity = 16, 10, 1 << 18
    else:
        vocab, dim, slots, batch, hidden = 200_000, 32, 8, 512, (128, 64)
        k, windows, capacity = 8, 8, 1 << 16

    reset_registry()
    paddle.seed(0)
    tables = [TableConfig(1000, "sparse", dim, "sgd", lr=0.05,
                          init_range=0.05, seed=1000),
              TableConfig(1001, "sparse", 1, "sgd", lr=0.05,
                          init_range=0.05, seed=1001)]
    srv = PsServer(tables, port=0)
    port = srv.start()
    cli = PsClient([f"127.0.0.1:{port}"])
    wb = WriteBackQueue(cli)
    try:
        model = WideAndDeep(vocab, dim=dim, slots=slots, hidden=hidden,
                            cached=True, capacity=capacity,
                            optimizer="sgd", lr=0.05, writeback=wb)
        comm = SyncCommunicator(cli, n_workers=1)
        ps.bind_model(model, comm)
        opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                    learning_rate=0.001)
        batches = synthetic_ctr_batches((windows + 1) * k,
                                        batch_size=batch, slots=slots,
                                        vocab=vocab, seed=3)
        t0 = time.perf_counter()
        r = train_ctr_windows(model, opt, batches, k=k, prefetch=True,
                              depth=2, flush=True)
        wall = time.perf_counter() - t0
        assert np.isfinite(r["losses"]).all()
        lookups_s = r["lookups"] / wall
        common = dict(backend=backend, batch=batch, slots=slots, dim=dim,
                      k=k, windows=r["windows"], vocab=vocab)
        return [
            {"metric": "ctr_lookups_per_s_chip",
             "value": round(lookups_s, 1), "unit": "lookups/s",
             "loss_head": round(float(np.mean(r["losses"][:k])), 4),
             "loss_tail": round(float(np.mean(r["losses"][-k:])), 4),
             "note": "sparse id lookups (deep + wide tables) per second "
             "through the cached scan-window pipeline, write-back "
             "flushed", **common},
            {"metric": "ctr_overlap_efficiency",
             "value": round(r["overlap_efficiency"], 3), "unit": "frac",
             "pull_ms": round(r["pull_s"] * 1e3, 1),
             "wait_ms": round(r["wait_s"] * 1e3, 1),
             "note": "PS pull/plan time hidden behind window compute / "
             "total (first-window fill excluded); >0.5 = majority of "
             "pull latency overlapped", **common},
        ]
    finally:
        wb.stop(flush=False)
        cli.stop_servers()
        srv.stop()


def bench_serving():
    """Serving-engine smoke: concurrent ragged-batch traffic through the
    bucketed-AOT engine (paddle_tpu/serving/) over a saved StableHLO
    artifact. Reports served qps/chip plus the p50/p95/p99 request-latency
    summary the SLO telemetry exports — the serve-heavy-traffic half of
    the north star, gated like the training rows (presence-only on CPU)."""
    import tempfile
    import threading

    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.serving as serving
    from paddle_tpu.jit.io import save as jit_save
    from paddle_tpu.jit.to_static import InputSpec
    from paddle_tpu.observability import export as obs_export

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    if on_tpu:
        feat, hidden, ladder = 256, 1024, (1, 8, 32, 128)
        clients, reqs_per_client = 16, 40
    else:
        feat, hidden, ladder = 16, 32, (1, 4, 16)
        clients, reqs_per_client = 8, 15

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(feat, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, 8))
    model.eval()
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "m")
        jit_save(model, prefix,
                 input_spec=[InputSpec([None, feat], "float32")])
        engine = serving.Engine(prefix, bucket_ladder=ladder,
                                batch_timeout_ms=1.0)
    try:
        rng = np.random.RandomState(0)
        sizes = [1, 2, 3, 5, 8]
        batches = [rng.rand(s, feat).astype(np.float32) for s in sizes]
        for b in batches:  # warmup: request path must be compile-free
            engine.predict(b)
        obs_export.clear_summaries()  # in-place reset: warmup excluded,
        # the engine's cached board handles stay registered

        def client(seed):
            r = np.random.RandomState(seed)
            for _ in range(reqs_per_client):
                engine.predict(batches[r.randint(len(batches))])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        stats = engine.stats()
    finally:
        engine.close()
    n_req = clients * reqs_per_client
    lat = obs_export.summaries().get("serving_latency_ms", {})
    return {"metric": "serving_mlp_qps_per_chip",
            "value": round(n_req / dt, 1), "unit": "req/s",
            "backend": backend,
            "p50_ms": round(lat.get("p50", float("nan")), 3),
            "p95_ms": round(lat.get("p95", float("nan")), 3),
            "p99_ms": round(lat.get("p99", float("nan")), 3),
            "bucket_ladder": list(ladder),
            "aot_compiles": stats["aot_compiles"],
            "batches": stats["batches"],
            "multi_request_batches": stats["multi_request_batches"],
            "clients": clients}


def bench_checkpoint():
    """Checkpoint save+restore throughput through the crash-consistent
    core (paddle_tpu/checkpoint/): full training state (params + Adam
    moments + RNG) captured, hashed, fsynced and atomically published,
    then restored with content-hash validation. The number that bounds
    how often a preemptible-pool job can afford to checkpoint."""
    import shutil
    import tempfile

    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import checkpoint

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    hidden, saves = (2048, 4) if on_tpu else (512, 3)

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden), nn.ReLU(),
                          nn.Linear(hidden, hidden))
    opt = paddle.optimizer.Adam(parameters=model.parameters())
    root = tempfile.mkdtemp(prefix="pt_ckpt_bench_")
    try:
        mgr = checkpoint.CheckpointManager(root, keep_last_n=2)
        mgr.add_model(model).add_optimizer(opt)
        p0 = mgr.save(0)  # warm (dir creation, first pickle)
        n_bytes = sum(
            os.path.getsize(os.path.join(p0, f)) for f in os.listdir(p0))
        t0 = time.perf_counter()
        for i in range(1, saves + 1):
            mgr.save(i)
        save_s = (time.perf_counter() - t0) / saves
        t0 = time.perf_counter()
        meta = mgr.restore()
        restore_s = time.perf_counter() - t0
        assert meta is not None and meta["step"] == saves
        rt_mbps = 2 * n_bytes / (save_s + restore_s) / 1e6
        return {"metric": "checkpoint_save_restore_MBps",
                "value": round(rt_mbps, 1), "unit": "MB/s",
                "backend": backend,
                "state_mb": round(n_bytes / 1e6, 2),
                "save_ms": round(save_s * 1e3, 2),
                "restore_ms": round(restore_s * 1e3, 2),
                "keep_last_n": 2, "note": "atomic publish (fsync + "
                "manifest + rename) incl. hash validation on restore"}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_tracing_overhead():
    """Span-tracing overhead: spans/s through the full emission path
    (context ids + profiler buffer + flight ring) with tracing ON, vs
    the guarded no-op path with tracing OFF. The row that keeps the
    observability tax visible — a regression here is every instrumented
    hot path getting slower at once."""
    import paddle_tpu.observability as obs
    from paddle_tpu import profiler

    N = 20000

    def spin():
        t0 = time.perf_counter()
        for _ in range(N):
            with obs.trace_span("bench/span", cat="user"):
                pass
        return time.perf_counter() - t0

    import jax
    obs.disable()
    spin()  # warm
    off_s = spin()
    profiler.reset()
    obs.enable(categories=["user"])
    try:
        spin()  # warm (allocators, ring)
        profiler.reset()
        on_s = spin()
    finally:
        obs.disable()
        profiler.reset()
    return {"metric": "tracing_overhead_spans_per_s",
            "value": round(N / on_s, 1), "unit": "spans/s",
            "backend": jax.default_backend(),
            "span_ns_enabled": round(on_s / N * 1e9, 1),
            "span_ns_disabled": round(off_s / N * 1e9, 1),
            "note": "enabled = ids + profiler buffer + flight ring; "
            "disabled = shared null span (guard-only)"}


def bench_lockwatch_overhead():
    """Lock-order watchdog tax: uncontended acquire/release throughput
    of a plain threading.Lock vs the lockwatch factory DISARMED (must
    be the same object kind — the row asserts <2x) vs ARMED (the
    instrumented wrapper: held-set + edge-graph bookkeeping). The row
    that keeps the watchdog honest about 'near-zero cost when off' —
    and shows what the chaos tier pays for running deadlock-checked."""
    import threading

    import jax
    from paddle_tpu import _lockwatch as lockwatch

    N = 200_000

    def spin(lk):
        t0 = time.perf_counter()
        for _ in range(N):
            lk.acquire()
            lk.release()
        return time.perf_counter() - t0

    plain = threading.Lock()
    spin(plain)  # warm
    plain_s = min(spin(plain) for _ in range(3))

    was = lockwatch.disable()
    try:
        disarmed = lockwatch.Lock("bench.disarmed")
        spin(disarmed)
        disarmed_s = min(spin(disarmed) for _ in range(3))
        lockwatch.enable()
        armed = lockwatch.Lock("bench.armed")
        spin(armed)
        armed_s = min(spin(armed) for _ in range(3))
    finally:
        (lockwatch.enable if was else lockwatch.disable)()
        lockwatch.reset()

    disarmed_x = disarmed_s / plain_s
    if disarmed_x >= 2.0:
        raise RuntimeError(
            f"disarmed lockwatch lock costs {disarmed_x:.2f}x a plain "
            "threading.Lock (acceptance: <2x) — the opt-out path "
            "regressed")
    return {"metric": "lockwatch_overhead_ops_per_s",
            "value": round(N / armed_s, 1), "unit": "ops/s",
            "backend": jax.default_backend(), "gate": "presence",
            "plain_ns": round(plain_s / N * 1e9, 1),
            "disarmed_ns": round(disarmed_s / N * 1e9, 1),
            "armed_ns": round(armed_s / N * 1e9, 1),
            "disarmed_overhead_x": round(disarmed_x, 3),
            "armed_overhead_x": round(armed_s / plain_s, 3),
            "note": "uncontended acquire/release; disarmed factory "
            "returns a raw threading.Lock (the <2x acceptance is "
            "asserted in-bench), armed pays held-set + order-graph "
            "bookkeeping — host-dependent, presence-pinned"}


def bench_memory(n_virtual=8):
    """HBM memory accounting rows (observability.memory): compiled-step
    XLA attribution peak + per-rank state residency of a ZeRO-3 scan
    step on the 8-device mesh. Byte accounting is backend-deterministic
    (unlike wall time), so these rows VALUE-gate even between CPU runs
    — direction pinned lower-is-better: more bytes is a regression."""
    import jax
    if jax.device_count() < n_virtual:
        if jax.default_backend() == "cpu":
            return _reexec_bench("memory", n_virtual, all_records=True)
        return [{"metric": m, "value": -1.0, "unit": "MB",
                 "direction": "lower", "backend": jax.default_backend(),
                 "note": f"needs {n_virtual} devices (have "
                         f"{jax.device_count()})"}
                for m in ("mlp_zero3_scan_hbm_peak_mb",
                          "mlp_zero3_state_resident_mb")]
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import parallel_env
    from paddle_tpu.observability import memory

    dp, k = n_virtual, 4
    mesh = parallel_env.make_mesh({"dp": dp})
    parallel_env.set_mesh(mesh)
    try:
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 32))
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.01)
        opt._zero_enable(axis="dp", stage=3)

        def one(x, y):
            loss = nn.functional.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp")
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(k, 16, 64).astype("float32"))
        y = paddle.to_tensor(rng.randint(0, 32, (k, 16)).astype("int64"))
        step(x, y)
        stats = next(iter(step.memory_stats().values()))
        step.export_memory_stats()
        ledger = memory.export_state_ledger()
        # value-gate on THIS optimizer's flat stores walked directly —
        # the global ledger total picks up whatever stateful tensors
        # earlier in-process benches left alive, which would make a
        # 10%-tolerance gate on a small pinned value nondeterministic;
        # the ledger totals ride along as ungated metadata
        model_state = 0
        for sdict in opt._zero["stores"]:
            for store in sdict.values():
                _g, resident = memory.value_bytes(store.tensor._value)
                model_state += resident
        common = dict(backend=jax.default_backend(), unit="MB",
                      direction="lower", dp=dp, k=k)
        return [
            {"metric": "mlp_zero3_scan_hbm_peak_mb",
             "value": memory.mb(stats["peak_bytes"]),
             "argument_mb": memory.mb(stats["argument_bytes"]),
             "temp_mb": memory.mb(stats["temp_bytes"]),
             "alias_mb": memory.mb(stats["alias_bytes"]),
             "note": "XLA memory_analysis peak (arg+out+temp+code-alias) "
             "of the compiled zero3 scan step", **common},
            {"metric": "mlp_zero3_state_resident_mb",
             "value": memory.mb(model_state),
             "ledger_total_mb": memory.mb(ledger["total_bytes"]),
             "ledger_global_mb": memory.mb(ledger["total_global_bytes"]),
             "note": "per-rank resident zero3 model state (param + "
             "moment flat stores walked directly; 1/dp of the "
             "replicated layout); ledger totals ride as metadata",
             **common},
        ]
    finally:
        parallel_env.set_mesh(None)


def bench_overlap(n_virtual=8):
    """Collective overlap rows (observability.overlap): latency-hiding
    flag A/B over the ZeRO-3 scan step on the 8-device mesh. Both arms
    compile the same step program — control unflagged, treatment with
    the ``jit.xla_flags`` "latency-hiding" preset — and the schedule
    analyzer scores hidden vs exposed collective time from the compiled
    HLO. On XLA:CPU the scheduler emits synchronous collectives and the
    ``xla_tpu_*`` treatment flags fall back (recorded in the row), so
    both arms honestly report efficiency 0.0 / exposed 1.0 with
    ``backend_sync_schedule=True`` — the pinned-presence baseline the
    TPU re-capture replaces with a real A/B delta."""
    import jax
    if jax.device_count() < n_virtual:
        if jax.default_backend() == "cpu":
            return _reexec_bench("overlap", n_virtual, all_records=True)
        return [{"metric": m, "value": -1.0, "unit": "frac",
                 "backend": jax.default_backend(),
                 "note": f"needs {n_virtual} devices (have "
                         f"{jax.device_count()})"}
                for m in ("mlp_zero3_overlap_efficiency",
                          "mlp_zero3_exposed_collective_frac")]
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import parallel_env

    dp, k = n_virtual, 4
    mesh = parallel_env.make_mesh({"dp": dp})
    parallel_env.set_mesh(mesh)
    try:
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                          nn.Linear(128, 32))
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=0.01)
        opt._zero_enable(axis="dp", stage=3)

        def one(x, y):
            loss = nn.functional.cross_entropy(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(k, 16, 64).astype("float32"))
        y = paddle.to_tensor(rng.randint(0, 32, (k, 16)).astype("int64"))

        arms = {}
        for arm, flags in (("off", None), ("on", "latency-hiding")):
            step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp",
                                        xla_flags=flags)
            step(x, y)
            arms[arm] = {"stats": step.overlap_stats(),
                         "provenance": step.xla_flags()}
        on, off = arms["on"]["stats"], arms["off"]["stats"]
        prov = arms["on"]["provenance"]
        common = dict(
            backend=jax.default_backend(), unit="frac", dp=dp, k=k,
            async_pairs_total=on["async_pairs_total"],
            sync_total=on["sync_total"],
            backend_sync_schedule=on["backend_sync_schedule"],
            xla_flags_applied=prov["applied"],
            xla_flags_fallback=prov["fallback_error"],
            note=("latency-hiding flag A/B over the zero3 scan step; "
                  "value is the flags-on arm"
                  + ("; CPU backend schedules collectives "
                     "synchronously and rejects the xla_tpu_* "
                     "treatment flags, so both arms are the honest "
                     "sync-schedule baseline" if
                     on["backend_sync_schedule"] else "")))
        return [
            {"metric": "mlp_zero3_overlap_efficiency",
             "value": round(on["collective_overlap_efficiency"], 4),
             "flags_off_value":
                 round(off["collective_overlap_efficiency"], 4),
             **common},
            {"metric": "mlp_zero3_exposed_collective_frac",
             "value": round(on["exposed_collective_frac"], 4),
             "flags_off_value":
                 round(off["exposed_collective_frac"], 4),
             "exposed_ns_estimate": round(on["exposed_ns"], 1),
             **common},
        ]
    finally:
        parallel_env.set_mesh(None)


def bench_prefetch(n_virtual=8):
    """Latency-hiding ZeRO step A/B (``_zero_enable(prefetch=...)``):
    the double-buffered bucket pipeline vs the on-demand serial
    schedule, scored by the jaxpr-level schedulable-overlap meter
    (``overlap.schedulable_stats`` — emission-order headroom from the
    traced program, deterministic and backend-independent, so the row
    VALUE-gates between CPU runs; the compiled-text analyzer cannot see
    this structure because XLA re-sorts instructions into dependency
    postorder).

    Workload: the layer-aligned two-bucket MLP zero3 scan step
    (``comm_buffer_mb`` sized so bucket0={w1,b1}, bucket1={w2,b2}) —
    the config where the serial arm scores EXACTLY 0.0 (every gather's
    first consumer is adjacent) and any pipeline value is pure
    restructure. The bench asserts the two arms' losses are
    bitwise-equal before reporting: a score bought with different math
    would be a bug, not a win. Row:

    - ``mlp_zero3_schedulable_overlap`` — prefetch-on arm's score
      (direction up via the metric-suffix pin); the off arm's 0.0 and
      the per-collective windows ride as metadata
    """
    import jax
    if jax.device_count() < n_virtual:
        if jax.default_backend() == "cpu":
            return _reexec_bench("prefetch", n_virtual, all_records=True)
        return [{"metric": "mlp_zero3_schedulable_overlap",
                 "value": -1.0, "unit": "frac",
                 "backend": jax.default_backend(),
                 "note": f"needs {n_virtual} devices (have "
                         f"{jax.device_count()})"}]
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import parallel_env

    dp, k = n_virtual, 4
    mesh = parallel_env.make_mesh({"dp": dp})
    parallel_env.set_mesh(mesh)
    try:
        rng = np.random.RandomState(5)
        x = paddle.to_tensor(rng.rand(k, 16, 16).astype("float32"))
        y = paddle.to_tensor(rng.randint(0, 8, (k, 16)).astype("int64"))

        arms = {}
        for arm in ("off", "on"):
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                              nn.Linear(32, 8))
            opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                         learning_rate=0.01)
            opt._zero_enable(axis="dp", stage=3, comm_buffer_mb=0.003,
                             prefetch=arm == "on")

            def one(xb, yb):
                loss = nn.functional.cross_entropy(m(xb), yb)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp")
            losses = step(x, y).numpy()
            arms[arm] = {"sched": step.schedulable_stats(),
                         "losses": losses.tobytes(),
                         "mem": next(iter(
                             step.traced_memory_stats().values()))}
        on, off = arms["on"], arms["off"]
        if on["losses"] != off["losses"]:
            raise RuntimeError(
                "prefetch A/B arms diverged bitwise — the pipelined "
                "schedule changed the math")
        windowed = sum(1 for p in on["sched"]["pairs"]
                       if p["available_ns"] > 0)
        return [{
            "metric": "mlp_zero3_schedulable_overlap",
            "value": round(on["sched"]["schedulable_overlap"], 4),
            "unit": "frac", "backend": jax.default_backend(),
            "dp": dp, "k": k,
            "prefetch_off_value":
                round(off["sched"]["schedulable_overlap"], 4),
            "windowed_collectives": windowed,
            "jaxpr_peak_delta_bytes":
                on["mem"]["peak_bytes"] - off["mem"]["peak_bytes"],
            "source": on["sched"]["source"],
            "note": ("double-buffered bucket prefetch vs serial zero3 "
                     "step; emission-order overlap headroom from the "
                     "traced jaxpr (arms verified bitwise-equal; "
                     "serial control scores 0.0 on the layer-aligned "
                     "buckets)")}]
    finally:
        parallel_env.set_mesh(None)


def bench_remat(n_virtual=8):
    """Activation recompute A/B (paddle_tpu.recompute): BOTH sides of
    the memory-for-compute trade as value-gated rows. Workload: an
    FFN-block MLP (narrow 64-wide boundaries, 1024-wide ReLU+Dropout
    internals — the transformer-FFN residency shape) trained as a
    zero3 scan step on the 8-device mesh, each block a per-block remat
    segment.

    Meter: the jaxpr-liveness peak (``observability.jaxpr_mem``) — the
    XLA CPU pipeline strips optimization barriers and CSEs
    rematerialization away entirely (a remat'd and a plain step compile
    to byte-identical CPU executables), so executable-level
    ``memory_analysis()`` cannot show this trade on the smoke host; the
    traced-program liveness walk can, deterministically, and the TPU
    re-pin (ROADMAP) re-captures the executable view where barriers
    survive. Rows:

    - ``mlp_zero3_scan_jaxpr_peak_mb``  — control (remat=none)
    - ``mlp_zero3_remat_jaxpr_peak_mb`` — remat=full, SAME config;
      the bench itself asserts it lands strictly below the control
    - ``mlp_zero3_remat_b2x_jaxpr_peak_mb`` — remat=full at 2x batch;
      asserted <= the control's peak (the freed HBM converted to
      samples/step at no higher gated peak)
    """
    import jax
    if jax.device_count() < n_virtual:
        if jax.default_backend() == "cpu":
            return _reexec_bench("remat", n_virtual, all_records=True)
        return [{"metric": m, "value": -1.0, "unit": "MB",
                 "direction": "lower", "backend": jax.default_backend(),
                 "note": f"needs {n_virtual} devices (have "
                         f"{jax.device_count()})"}
                for m in ("mlp_zero3_scan_jaxpr_peak_mb",
                          "mlp_zero3_remat_jaxpr_peak_mb",
                          "mlp_zero3_remat_b2x_jaxpr_peak_mb")]
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import parallel_env
    from paddle_tpu.observability import memory

    dp, k, width, blocks, batch = n_virtual, 2, 1024, 6, 2048

    def capture(remat, bs):
        parallel_env.set_mesh(parallel_env.make_mesh({"dp": dp}))
        try:
            paddle.seed(0)
            blks = [nn.Sequential(nn.Linear(64, width), nn.ReLU(),
                                  nn.Dropout(0.1), nn.Linear(width, 64))
                    for _ in range(blocks)]
            m = nn.Sequential(*(blks + [nn.Linear(64, 32)]))
            m.train()
            opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                         learning_rate=0.01)
            opt._zero_enable(axis="dp", stage=3)
            if remat:
                for blk in blks:
                    blk.enable_recompute("full")

            def one(x, y):
                loss = nn.functional.cross_entropy(m(x), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss

            step = paddle.jit.to_static(one, scan_steps=k, dp_axis="dp")
            rng = np.random.RandomState(0)
            x = paddle.to_tensor(rng.rand(k, bs, 64).astype("float32"))
            y = paddle.to_tensor(rng.randint(0, 32, (k, bs))
                                 .astype("int64"))
            loss = step(x, y)
            traced = next(iter(step.traced_memory_stats().values()))
            xla = next(iter(step.memory_stats().values()))
            return traced, xla, float(np.asarray(loss.numpy())[-1])
        finally:
            parallel_env.set_mesh(None)

    ctl_t, ctl_x, ctl_loss = capture(False, batch)
    rem_t, rem_x, rem_loss = capture(True, batch)
    big_t, _big_x, _ = capture(True, 2 * batch)

    # the claim IS the comparison: a remat row that fails to undercut
    # its control is a broken policy surface, not a noisy measurement
    # (the meter is deterministic) — fail the bench, not just the gate
    if rem_t["peak_bytes"] >= ctl_t["peak_bytes"]:
        raise RuntimeError(
            f"remat=full did not reduce the traced peak: "
            f"{rem_t['peak_bytes']} >= {ctl_t['peak_bytes']}")
    if big_t["peak_bytes"] > ctl_t["peak_bytes"]:
        raise RuntimeError(
            f"remat=full at 2x batch exceeded the control peak: "
            f"{big_t['peak_bytes']} > {ctl_t['peak_bytes']}")
    if rem_loss != ctl_loss:
        raise RuntimeError(
            f"remat changed the math: loss {rem_loss} != {ctl_loss}")

    common = dict(backend=jax.default_backend(), unit="MB",
                  direction="lower", dp=dp, k=k, blocks=blocks,
                  width=width,
                  note="jaxpr-liveness peak (observability.jaxpr_mem); "
                  "XLA CPU strips remat barriers so executable "
                  "memory_analysis cannot meter this trade on the "
                  "smoke host (xla_* ride as metadata; TPU re-pin "
                  "captures the executable view)")
    return [
        {"metric": "mlp_zero3_scan_jaxpr_peak_mb",
         "value": memory.mb(ctl_t["peak_bytes"]), "batch": batch,
         "xla_temp_mb": memory.mb(ctl_x["temp_bytes"]),
         "xla_peak_mb": memory.mb(ctl_x["peak_bytes"]),
         "loss": round(ctl_loss, 6), **common},
        {"metric": "mlp_zero3_remat_jaxpr_peak_mb",
         "value": memory.mb(rem_t["peak_bytes"]), "batch": batch,
         "policy": "full",
         "vs_control_mb": memory.mb(ctl_t["peak_bytes"]),
         "saved_frac": round(1 - rem_t["peak_bytes"]
                             / ctl_t["peak_bytes"], 4),
         "xla_temp_mb": memory.mb(rem_x["temp_bytes"]),
         "xla_peak_mb": memory.mb(rem_x["peak_bytes"]),
         "host_offload_mb": memory.mb(
             rem_x.get("host_offload_bytes", 0)),
         "loss": round(rem_loss, 6), **common},
        {"metric": "mlp_zero3_remat_b2x_jaxpr_peak_mb",
         "value": memory.mb(big_t["peak_bytes"]), "batch": 2 * batch,
         "policy": "full", "batch_multiplier": 2.0,
         "vs_control_mb": memory.mb(ctl_t["peak_bytes"]),
         "samples_per_step": 2 * batch * k, **common},
    ]


def bench_pod_recovery():
    """Elastic recovery wall time: a 2-process virtual pod, rank 1
    SIGKILLed mid-step, supervised respawn under the shared
    RestartPolicy — the row is seconds from the supervisor reaping the
    kill to the HEALED world's resumed training (detect -> shrink
    reform -> respawn -> lobby -> grow reform -> elastic restore ->
    resume). The number that bounds how fast a preempted rank comes
    back at full throughput."""
    import re
    import shutil
    import tempfile

    from paddle_tpu.testing.virtual_pod import RestartPolicy, VirtualPod

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixture = os.path.join(repo, "tests", "fixtures",
                           "virtual_pod_fixture.py")
    wd = tempfile.mkdtemp(prefix="pt_pod_recovery_")
    root = os.path.join(wd, "ck")
    try:
        pod = VirtualPod(
            2, fixture, workdir=wd, kill=(1, "pod/mid_step", 5),
            lease_ttl=2.0,
            restart=RestartPolicy(max_restarts=2, base_delay=0.2, seed=0),
            env={"POD_FIX_CKPT_ROOT": root, "POD_FIX_TARGET_WORLD": "2",
                 "POD_FIX_HEAL_BY_STEP": "6"})
        exits = pod.run(timeout=240)
        kills = [e for e in pod.exit_history
                 if e.rank == 1 and e.signal == "SIGKILL"]
        log0 = pod.log(0)
        grow = None
        for m in re.finditer(r"REFORMED rank=\d+ world=(\d+) gen=(\d+) "
                             r"dir=grow t=([\d.]+)", log0):
            grow = m
        resume = None
        if grow is not None:
            resume = re.search(r"RESUME_FROM \d+ t=([\d.]+)",
                               log0[grow.end():])
        if not kills or resume is None:
            raise RuntimeError(
                "pod recovery cycle did not complete: "
                f"exits={exits} log0 tail: {log0[-800:]}")
        recovery_s = float(resume.group(1)) - kills[0].t_reaped
        healed_gen = int(grow.group(2))
    finally:
        shutil.rmtree(wd, ignore_errors=True)
    return {"metric": "pod_recovery_s", "value": round(recovery_s, 2),
            "unit": "s", "direction": "lower", "backend": "cpu",
            "world": 2, "healed_gen": healed_gen,
            "note": "SIGKILL reap -> shrink reform -> supervised "
            "respawn (RestartPolicy backoff) -> lobby join -> grow "
            "reform -> elastic restore -> first healed resume; "
            "includes one full python+jax process boot (~2-4s of it)"}


def bench_bert():
    """Config 3: the flagship BERT pretraining step — bench.py (it owns
    program structure and timing) run IN THIS PROCESS, which holds the
    chip: a child could not reach it. Its record folds into the ladder
    so `--gate` covers the headline metric too; a CPU dry-run has no
    record to fold."""
    import bench
    rec = bench.measure([])
    return [] if rec is None else rec


BENCHES = {"resnet": bench_resnet50, "gpt": bench_gpt_sharding_pp,
           "allreduce": bench_allreduce, "detection": bench_detection,
           "hbm_cache": bench_hbm_cache, "ctr": bench_ctr,
           "serving": bench_serving, "checkpoint": bench_checkpoint,
           "tracing_overhead": bench_tracing_overhead,
           "lockwatch_overhead": bench_lockwatch_overhead,
           "memory": bench_memory, "remat": bench_remat,
           "overlap": bench_overlap, "prefetch": bench_prefetch,
           "pod_recovery": bench_pod_recovery,
           "bert": bench_bert}


def run_benches(configs):
    """Run the named configs, printing one JSON record per line (errors
    become ``{"metric": name, "error": ...}`` records so the rest of the
    ladder still runs; a bench may return a LIST of records — the ctr
    config reports lookups/s + overlap efficiency). Returns
    ``(records, any_errored)`` — the single bench-loop implementation
    shared with tools/perf_gate.py."""
    results, failed = [], False
    for name in configs.split(","):
        name = name.strip()
        try:
            recs = BENCHES[name]()
            if not isinstance(recs, list):
                recs = [recs]
        except Exception as e:
            recs = [{"metric": name, "error": str(e)[:300]}]
            failed = True
        for rec in recs:
            print(json.dumps(rec), flush=True)
            results.append(rec)
    return results, failed


DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BASELINE_PERF.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="resnet,gpt,allreduce,detection,"
                    "hbm_cache,ctr,serving,checkpoint,tracing_overhead,"
                    "lockwatch_overhead,memory,remat,overlap,prefetch,"
                    "pod_recovery,bert")
    ap.add_argument("--out", help="write the run's records as a JSON file")
    ap.add_argument("--results", help="gate a previously recorded results "
                    "JSON instead of running the ladder")
    ap.add_argument("--gate", nargs="?", const=DEFAULT_BASELINE,
                    help="baseline JSON to gate against (exit 2 on "
                    "regression); no value = the pinned repo baseline "
                    "BASELINE_PERF.json")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="fractional noise allowance (default 0.10)")
    ap.add_argument("--write-baseline", dest="write_baseline",
                    help="store this run's records as a gate baseline")
    args = ap.parse_args()
    from paddle_tpu.observability import gate as gate_mod

    failed = False
    if args.results:
        results = list(gate_mod.load_results(args.results).values())
    else:
        results, failed = run_benches(args.configs)

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results}, f, indent=1)
    if args.write_baseline:
        # a perf baseline is only meaningful for programs the static
        # analyzer accepts: verify the ladder's program miniatures —
        # including the shardcheck sharding/collective-budget rules —
        # and refuse to pin from an unverified ladder (tools/
        # lint_program.py --ladder is the standalone front-end)
        from paddle_tpu.analysis import errors, format_findings, ladder
        bad = errors(ladder.verify_ladder()[0])
        if bad:
            print("refusing to pin a baseline: ladder program "
                  "verification failed\n" + format_findings(bad),
                  flush=True)
            return 1
        n = gate_mod.write_baseline(results, args.write_baseline)
        print(f"wrote {n} baseline metrics to {args.write_baseline}",
              flush=True)
    if args.gate:
        tol = (args.tolerance if args.tolerance is not None
               else gate_mod.DEFAULT_TOLERANCE)
        ok, report = gate_mod.compare(
            gate_mod.load_results(args.gate),
            {r["metric"]: r for r in results if "metric" in r},
            tolerance=tol)
        print(gate_mod.format_report(report), flush=True)
        if not ok:
            print("PERF GATE: FAIL", flush=True)
            return 2
        print("PERF GATE: PASS", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
