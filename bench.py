"""Flagship benchmark: BERT-base MLM pretraining step, bf16, whole-program XLA.

On a chip whose bf16 peak is published (observability/step.py
PEAK_BF16_FLOPS) prints ONE JSON line: {"metric", "value", "unit",
"backend", "device_kind", "vs_baseline"}. The reference publishes no
numbers (BASELINE.md); the north-star target is 50% MFU for BERT-base
pretraining — vs_baseline reports measured_MFU / 0.50. On a CPU the same
command is a DRY-RUN at a miniature size: it proves the path and prints
no metric line. An accelerator that is not in the peak table is an error.
Nothing here shrinks the cell to make it fit: an OOM fails the run.

Program structure (each kept because it won the round-2..4 A/Bs on the
shared v5e of that time — history, not re-measured on today's machine):
- ONE compiled program per k training steps (k-unroll amortizes the
  per-execute dispatch overhead). k=20 beat k=16 by ~2.2% (k=32 compiled
  >10 min; don't).
- PURE-bf16 parameters with fp32 master weights in AdamW
  (multi_precision): halves the param-read HBM traffic the O1 auto_cast
  paid per use; +0.5% back-to-back, composes with k=20 (0.511→0.525 MFU,
  benchmarks/ab_mfu.py k16 vs k20_bf16).
- jax.lax.optimization_barrier between the backward and the AdamW update:
  without it XLA interleaves the update fusions with the backward matmuls
  and their HBM throughput drops ~3x (the round-2 fix was a separate
  program; the barrier gets the same effect without the program boundary).
- Timing reports the best of N windows (6 on TPU), each closed by
  ``block_until_ready`` — what rounds 1-5 reported; the ledgered
  benchmark (ROADMAP S1) replaces it with a median and its spread.
- `--scan` switches the program structure from the python-unrolled k-step
  body to the scan-compiled step program (`to_static(one_step,
  scan_steps=k)`, stacked [k, ...] batch as scan xs): same math, compile
  time ~independent of k — use it with `--k 32`/`--k 64`, where the
  unrolled trace/compile is prohibitive (>10 min). Steady-state MFU of
  both structures is compared back-to-back in benchmarks/ab_mfu.py.
"""
import argparse
import json
import sys
import time

import numpy as np


def measure(argv=None):
    """Build, warm and time the step program. Returns the metric record,
    or None for the CPU dry-run (which reports no device metric)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scan", action="store_true",
                    help="scan-compiled step program instead of the "
                         "python-unrolled k-step body")
    ap.add_argument("--k", type=int, default=None,
                    help="dispatch-amortization factor (steps per "
                         "compiled program); default 20 TPU / 2 CPU")
    ap.add_argument("--zero", type=int, default=0, choices=(0, 1, 2, 3),
                    help="ZeRO stage: shard optimizer state (moments + "
                         "fp32 masters) 1/dp per chip, bucketed "
                         "psum_scatter grad reduction + param all_gather "
                         "inside the scan step (implies --scan; dp = all "
                         "local devices). Stage 3 also shards the "
                         "PARAMETERS 1/dp: per-bucket all_gather "
                         "materializes them just-in-time before forward "
                         "and the update writes only shard rows")
    ap.add_argument("--prefetch", default="on", choices=("on", "off"),
                    help="latency-hiding ZeRO step (default on): "
                         "double-buffered bucket pipeline — next "
                         "bucket's param all_gather is emitted under "
                         "the current bucket's compute, grad "
                         "reduce-scatter under the next bucket's "
                         "update, and the step tail re-gathers bucket "
                         "0 into a carry slot so the next step starts "
                         "warm. 'off' keeps the on-demand serial "
                         "schedule (the A/B control; bitwise-equal "
                         "losses either way)")
    ap.add_argument("--accumulate", type=int, default=1,
                    help="gradient-accumulation window: group the k "
                         "inner steps into k/N windows, optimizer "
                         "update + reduce/all_gather once per window "
                         "(cuts collective bytes per step ~N x for "
                         "zero<=1; needs k %% N == 0)")
    ap.add_argument("--remat", default="none",
                    choices=("none", "full", "selective", "offload"),
                    help="activation-recompute policy applied per "
                         "encoder layer (paddle_tpu.recompute): trade "
                         "recompute FLOPs (full), saved matmul outputs "
                         "(selective), or host traffic (offload — falls "
                         "back loudly to selective without a "
                         "pinned_host memory space) for the HBM the "
                         "backward otherwise holds — then spend it on "
                         "--batch/--k")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the per-step batch size (the knob "
                         "the remat-freed HBM buys back)")
    args_cli = ap.parse_args(argv)
    if args_cli.zero:
        args_cli.scan = True  # ZeRO is an option of the scan step program
    if args_cli.accumulate > 1:
        args_cli.scan = True  # accumulation windows live in the scan step

    import jax
    import jax.lax as lax

    import paddle_tpu as paddle
    from paddle_tpu.models import BertConfig, BertForPretraining, synthetic_mlm_batch
    from paddle_tpu.observability.step import StepTimer, peak_bf16_flops

    backend = jax.default_backend()
    on_tpu = backend != "cpu"
    device_kind = jax.devices()[0].device_kind
    # an accelerator with no published peak raises HERE, before any work
    peak = peak_bf16_flops(device_kind) if on_tpu else None

    paddle.seed(0)
    if on_tpu:
        cfg = BertConfig(vocab_size=30720, hidden_dropout=0.0,
                         attention_dropout=0.0)  # base, vocab padded to 128x
        batch, seq, k, iters, warmup, windows = 16, 512, 20, 1, 1, 6
    else:
        cfg = BertConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                         num_heads=4, intermediate_size=512,
                         hidden_dropout=0.0, attention_dropout=0.0)
        batch, seq, k, iters, warmup, windows = 4, 128, 2, 2, 1, 1
    if args_cli.k:
        k = args_cli.k
    if args_cli.batch is not None:
        if args_cli.batch < 1:
            raise SystemExit(f"--batch must be >= 1, got {args_cli.batch}")
        batch = args_cli.batch

    dp = 1
    if args_cli.zero:
        from paddle_tpu.distributed import parallel_env
        dp = jax.device_count()
        parallel_env.set_mesh(parallel_env.make_mesh({"dp": dp}))
        if batch % dp:
            batch = max(dp, batch - batch % dp)

    model = BertForPretraining(cfg)
    if args_cli.remat != "none":
        # per-encoder-layer remat segments (the granularity that pays:
        # layer boundaries are the only fwd->bwd residuals left; each
        # layer's attention/FFN internals rematerialize in backward)
        for layer in model.bert.layers:
            layer.enable_recompute(args_cli.remat)
    if on_tpu:
        model.to("bfloat16")  # pure-bf16 params, fp32 masters in AdamW
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4,
                                 multi_precision=on_tpu)
    if args_cli.zero:
        n_sharded = opt._zero_enable(axis="dp", stage=args_cli.zero,
                                     prefetch=args_cli.prefetch == "on")
        print(f"# zero{args_cli.zero}: dp={dp} sharded_stores={n_sharded} "
              f"state_bytes/chip={opt._zero_state_bytes()} "
              f"prefetch={args_cli.prefetch}",
              file=sys.stderr)
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

    if args_cli.scan:
        # scan-compiled program: one traced body rolled k times; the
        # [k, ...]-stacked batch is the scan xs (same microbatch repeated
        # here, matching the unrolled control's batch reuse). Under
        # --zero the scan runs inside shard_map over 'dp' and the AdamW
        # update is the sharded bucketed-psum_scatter step. --accumulate
        # groups the k steps into windows with one update each.
        if args_cli.accumulate > 1 and k % args_cli.accumulate:
            raise SystemExit(f"--k {k} must be a multiple of "
                             f"--accumulate {args_cli.accumulate}")
        step = paddle.jit.to_static(
            one_step, scan_steps=k,
            dp_axis="dp" if args_cli.zero else None,
            accumulate_steps=(args_cli.accumulate
                              if args_cli.accumulate > 1 else None))
    else:
        def k_steps(ids, tok, labels, nsp_labels):
            for _ in range(k):
                loss = one_step(ids, tok, labels, nsp_labels)
            return loss

        step = paddle.jit.to_static(k_steps)

    # window telemetry cross-check: the per-model FLOP count (not the
    # 6*N*T estimate) drives the exported MFU gauge (no peak, no MFU)
    flops_per_token = model.flops_per_token(seq)
    timer = StepTimer(window=max(windows * iters, 2),
                      flops_per_token=flops_per_token if on_tpu else None,
                      peak_flops=peak, publish_as="bench")

    def run(bs):
        ids, tok, labels, nsp = synthetic_mlm_batch(bs, seq,
                                                    vocab_size=cfg.vocab_size)
        if args_cli.scan:
            stack = lambda a: np.broadcast_to(a, (k,) + a.shape).copy()
            ids, tok, labels, nsp = (stack(a) for a in
                                     (ids, tok, labels, nsp))
        t_ids = paddle.to_tensor(ids)
        t_tok = paddle.to_tensor(tok)
        t_lab = paddle.to_tensor(labels)
        t_nsp = paddle.to_tensor(nsp)
        args = (t_ids, t_tok, t_lab, t_nsp)
        t_compile = time.perf_counter()
        for _ in range(warmup):
            loss = step(*args)
        jax.block_until_ready(loss._value)  # warm-up done before timing
        t_compile = time.perf_counter() - t_compile
        print(f"# first-call (trace+compile+run) {t_compile:.1f}s "
              f"structure={'scan' if args_cli.scan else 'unroll'} k={k}",
              file=sys.stderr)
        best = 0.0
        timer.start()
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = step(*args)
            jax.block_until_ready(loss._value)  # the steps chain on state
            dt = time.perf_counter() - t0
            timer.step(tokens=bs * seq * iters * k)
            best = max(best, bs * seq * iters * k / dt)
        last = loss[-1] if args_cli.scan else loss
        return best, float(last.numpy())

    tokens_per_s, loss_val = run(batch)

    diag = (f"# backend={backend} device_kind={device_kind} batch={batch} "
            f"seq={seq} k={k} "
            f"structure={'scan' if args_cli.scan else 'unroll'} "
            f"zero={args_cli.zero} accumulate={args_cli.accumulate} "
            f"remat={args_cli.remat} loss={loss_val:.3f}")
    result = None
    if on_tpu:
        mfu = tokens_per_s * flops_per_token / peak
        result = {
            "metric": "bert_base_pretrain_tokens_per_s_per_chip",
            "value": round(tokens_per_s, 1),
            "unit": "tokens/s",
            "backend": backend,
            "device_kind": device_kind,
            "vs_baseline": round(mfu / 0.50, 4),
        }
        diag += (f" mfu={mfu:.3f} "
                 f"timer_mfu={timer.telemetry().get('mfu', 0.0):.3f}")
    else:
        diag += " dry-run: a CPU run reports no device metric"
    print(diag, file=sys.stderr)
    if args_cli.remat != "none":
        # memory side of the trade: XLA attribution (meaningful on TPU,
        # where barriers survive) + the backend-independent jaxpr
        # liveness peak (the meter that shows remat even on CPU) — run
        # `--remat none` back to back for the A/B
        xs = next(iter(step.memory_stats().values()))
        ts = next(iter(step.traced_memory_stats().values()))
        print(f"# remat memory: xla_temp={xs['temp_bytes']} "
              f"xla_peak={xs['peak_bytes']} "
              f"host_offload={xs.get('host_offload_bytes', 0)} "
              f"jaxpr_peak={ts['peak_bytes']}", file=sys.stderr)
    if args_cli.zero or args_cli.accumulate > 1:
        # after the timed windows (the AOT stats path recompiles once):
        # the psum_scatter-vs-psum evidence for this structure, plus the
        # per-execution view (trip-count-weighted) that shows the
        # accumulation window dividing reduction traffic
        stats = step.export_collective_bytes()
        top = ", ".join(f"{s['op']}[{s['axis']}] {s['bytes']}B"
                        f"x{s['count']}" for s in stats[:4])
        print(f"# in-trace collectives: {top}", file=sys.stderr)
        per_exec = step.collective_stats(per_execution=True)
        top = ", ".join(f"{s['op']}[{s['axis']}] {s['bytes']}B"
                        f"x{s['count']}" for s in per_exec[:4])
        print(f"# per-execution collectives: {top}", file=sys.stderr)
    if args_cli.zero:
        # the --prefetch A/B's structural evidence: emission-order
        # overlap headroom from the traced jaxpr (backend-independent —
        # the number the mlp_zero3_schedulable_overlap row gates)
        sched = step.schedulable_stats()
        print(f"# schedulable overlap: "
              f"{sched['schedulable_overlap']:.4f} "
              f"(prefetch={args_cli.prefetch}, "
              f"source={sched['source']})", file=sys.stderr)
    return result


def main(argv=None):
    result = measure(argv)
    if result is not None:
        print(json.dumps(result))


if __name__ == "__main__":
    main()
