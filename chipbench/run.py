"""chipbench: run one cell once.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A new process each time: it takes the cell's chips (no TPU, or another
device count than the cell's `chips`, is an error — never a CPU), builds
the configuration's model through the program's public API, sets every
parameter from `--seed`, warms the one program the cell uses, measures
for `--seconds`, holds the timed step's first call against the plain
reference, prints one JSON object as its last line and exits.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import check, manifest, trace as trace_mod, window  # noqa: E402
from chipbench.models import _common  # noqa: E402

TRACED_CALLS = 6
WARM_CALLS = 2  # after the first: the carry's second entry, then steady


def info(kind, **fields):
    """An earlier line of standard output (never the last)."""
    print(json.dumps({"info": kind, **fields}), flush=True)


def take_devices(chips, require_tpu):
    """The first jax call of the process: the cell's chips or no run."""
    import jax

    devices = jax.devices()
    if not require_tpu:
        return devices[:chips]
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chipbench: needs a TPU; jax found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}). "
            f"Nothing was run.")
    if len(devices) != chips:
        raise SystemExit(
            f"chipbench: the cell asks for {chips} chip(s), jax reports "
            f"{len(devices)} device(s). Nothing was run.")
    return devices


class CompileCounter:
    """Backend-compile events, by jax.monitoring (installed once)."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event.endswith("backend_compile_duration"):
            self.count += 1


def program_readings(model, opt, model_mod, cfg, seed):
    """Leaf by leaf, the norms of AdamW's first moment and of the fp32
    master's change from the seeded start, read from the public
    `state_dict()` one stacked key at a time."""
    import functools

    import jax
    import jax.numpy as jnp

    names = model_mod.program_names(cfg)
    start = _common.init_weights(model_mod.weight_shapes(cfg),
                                 cfg["initializer_range"], seed,
                                 cfg["training"]["param_dtype"])
    state = opt.state_dict()
    members = {}
    for pname, param in model.named_parameters():
        key, layer = names[pname]
        members.setdefault(key, []).append((layer, param.name))

    @functools.partial(jax.jit, static_argnames=("key", "stacked"))
    def norms(moment, master, first, key, stacked):
        reduce = functools.partial(
            check.leaf_norms, stacked={key} if stacked else set(),
            splits=_common.LEAF_SPLITS)
        delta = master - first.astype(jnp.float32)
        return reduce({key: moment})[key], reduce({key: delta})[key]

    moment, change = {}, {}
    for key, group in members.items():
        stacked = group[0][0] is not None
        group.sort(key=lambda m: m[0] if stacked else 0)

        def pick(slot):
            arrays = [state[f"{name}.{slot}"]._value for _l, name in group]
            return jnp.stack(arrays) if stacked else arrays[0]

        moment[key], change[key] = jax.device_get(norms(
            pick("moment1"), pick("master"), start[key], key, stacked))
    return {"moment": check.flatten(moment), "change": check.flatten(change)}


def reference_readings(ref_mod, model_mod, cfg, cell, seed, batches,
                       precision="float32", rows=None):
    """The plain reference over the first call's batches. `rows` keeps
    only those rows of every batch (a planted fault of limits.py)."""
    import functools

    import jax
    import jax.numpy as jnp
    from chipbench.reference import common

    shapes = model_mod.weight_shapes(cfg)
    stacked = set(model_mod.stacked_keys())
    shardings = None
    if cell["chips"] > 1:
        # so that it fits: every large leaf split over the cell's chips
        # along its first axis that divides (never the layers' axis,
        # which the reference scans over); XLA places the rest
        mesh = jax.sharding.Mesh(jax.devices()[:cell["chips"]], ("chips",))
        shardings = {}
        for key, (shape, _kind) in shapes.items():
            axes = [None] * len(shape)
            for axis in range(1 if key in stacked else 0, len(shape)):
                if shape[axis] % cell["chips"] == 0 and len(shape) > 1:
                    axes[axis] = "chips"
                    break
            shardings[key] = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(*axes))

    def make_w0():
        return _common.init_weights(
            shapes, cfg["initializer_range"], seed,
            cfg["training"]["param_dtype"], out_dtype=jnp.float32,
            shardings=shardings)

    steps = [tuple(jnp.asarray(a[i] if rows is None else a[i][rows])
                   for a in batches) for i in range(cell["k"])]
    out = common.train(
        functools.partial(ref_mod.loss_fn, cfg=cfg, precision=precision),
        make_w0, steps, cfg["training"],
        functools.partial(check.leaf_norms, stacked=stacked,
                          splits=_common.LEAF_SPLITS))
    return {"losses": out["losses"],
            **{k: check.flatten(out[k])
               for k in ("grad", "moment", "change")}}


def device_record(devices):
    peak = None
    for dev in devices:
        stats = dev.memory_stats()
        if stats is None:
            if dev.platform == "tpu":
                raise RuntimeError(f"{dev} reports no memory_stats()")
            continue
        peak = max(peak or 0, stats["peak_bytes_in_use"])
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(workload, seed, seconds, traced, bench=None, require_tpu=True):
    """One run of one cell; returns the result object of the last line.
    `bench` is the Manifest (the tests pass their fixture's) and
    `require_tpu=False` is the tests' way past the look for a chip."""
    marks = [("process", T_PROCESS)]

    def mark(name):
        marks.append((name, time.time()))

    bench = bench or manifest.Manifest()
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    model_mod, ref_mod = manifest.family(cfg["family"])
    devices = take_devices(cell["chips"], require_tpu)

    import jax
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.distributed import parallel_env
    from paddle_tpu.jit import compile_cache

    # where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache;
    # before the benchmark's own first jit and with no floor on compile
    # time, so that every small program of set-up is cached too (about
    # 60-80 of them: 8 s of a GPT run's set-up, 15 s of a BERT run's)
    if require_tpu:
        compile_cache.enable(min_compile_time_secs=0.0)
    compiles = CompileCounter()
    mark("imports")
    k = cell["k"]
    tokens_per_step = cell["batch"] * cell["seq"]
    if cell.get("dp_axis"):
        parallel_env.set_mesh(parallel_env.make_mesh(
            {cell["dp_axis"]: cell["chips"]}, devices=devices))

    def feed(call):
        arrays = _common.stack_steps(model_mod.make_batch, cfg, cell, seed,
                                     call * k, k)
        return [paddle.to_tensor(a) for a in arrays]

    # ---- set-up: weights, build, the first call, its readings, warm-up
    weights = _common.init_weights(
        model_mod.weight_shapes(cfg), cfg["initializer_range"], seed,
        cfg["training"]["param_dtype"])
    jax.block_until_ready(weights)
    mark("weights")
    step, model, opt = model_mod.build_step(cfg, cell, weights)
    del weights
    mark("build")
    t0 = time.perf_counter()
    first = step(*feed(0))
    first_losses = [float(x) for x in first.numpy().ravel()]
    first_call_s = time.perf_counter() - t0
    cache = {"hits": monitor.stat_get("jit_persistent_cache_hits"),
             "misses": monitor.stat_get("jit_persistent_cache_misses")}
    mark("first_call")
    program = dict(program_readings(model, opt, model_mod, cfg, seed),
                   losses=first_losses)
    mark("readings")
    warm = window.drive(step, feed, first_call=1, calls=WARM_CALLS)
    mark("warm_calls")
    setup_s = marks[-1][1] - T_PROCESS
    # ---- the window
    compiled_before = compiles.count
    record = window.drive(step, feed, first_call=warm["next_call"],
                          seconds=seconds)
    window_compiles = compiles.count - compiled_before
    summary = window.summarize(record, k, tokens_per_step, cell["chips"])
    summary["spans"] = record["spans"]
    run = {"cell": cell, "k": k, "setup_s": setup_s,
           "first_call_s": first_call_s, "window": summary,
           "window_compiles": window_compiles,
           "peaks": manifest.peaks(devices[0].device_kind)
           if devices[0].platform == "tpu" else None,
           "flops_per_token": model_mod.flops_per_token(cfg, cell["seq"]),
           "attention_calls": model_mod.attention_calls(cfg, cell)}
    # ---- a traced slice of a few calls, in traced runs only
    if traced:
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            try:
                slice_ = window.drive(step, feed,
                                      first_call=record["next_call"],
                                      calls=TRACED_CALLS)
            finally:
                jax.profiler.stop_trace()
            profile = trace_mod.load(tmp)
        # the trace also holds the call that was in flight when the
        # slice's last completion came: `drive` waits for it
        run["traced"] = {
            "trace": profile, "busy": trace_mod.busy(profile),
            "device_steps": (slice_["next_call"] - record["next_call"]) * k}
        hlo = step.hlo_text()
        run["kernel_names"] = trace_mod.custom_call_names(hlo)
        if cell["chips"] > 1:
            run["collective_stats"] = step.collective_stats(
                per_execution=True)
    device = device_record(devices)
    info("program", first_call_s=first_call_s, setup_s=setup_s,
         setup_parts_s={name: t - before for (name, t), (_n, before)
                        in zip(marks[1:], marks)},
         first_call_losses=first_losses,
         last_call_losses=record["losses"][-1],
         step_ms_p90_samples=len(summary["step_s"]),
         calls=len(record["done"]), window_s=summary["elapsed_s"],
         window_compiles=window_compiles, persistent_cache=cache,
         xla_flags=step.xla_flags(),
         peak_bytes_in_use=[(d.memory_stats() or {}).get(
             "peak_bytes_in_use") for d in devices],
         memory_analysis=step.memory_stats() if traced else
         "in traced runs (costs an AOT compile)")
    # ---- metrics, by their files
    metrics = {}
    for spec in bench.metrics_of(workload, traced):
        value = manifest.reader(spec["reader"]).read(run, spec["args"])
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    result = {"correct": None, "attempted": summary["attempted"],
              "failed": summary["failed"], "metrics": metrics,
              "device": device}
    if traced:
        busy = run["traced"]["busy"]
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(run["traced"]["trace"]),
            "idle_gaps": trace_mod.idle_gaps(run["traced"]["trace"])}
    # ---- correct: the first call against the plain reference, once the
    # window has closed, the peak has been read and the state is freed
    batches = _common.stack_steps(model_mod.make_batch, cfg, cell, seed,
                                  0, k)
    del step, model, opt, first, run
    parallel_env.set_mesh(None)
    gc.collect()
    t0 = time.perf_counter()
    reference = reference_readings(ref_mod, model_mod, cfg, cell, seed,
                                   batches)
    compared = check.compare(program, reference)
    ok, rows = check.verdict(compared, cell["limits"])
    info("reference", seconds=time.perf_counter() - t0,
         losses=reference["losses"],
         next_widest={n: row["next"] for n, row in compared.items()
                      if "next" in row})
    result["correct"] = bool(ok and summary["failed"] == 0)
    result["compared"] = rows
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']:.6g} limit {row['limit']} "
              f"at {row['at']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
