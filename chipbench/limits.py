"""The readings a cell's limits are set from, taken on the chip.

    python3 chipbench/limits.py --workload <name> --seeds 3 [--first 900]

No program runs here. For each seed the plain reference follows the
cell's first call at the cell's own size, and in its place (as the
"program" of `check.compare`) are put, in turn:

  control      the same reference with every matrix product's operands
               rounded to float8_e4m3 (per-tensor scaled): one precision
               below the bfloat16 the configuration states;
  half_batch   the reference given the first half of every batch's rows,
               its mean taken over those;
  one_chip     (cells on several chips) the reference given one chip's
               rows only: what a chip computes when the exchange between
               chips is left out;
  bfloat16     the reference with operands rounded to bfloat16 — not a
               fault: a second witness of what sound bf16 runs read.

A state left unchanged reads 1 for `change` by the measure and needs no
run. Each line of output is one JSON object; PERF.md quotes them.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import check, manifest  # noqa: E402
from chipbench.models import _common  # noqa: E402
from chipbench.run import reference_readings, take_devices  # noqa: E402


def readings(workload, seed, bench=None, require_tpu=True, which=None):
    bench = bench or manifest.Manifest()
    cell = bench.cell(workload)
    cfg = bench.config(cell["config"])
    model_mod, ref_mod = manifest.family(cfg["family"])
    take_devices(cell["chips"], require_tpu)
    batches = _common.stack_steps(model_mod.make_batch, cfg, cell, seed,
                                  0, cell["k"])

    def follow(**kw):
        return reference_readings(ref_mod, model_mod, cfg, cell, seed,
                                  batches, **kw)

    reference = follow()
    stand_ins = {"control": dict(precision="float8"),
                 "half_batch": dict(rows=slice(0, cell["batch"] // 2)),
                 "bfloat16": dict(precision="bfloat16")}
    if cell["chips"] > 1:
        stand_ins["one_chip"] = dict(
            rows=slice(0, cell["batch"] // cell["chips"]))
    out = {}
    for name, kw in stand_ins.items():
        if which and name not in which:
            continue
        compared = check.compare(follow(**kw), reference)
        out[name] = {n: compared[n]["value"] for n in check.NUMBERS}
        out[name]["at"] = {n: compared[n]["at"] for n in check.NUMBERS}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first", type=int, default=900)
    args = ap.parse_args(argv)
    for seed in range(args.first, args.first + args.seeds):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(args.workload, seed)}), flush=True)


if __name__ == "__main__":
    main()
