#!/usr/bin/env python
"""Program verifier + TPU lint CLI — the repo's static-analysis gate.

Reference analog: the C++-side graph checks that keep fluid's ~80 IR
passes and `framework/prune.cc` honest, surfaced as a CI-runnable tool
over the collapsed trace->XLA pipeline.

    python tools/lint_program.py               # --ladder, --source and
                                               # --concurrency (the default
                                               # sweep)
    python tools/lint_program.py --ladder      # verify the analyzers'
                                               # ladder of tiny programs
    python tools/lint_program.py --source      # AST lint (nondeterminism in
                                               # traced fns, eager jnp in
                                               # dispatch hot paths)
    python tools/lint_program.py --source paddle_tpu/core/dispatch.py ...
    python tools/lint_program.py --concurrency # lock-order cycles, blocking
                                               # calls under a lock, cv-wait
                                               # discipline over the thread-
                                               # heavy runtime modules

Exit codes: 0 clean, 1 any error-severity finding (warnings print but do
not fail the gate; --strict promotes them). Wired into the verify-skill
recipe.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="static analysis over paddle_tpu programs and sources")
    ap.add_argument("--ladder", action="store_true",
                    help="verify the benchmark ladder's program miniatures")
    ap.add_argument("--source", nargs="*", metavar="PATH",
                    help="AST-lint sources (no PATH = the registered "
                    "hot-path files)")
    ap.add_argument("--concurrency", nargs="*", metavar="PATH",
                    help="static concurrency analysis (no PATH = the "
                    "thread-heavy runtime modules under "
                    "distributed/serving/observability/testing)")
    ap.add_argument("--configs", default=None,
                    help="comma list of ladder configs (default: all)")
    ap.add_argument("--strict", action="store_true",
                    help="warnings also fail the gate")
    args = ap.parse_args(argv)

    # no flags = the full default sweep; any flag alone selects its part
    none_selected = (not args.ladder and args.source is None
                     and args.concurrency is None)
    run_ladder = args.ladder or none_selected
    run_source = args.source is not None or none_selected
    run_concurrency = args.concurrency is not None or none_selected

    findings = []
    if run_ladder:
        # the miniatures are smoke-scale: always verify on CPU, whatever
        # backend the caller's environment selects (the update must come
        # before first jax use)
        import jax
        jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.analysis import ERROR, Finding, ladder
        from paddle_tpu.analysis.shardcheck import format_shard_stats
        from paddle_tpu.observability import memory as mem
        configs = args.configs.split(",") if args.configs else None
        # build the twins once, verify without the built-in attribution
        # pass, then attribute here — the stats feed both the gate (an
        # unattributable twin refuses the ladder, like a verify failure)
        # and the per-config hbm_peak column, without building or
        # compiling twins twice
        programs = ladder.build_ladder_programs(configs)
        fs, summary = ladder.verify_ladder(memory=False,
                                           programs=programs)
        findings.extend(fs)
        attribution = ladder.attribute_memory(programs=programs)
        for name, rows in sorted(attribution.items()):
            for pi, s in enumerate(rows):
                if "error" in s:
                    findings.append(Finding(
                        "memory-attribution-failed", ERROR,
                        f"[{name}] program {pi}: {s['error']}"))
        # record-level sharding summary: the stamped collective multiset
        # per twin, rendered as the shard= column (shardcheck's budget
        # findings already rode in through verify_ladder)
        shard_attr = ladder.attribute_sharding(programs=programs)
        # overlap attribution rides the same contract: a verified twin
        # whose schedule cannot be parsed/priced refuses the ladder
        overlap_attr = ladder.attribute_overlap(programs=programs)
        for name, rows in sorted(overlap_attr.items()):
            for pi, s in enumerate(rows):
                if "error" in s:
                    findings.append(Finding(
                        "overlap-attribution-failed", ERROR,
                        f"[{name}] program {pi}: {s['error']}"))
        for name, op_counts in sorted(summary.items()):
            peaks = [("err" if "error" in s
                      else f"{mem.mb(s['peak_bytes']):g}MB")
                     for s in attribution.get(name, [])]
            overlaps = [("err" if "error" in s
                         else "none" if not (s["sync_total"]
                                             + s["async_pairs_total"])
                         else f"{s['collective_overlap_efficiency']:.2f}")
                        for s in overlap_attr.get(name, [])]
            # record-level schedulable score (emission-order slack the
            # stamped collective sequence leaves hideable) — nonzero
            # only for twins that carry collectives; the pipelined
            # zero3_prefetch twin is the one that should read 1.00
            scheds = [f"{s.get('sequence_schedulable', 0.0):.2f}"
                      for s in overlap_attr.get(name, [])]
            shards = [format_shard_stats(s)
                      for s in shard_attr.get(name, [])]
            print(f"ladder[{name}]: {len(op_counts)} program(s), "
                  f"ops={op_counts}, hbm_peak={peaks}, "
                  f"overlap={overlaps}, sched={scheds}, "
                  f"shard={shards}")
    if run_source:
        from paddle_tpu.analysis import lint_source
        findings.extend(lint_source(paths=args.source or None))
    if run_concurrency:
        from paddle_tpu.analysis import check_concurrency
        findings.extend(check_concurrency(paths=args.concurrency or None))

    n_err = sum(f.severity == "error" for f in findings)
    n_warn = sum(f.severity == "warning" for f in findings)
    for f in findings:
        print(f)
    print(f"lint_program: {n_err} error(s), {n_warn} warning(s), "
          f"{len(findings) - n_err - n_warn} info")
    if n_err or (args.strict and n_warn):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
