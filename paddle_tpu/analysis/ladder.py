"""The analyzers' set of verified tiny programs (the "ladder").

One static-graph miniature per workload class (conv+BN for resnet,
embedding+attention-ish matmuls for gpt/bert, ragged-ish head for
detection, table lookup for hbm_cache, per-rank collective sequences for
allreduce) at smoke scale, recorded as a Program and pushed through the
full analyzer (graph verifier, dtype/shape checker, donation checker,
program lint, collective-order checker). ``tools/lint_program.py
--ladder`` verifies them; ``tools/mem_view.py --ladder`` and
``tools/overlap_view.py --ladder`` attribute them.
"""

__all__ = ["LADDER_BUILDERS", "build_ladder_programs", "verify_ladder",
           "attribute_memory", "attribute_overlap", "attribute_sharding"]


def _resnet_like():
    """conv + batch_norm(train) + relu + pool + fc + ce — exercises the
    _buffer_updates path the executor write-backs ride."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("image", [2, 3, 8, 8], "float32")
        y = static.data("label", [2], "int64")
        conv = nn.Conv2D(3, 4, 3, padding=1)
        bn = nn.BatchNorm2D(4)
        h = nn.functional.relu(bn(conv(x)))
        h = nn.functional.adaptive_avg_pool2d(h, 1)
        h = paddle.reshape(h, [2, 4])
        w = static.create_parameter([4, 10], "float32")
        logits = paddle.matmul(h, w)
        loss = nn.functional.cross_entropy(logits, y)
    return [(prog, [loss])]


def _gpt_like():
    """embedding + qk matmul + softmax + v matmul + lm head — the
    attention core of the gpt/bert ladder rows."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("ids", [2, 6], "int64")
        emb = nn.Embedding(32, 8)
        h = emb(ids)
        wq = static.create_parameter([8, 8], "float32")
        wk = static.create_parameter([8, 8], "float32")
        q = paddle.matmul(h, wq)
        k = paddle.matmul(h, wk)
        att = nn.functional.softmax(
            paddle.matmul(q, paddle.transpose(k, [0, 2, 1])))
        ctx = paddle.matmul(att, h)
        logits = paddle.matmul(ctx, paddle.transpose(emb.weight, [1, 0]))
        loss = nn.functional.cross_entropy(
            paddle.reshape(logits, [-1, 32]), paddle.reshape(ids, [-1]))
    return [(prog, [loss])]


def _bert_like():
    """gpt core + layer_norm + dropout, then the delete_dropout pass —
    the pass output must verify as clean as its input."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    prog.random_seed = 0  # dropout: keep the replay reproducible
    with static.program_guard(prog):
        ids = static.data("ids", [2, 4], "int64")
        emb = nn.Embedding(16, 8)
        h = emb(ids)
        h = nn.functional.dropout(h, p=0.1, training=True)
        h = nn.functional.layer_norm(h, [8])
        w = static.create_parameter([8, 16], "float32")
        logits = paddle.matmul(h, w)
        loss = nn.functional.cross_entropy(
            paddle.reshape(logits, [-1, 16]), paddle.reshape(ids, [-1]))
    rewritten = static.apply_pass(prog, "delete_dropout_op_pass")
    return [(prog, [loss]), (rewritten, [loss])]


def _detection_like():
    """conv head over a dynamic batch dim — the variable-shape bucket
    path; the program must stay polymorphic in the batch."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    with static.program_guard(prog):
        img = static.data("img", [-1, 3, 8, 8], "float32")
        conv = nn.Conv2D(3, 6, 3, padding=1)
        pred = nn.functional.sigmoid(conv(img))
        loss = paddle.mean(pred)
    return [(prog, [loss])]


def _hbm_cache_like():
    """embedding-table lookup + reduce — the CTR lookup workload."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("slot_ids", [4, 3], "int64")
        table = nn.Embedding(64, 8)
        rows = table(ids)
        loss = paddle.sum(rows)
    return [(prog, [loss])]


def _allreduce_ranks():
    """Two per-rank programs with the SAME recorded collective sequence —
    what the transpiled/hand-built multi-device path must look like for
    the order checker to accept it."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core.dispatch import call_op

    pairs = []
    for _rank in range(2):
        prog = static.Program()
        with static.program_guard(prog):
            g = static.data("grad", [4], "float32")
            # identity stand-ins for the in-shard_map lowerings, stamped
            # the way distributed.collective stamps the real ones
            def _ar(v):
                return v
            _ar._collective_axis = "dp"
            summed = call_op(_ar, g, op_name="c_allreduce")

            def _bc(v):
                return v
            _bc._collective_axis = "dp"
            out = call_op(_bc, summed, op_name="c_broadcast")
            loss = paddle.sum(out)
        pairs.append((prog, [loss]))
    return pairs


def _zero1_ranks():
    """Two per-rank programs with the ZeRO-1 collective schedule —
    bucketed grad reduce-scatter (two comm buckets) followed by the
    refreshed-param all-gather, payload-stamped the way
    distributed.collective stamps the real lowerings. The order checker
    must accept matching ranks (and tests seed the divergent-bucket
    variant it must reject)."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core.dispatch import call_op

    pairs = []
    for _rank in range(2):
        prog = static.Program()
        with static.program_guard(prog):
            g0 = static.data("grad_bucket0", [8, 16], "float32")
            g1 = static.data("grad_bucket1", [4, 16], "float32")

            def _rs(v, _nbytes):
                def fn(x):
                    return x
                fn._collective_axis = "dp"
                fn._collective_nbytes = _nbytes
                return call_op(fn, v, op_name="c_reducescatter")

            s0 = _rs(g0, 8 * 16 * 4)
            s1 = _rs(g1, 4 * 16 * 4)

            def _ag(x):
                return x
            _ag._collective_axis = "dp"
            _ag._collective_nbytes = (8 + 4) * 16 * 4
            out = call_op(_ag, s0, op_name="c_allgather")
            loss = paddle.sum(out) + paddle.sum(s1)
        pairs.append((prog, [loss]))
    return pairs


def _zero3_ranks():
    """Two per-rank programs with the ZeRO-3 + gradient-accumulation
    collective schedule: the per-bucket param all-gather fires every
    micro step (cadence 1, ag -> forward), while the bucketed gradient
    reduce-scatter is window-gated (cadence 4: one reduction per 4-step
    accumulation window, the ``to_static(accumulate_steps=4)`` shape)
    and the update writes only shard rows — no trailing param
    all-gather. The cadence stamps are what keep the order checker from
    reading the window-gated reduction as rank divergence; tests seed
    the per-step-vs-per-window mismatch it must reject."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core.dispatch import call_op

    def _stamped(op_name, nbytes, every):
        def fn(*vs):
            return vs[0]
        fn._collective_axis = "dp"
        fn._collective_nbytes = nbytes
        fn._collective_every = every
        return lambda *vs: call_op(fn, *vs, op_name=op_name)

    pairs = []
    for _rank in range(2):
        prog = static.Program()
        with static.program_guard(prog):
            pshard = static.data("param_shard_b0", [2, 16], "float32")
            grads = static.data("grad_b0", [8, 16], "float32")
            # ag -> fwd: params materialize just-in-time from the shard
            full = _stamped("c_allgather", 8 * 16 * 4, 1)(pshard)
            h = paddle.matmul(full, paddle.transpose(full, [1, 0]))
            # rs fires once per 4-step accumulation window
            gshard = _stamped("c_reducescatter", 8 * 16 * 4, 4)(grads)
            # shard-local update: only the local rows are written back
            loss = paddle.sum(h) + paddle.sum(
                paddle.add(pshard, paddle.scale(gshard[:2], -0.01)))
        pairs.append((prog, [loss]))
    return pairs


def _zero3_prefetch_ranks():
    """Two per-rank programs with the latency-hiding ZeRO-3 schedule —
    the double-buffered prefetch pipeline's recorded twin. Bucket 0's
    params arrive warm in the carry slot (no leading gather — the
    previous step's tail re-gather filled it), bucket 1's all-gather is
    emitted BEFORE bucket 0's compute consumes the slot, each bucket's
    grad reduce-scatter drains under downstream compute, and the tail
    re-gather of the updated bucket-0 shard warms the next step. The
    reorder is deterministic and identical across ranks, so the order
    checker accepts it (tests seed the serial-vs-pipelined mixed-rank
    skew it must still reject), and ``collectives
    .sequence_overlap_score`` reads every stamped payload as
    schedulable — the record-level counterpart of the traced step's
    ``schedulable_stats`` score."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.core.dispatch import call_op

    def _stamped(op_name, nbytes):
        def fn(*vs):
            return vs[0]
        fn._collective_axis = "dp"
        fn._collective_nbytes = nbytes
        fn._collective_every = 1
        return lambda *vs: call_op(fn, *vs, op_name=op_name)

    pairs = []
    for _rank in range(2):
        prog = static.Program()
        with static.program_guard(prog):
            slot0 = static.data("prefetch_slot_b0", [8, 16], "float32")
            pshard1 = static.data("param_shard_b1", [2, 16], "float32")
            g0 = static.data("grad_b0", [8, 16], "float32")
            g1 = static.data("grad_b1", [8, 16], "float32")
            # prefetch: bucket 1 gathers while bucket 0 computes
            full1 = _stamped("c_allgather", 8 * 16 * 4)(pshard1)
            h0 = paddle.matmul(slot0, paddle.transpose(slot0, [1, 0]))
            # deferred rs: bucket 0's reduction drains under bucket 1
            gs0 = _stamped("c_reducescatter", 8 * 16 * 4)(g0)
            h1 = paddle.matmul(full1, paddle.transpose(full1, [1, 0]))
            gs1 = _stamped("c_reducescatter", 8 * 16 * 4)(g1)
            upd0 = paddle.add(slot0[:2], paddle.scale(gs0[:2], -0.01))
            upd1 = paddle.add(pshard1, paddle.scale(gs1[:2], -0.01))
            # tail re-gather: warm the next step's bucket-0 slot
            nxt = _stamped("c_allgather", 8 * 16 * 4)(upd0)
            loss = paddle.sum(h0) + paddle.sum(h1) + paddle.sum(nxt) \
                + paddle.sum(upd1)
        pairs.append((prog, [loss]))
    return pairs


def _remat_like():
    """Activation-recompute structures, both representations:

    1. the POLICY SURFACE program — a Linear/ReLU/Linear block run
       through ``paddle_tpu.recompute`` under ``program_guard``, which
       records ONE fused ``recompute`` op (the control-flow fused-op
       discipline: capture probes never leak into the Program);
    2. the EXPANDED rewrite — the segment's forward ops re-recorded in
       the backward region writing the SAME slots, stamped with
       ``recompute.remat_replay`` and feeding a grad consumer, the
       reference recompute_optimizer's backward-block replay shape. The
       graph verifier must read the stamped re-writes as
       rematerialization, not ``duplicate-slot-write`` (tests seed the
       unstamped variant it must still reject)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, recompute as rc, static
    from paddle_tpu.static.program import _OpRecord

    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [4, 8], "float32")
        y = static.data("label", [4], "int64")
        blk = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 8))
        h = rc.recompute(blk, x, policy="selective")
        w = static.create_parameter([8, 16], "float32")
        logits = paddle.matmul(h, w)
        loss = nn.functional.cross_entropy(logits, y)
    pairs = [(prog, [loss])]

    prog2 = static.Program()
    with static.program_guard(prog2):
        x2 = static.data("x", [4, 8], "float32")
        w1 = static.create_parameter([8, 16], "float32")
        w2 = static.create_parameter([16, 8], "float32")
        h1 = paddle.matmul(x2, w1)
        a1 = nn.functional.relu(h1)
        h2 = paddle.matmul(a1, w2)
        loss2 = paddle.mean(h2)
    seg = list(prog2.ops[:3])  # the forward segment to rematerialize
    for op in seg:
        replay = rc.remat_replay(
            lambda *a, _fn=op.fn, **k: _fn(*a, **k))
        prog2.ops.append(_OpRecord(replay, op.arg_slots, op.kwarg_slots,
                                   op.out_slots, op.name))
    with static.program_guard(prog2):
        # the backward-region consumer of the replayed activations
        gw2 = paddle.matmul(paddle.transpose(a1, [1, 0]), h2)
    pairs.append((prog2, [loss2, gw2]))
    return pairs


def _ctr_like():
    """wide & deep CTR core — slot-id embedding gathers (the cached
    scan-window lookup is a gather from a device table; the Embedding
    op is its program-level twin) + wide per-key scalar sum + MLP head
    through a bce-with-logits loss, the workload class of the ctr bench
    rows."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static

    prog = static.Program()
    with static.program_guard(prog):
        ids = static.data("slot_ids", [4, 4], "int64")
        label = static.data("label", [4, 1], "float32")
        deep = nn.Embedding(64, 8)
        wide = nn.Embedding(64, 1)
        e = deep(ids)                          # [4, 4, 8]
        w = wide(ids)                          # [4, 4, 1]
        h = paddle.reshape(e, [4, 32])
        w1 = static.create_parameter([32, 16], "float32")
        w2 = static.create_parameter([16, 1], "float32")
        h = nn.functional.relu(paddle.matmul(h, w1))
        logit = paddle.add(paddle.matmul(h, w2), paddle.sum(w, axis=1))
        loss = nn.functional.binary_cross_entropy_with_logits(logit, label)
    return [(prog, [loss])]


def _serving_like():
    """The serving engine's load-time pipeline over a dynamic-batch
    forward program: eval clone → prune-to-fetch → bf16 weight/compute
    cast (explicit leading ``cast`` ops, bf16 params). The optimized
    program must verify as clean as its input — the engine refuses to
    come up otherwise, so a dirty twin here means the serving pass
    pipeline itself regressed."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, static
    from paddle_tpu.serving.passes import build_serving_program

    prog = static.Program()
    prog.random_seed = 0  # dropout records an RNG op: keep replays pinned
    with static.program_guard(prog):
        x = static.data("feat", [-1, 8], "float32")
        w1 = static.create_parameter([8, 16], "float32")
        w2 = static.create_parameter([16, 4], "float32")
        h = nn.functional.relu(paddle.matmul(x, w1))
        h = nn.functional.dropout(h, p=0.1, training=True)
        logits = paddle.matmul(h, w2)
        aux = paddle.mean(logits)  # unfetched: prune must slice it away
    optimized = build_serving_program(prog, [logits], passes=("bf16",))
    return [(prog, [logits, aux]), (optimized, [logits])]


LADDER_BUILDERS = {
    "resnet": _resnet_like,
    "gpt": _gpt_like,
    "bert": _bert_like,
    "detection": _detection_like,
    "hbm_cache": _hbm_cache_like,
    "ctr": _ctr_like,
    "remat": _remat_like,
    "serving": _serving_like,
    "allreduce": _allreduce_ranks,
    "zero1": _zero1_ranks,
    "zero3": _zero3_ranks,
    "zero3_prefetch": _zero3_prefetch_ranks,
}


def build_ladder_programs(configs=None):
    """name -> [(program, targets), ...]. Multi-entry lists are per-rank
    (allreduce) or pass-rewritten variants (bert)."""
    names = configs or sorted(LADDER_BUILDERS)
    return {n: LADDER_BUILDERS[n]() for n in names}


def verify_ladder(configs=None, mesh_axes=("dp",), memory=True,
                  programs=None):
    """Run the full analyzer over every ladder program — including
    XLA memory attribution of each twin (``observability.memory
    .attribute_program``): a twin whose executable yields no byte
    accounting refuses the ladder exactly like a verify failure.
    ``programs`` takes pre-built ``{name: pairs}`` (from
    :func:`build_ladder_programs`) so a caller running both this and
    :func:`attribute_memory` builds the twins once. Returns
    ``(findings, summary)`` where summary maps config -> op counts per
    program. Clean = no findings at all."""
    from . import lint, verify
    from .collectives import check_collective_order
    from .dtype_check import check_dtypes
    from .findings import ERROR, Finding
    from .shardcheck import check_program_sharding
    from ..observability.memory import (MemoryAttributionError,
                                        attribute_program)

    findings = []
    summary = {}

    def _tag(config, fs):
        for f in fs:
            f.message = f"[{config}] {f.message}"
            findings.append(f)

    if programs is None:
        programs = build_ladder_programs(configs)
    for name, pairs in programs.items():
        summary[name] = [len(p.ops) for p, _t in pairs]
        for pi, (prog, targets) in enumerate(pairs):
            _tag(name, verify(prog, targets=targets, mesh_axes=mesh_axes))
            _tag(name, check_dtypes(prog))
            _tag(name, lint(prog))
            _tag(name, check_program_sharding(prog, mesh_axes=mesh_axes))
            if memory:
                try:
                    attribute_program(prog, targets)
                except MemoryAttributionError as e:
                    _tag(name, [Finding(
                        "memory-attribution-failed", ERROR,
                        f"program {pi}: {e}")])
        if name in ("allreduce", "zero1", "zero3", "zero3_prefetch"):
            _tag(name, check_collective_order([p for p, _t in pairs],
                                              mesh_axes=mesh_axes))
    return findings, summary


def attribute_memory(configs=None, programs=None):
    """Memory attribution of every ladder twin: ``{config: [stats per
    program]}`` (``tools/mem_view.py --ladder`` renders this; a failed
    attribution surfaces as a stats dict with an ``"error"`` key so the
    table still names the broken twin). ``programs`` takes pre-built
    ``{name: pairs}`` to skip the rebuild."""
    from ..observability.memory import MemoryAttributionError, \
        attribute_program

    out = {}
    if programs is None:
        programs = build_ladder_programs(configs)
    for name, pairs in programs.items():
        rows = []
        for prog, targets in pairs:
            try:
                rows.append(attribute_program(prog, targets))
            except MemoryAttributionError as e:
                rows.append({"error": str(e)[:300]})
        out[name] = rows
    return out


def attribute_sharding(configs=None, programs=None, mesh_axes=("dp",)):
    """Stamped-collective sharding summary of every ladder twin
    (``analysis.shardcheck.program_shard_stats``): ``{config: [stats
    per program]}`` — the source of ``lint_program --ladder``'s
    ``shard=`` column. Record-level and cheap (no compile): each row is
    the per-axis multiset of the twin's stamped collectives, so a twin
    whose schedule silently drops its republishing all-gather is visible
    in the table as well as in :func:`verify_ladder`'s
    ``collective-budget-mismatch`` finding."""
    from .shardcheck import program_shard_stats

    out = {}
    if programs is None:
        programs = build_ladder_programs(configs)
    for name, pairs in programs.items():
        out[name] = [program_shard_stats(prog, mesh_axes=mesh_axes)
                     for prog, _targets in pairs]
    return out


def attribute_overlap(configs=None, programs=None):
    """Collective-overlap attribution of every ladder twin
    (``observability.overlap`` over the twin's AOT-compiled schedule):
    ``{config: [stats per program]}``, failures as ``{"error": ...}``
    rows — the same contract as :func:`attribute_memory`, rendered by
    ``tools/overlap_view.py --ladder`` and gated by ``lint_program
    --ladder``. The twins' stand-in collectives are identity ops, so
    their compiled HLO honestly reports zero collective time on the
    smoke mesh; what this pass certifies is that every verified twin's
    schedule *parses and prices* without error. Every row additionally
    carries ``"sequence_schedulable"`` — the record-level
    schedulable-overlap score (``analysis.collectives
    .sequence_overlap_score``) computed from the stamped collective
    sequence itself, which DOES discriminate on the smoke mesh: the
    serial zero3 twin's consumer-adjacent gather scores below the
    prefetch-pipelined twin's 1.0."""
    from .collectives import sequence_overlap_score
    from ..observability.memory import MemoryAttributionError
    from ..observability.overlap import attribute_program as _overlap

    out = {}
    if programs is None:
        programs = build_ladder_programs(configs)
    for name, pairs in programs.items():
        rows = []
        for prog, targets in pairs:
            try:
                row = _overlap(prog, targets)
            except MemoryAttributionError as e:
                row = {"error": str(e)[:300]}
            row["sequence_schedulable"] = \
                sequence_overlap_score(prog)["schedulable_overlap"]
            rows.append(row)
        out[name] = rows
    return out
