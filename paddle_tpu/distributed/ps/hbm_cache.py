"""HBM-resident embedding cache + pass-level trainer — the GPUPS analog
(reference: `framework/fleet/ps_gpu_wrapper.cc:43/533` BuildTask /
BuildGPUPSTask, `framework/fleet/heter_ps/hashtable.h` device hash
tables, `framework/trainer.h:250` PSGPUTrainer).

The reference's CTR perf story: before each dataset pass, every feasign
key in the pass is deduped and bulk-pulled from the parameter servers
into GPU-resident hash tables; trainer threads then read/update
embeddings at HBM speed, and EndPass writes the trained values back.

TPU-first redesign, not a translation:
  - the device "hash table" is a dense ``(capacity, dim)`` jax array in
    HBM, optionally row-sharded over a mesh axis (the multi-GPU
    ``heter_comm.h`` inter-card exchange becomes XLA collectives);
  - key->slot lookup is a host-side LRU dict (key hashing is host work
    in the reference too, and keeping it off-device leaves every device
    program static-shaped for XLA);
  - lookup / optimizer-update / write-back are jit'd gather/scatter
    programs with power-of-two bucket padding so the compile count stays
    bounded; row 0 is a scratch slot that absorbs padded lanes;
  - rows faulted on a miss are pulled per batch (batched), cold rows are
    LRU-evicted with a delta write-back — so capacity smaller than the
    working set degrades gracefully instead of OOMing;
  - the optimizer (sgd/adam, matching ps_service.cc's server rules
    bit-for-bit) runs on-device, like the reference's optimizer.cuh.h.

Write-back pushes ``trained - staged`` deltas (kPushSparseDelta), so the
server composes concurrent workers' contributions the same way geo mode
does; with one worker the final server rows equal the device rows
exactly.

Cache observability rides the global monitor registry (monitor.py):
``hbm_cache_hit`` / ``hbm_cache_miss`` / ``hbm_cache_evict`` /
``hbm_cache_writeback_rows`` — the analog of the reference's pull/push
timer VLOGs.

Async pipeline (the heter_ps overlap story — see ``async_cache.py``):
``plan_window``/``drain_window`` + a registered table Tensor
(``enable_scan_feeds``) integrate the cache with
``to_static(..., scan_steps=k)`` — lookups inside the traced body are
static-shaped gathers from the carried HBM table by prebuilt
``(slots, inv)`` feeds, gradients scatter-add into the table's CARRIED
grad (the delta store) and drain once per window; a
:class:`~.async_cache.CachePrefetcher` plans the next window while the
device runs the current one, and a :class:`~.async_cache.WriteBackQueue`
moves eviction/end-pass delta pushes behind the next window's compute.
Eviction gains a telemetry-driven adaptive watermark (``free_target`` /
``evict_ahead``): expensive PS pulls → evict ahead of pressure so a
future fault never pays eviction + pull serially; cheap pulls → lazy.
"""
import functools
import time
from collections import OrderedDict

import numpy as np

from ... import _lockwatch as lockwatch
from ... import monitor
from ...core.dispatch import call_op, unwrap, wrap
from .embedding import SparseEmbedding

__all__ = ["HbmEmbeddingCache", "CachedSparseEmbedding", "PsTpuTrainer"]


def _bucket(n):
    b = 8
    while b < n:
        b <<= 1
    return b


@functools.lru_cache(maxsize=None)
def _jit_gather():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda tbl, s: jnp.take(tbl, s, axis=0))


@functools.lru_cache(maxsize=None)
def _jit_install():
    import jax

    def f(tbl, staged, slots, rows):
        return tbl.at[slots].set(rows), staged.at[slots].set(rows)

    return jax.jit(f, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _jit_copy():
    import jax
    return jax.jit(lambda x: x + 0.0)  # on-device copy, keeps sharding


@functools.lru_cache(maxsize=None)
def _jit_move():
    import jax
    import jax.numpy as jnp

    # every gather reads the PRE-op table, every scatter lands after —
    # one fused move can therefore relocate a row into a slot that is
    # another move's source in the same batch without ordering hazards
    def f(tbl, staged, src, dst):
        return (tbl.at[dst].set(jnp.take(tbl, src, 0)),
                staged.at[dst].set(jnp.take(staged, src, 0)))

    return jax.jit(f, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _jit_delta():
    import jax
    import jax.numpy as jnp
    return jax.jit(
        lambda tbl, staged, s: jnp.take(tbl, s, 0) - jnp.take(staged, s, 0))


@functools.lru_cache(maxsize=None)
def _jit_sgd():
    import jax

    def f(tbl, slots, grad, lr):
        return tbl.at[slots].add(-lr * grad)

    return jax.jit(f, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jit_adam():
    import jax
    import jax.numpy as jnp

    # mirrors ps_service.cc SparseTable::apply_grad kOptAdam exactly:
    # p -= lr * (m/bc1) / (sqrt(v/bc2) + eps), t per-row
    def f(tbl, m, v, t, slots, grad, lr, b1, b2, eps):
        t = t.at[slots].add(1.0)
        ts = t[slots][:, None]
        mn = b1 * m[slots] + (1.0 - b1) * grad
        vn = b2 * v[slots] + (1.0 - b2) * grad * grad
        m = m.at[slots].set(mn)
        v = v.at[slots].set(vn)
        bc1 = 1.0 - b1 ** ts
        bc2 = 1.0 - b2 ** ts
        tbl = tbl.at[slots].add(-lr * (mn / bc1) /
                                (jnp.sqrt(vn / bc2) + eps))
        return tbl, m, v, t

    return jax.jit(f, donate_argnums=(0, 1, 2, 3))


class HbmEmbeddingCache:
    """Device-resident cache over one PS sparse table.

    ``capacity`` counts device rows; row 0 is reserved as the padding
    scratch slot, so ``capacity - 1`` keys can be resident. Keep
    ``capacity`` divisible by the mesh-axis size when sharding.
    """

    def __init__(self, client, table_id, dim, capacity, optimizer="sgd",
                 lr=0.01, beta1=0.9, beta2=0.999, eps=1e-8, mesh=None,
                 mesh_axis=None, writeback=None, watermark=(0.0, 0.15),
                 pull_chunk=1 << 16):
        import jax.numpy as jnp

        if capacity < 2:
            raise ValueError("capacity must be >= 2 (row 0 is scratch)")
        self.client = client
        self.table_id = table_id
        self.dim = dim
        self.capacity = capacity
        self.optimizer = optimizer
        self.lr = float(lr)
        self.beta1, self.beta2, self.eps = float(beta1), float(beta2), \
            float(eps)
        self._sharding = None
        self._sharding_1d = None
        if mesh is not None and mesh_axis is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            if capacity % mesh.shape[mesh_axis]:
                raise ValueError(
                    f"capacity {capacity} must divide the mesh axis "
                    f"{mesh_axis!r} ({mesh.shape[mesh_axis]} devices)")
            self._sharding = NamedSharding(mesh, P(mesh_axis, None))
            self._sharding_1d = NamedSharding(mesh, P(mesh_axis))
        self._table_t = None          # set by enable_scan_feeds()
        self._table = self._place(jnp.zeros((capacity, dim), jnp.float32))
        self.staged = self._place(jnp.zeros((capacity, dim), jnp.float32))
        if optimizer == "adam":
            self.m = self._place(jnp.zeros((capacity, dim), jnp.float32))
            self.v = self._place(jnp.zeros((capacity, dim), jnp.float32))
            self.t = self._place(jnp.zeros((capacity,), jnp.float32),
                                 one_d=True)
        elif optimizer != "sgd":
            raise ValueError(f"unsupported cache optimizer {optimizer!r}")
        self._fused_progs = {}        # (fn, shapes) -> compiled pass
        self._slots = OrderedDict()   # key -> slot, LRU order (front=cold)
        self._free = list(range(capacity - 1, 0, -1))  # never slot 0
        self._key_of = np.zeros(capacity, np.uint64)
        self._dirty = np.zeros(capacity, bool)
        self._pending = []            # (slots, slice_tensor) per lookup
        # async pipeline state: one re-entrant lock serializes the host
        # index structures between the foreground step and the
        # prefetch/write-back threads (device ops stay inside it —
        # correctness over parallel dispatch on the host index)
        self._mu = lockwatch.RLock(name="hbm_cache.mu")
        self.writeback = writeback    # optional WriteBackQueue
        self._plan_pins = {}          # key -> count of unconsumed plans
        # deferred device work from the prefetch stage: the planner
        # thread must NEVER touch device arrays (a to_static build may
        # have swapped the table Tensor's value for a tracer on the main
        # thread) — pulled rows stage host-side here and install on the
        # consumer thread (_flush_installs), one scatter per flush
        self._pending_install = []        # [(slots int32, rows f32)]
        self._pending_install_slots = set()
        self._pending_evict = []          # [(dirty victim slots, keys)]
        self._pending_copy = []           # [(src slots, dst slots)] —
        # resurrections: a deferred-evicted key re-planned before the
        # flush moves its still-intact rows instead of re-pulling stale
        # adaptive-watermark inputs: client-side per-pull latency EMA
        # (fallback when no in-process server exports ps_server_op_ns)
        # and decayed hit/miss pressure counters
        self.watermark_min_frac, self.watermark_max_frac = watermark
        self.pull_chunk = int(pull_chunk)
        self._pull_ms_ema = None
        self._hit_ema = 0.0
        self._miss_ema = 0.0

    # The device table lives either as a plain jax array or — after
    # enable_scan_feeds() — as the `_value` of a registered framework
    # Tensor riding to_static programs. One property keeps every
    # internal jit program and external test reading `cache.table`.
    @property
    def table(self):
        return self._table_t._value if self._table_t is not None \
            else self._table

    @table.setter
    def table(self, v):
        if self._table_t is not None:
            self._table_t._value = v
        else:
            self._table = v

    def _place(self, arr, one_d=False):
        if self._sharding is None:
            return arr
        import jax
        return jax.device_put(arr,
                              self._sharding_1d if one_d else self._sharding)

    # -- vectorized residency (shared by pass staging, the fused pass,
    # and window planning; no per-key dict walk — these run under _mu,
    # which lookup()/feeds() contend on) ----------------------------------
    @staticmethod
    def _member(sorted_keys, keys):
        """Membership of ``keys`` in sorted ``sorted_keys`` with the
        searchsorted insertion points clamped to the last valid index
        before comparing (an insertion point of ``size`` means "past
        the end", never a hit). Returns ``(mask, pos)``; where mask
        holds, ``sorted_keys[pos] == keys``."""
        pos = np.searchsorted(sorted_keys, keys)
        if not sorted_keys.size:
            return np.zeros(keys.size, bool), pos
        mask = (pos < sorted_keys.size) & (
            sorted_keys[np.minimum(pos, sorted_keys.size - 1)] == keys)
        return mask, pos

    def _resident_mask(self, keys):
        res = np.sort(np.fromiter(self._slots.keys(), np.uint64,
                                  len(self._slots)))
        return self._member(res, keys)[0]

    def _resident_index(self):
        """Aligned ``(keys, slots)`` snapshot of the resident index,
        sorted by key, for resolving many batches against one sort."""
        n = len(self._slots)
        keys = np.fromiter(self._slots.keys(), np.uint64, n)
        slots = np.fromiter(self._slots.values(), np.int32, n)
        order = np.argsort(keys)
        return keys[order], slots[order]

    # -- pass staging (BuildGPUPSTask analog) -----------------------------
    def build_pass(self, keys):
        """Dedup `keys` (every feasign in the upcoming pass), bulk-pull
        the non-resident ones from the PS, and stage them into HBM. If
        the pass working set exceeds capacity, the most frequent keys are
        staged and the tail is left to per-batch faulting."""
        keys = np.asarray(keys, np.uint64).ravel()
        uniq, counts = np.unique(keys, return_counts=True)
        order = np.argsort(-counts, kind="stable")
        uniq = uniq[order]
        with self._mu:
            self._flush_installs()
            return self._build_pass_locked(uniq)

    def _build_pass_locked(self, uniq):
        resident = self._resident_mask(uniq)
        missing = uniq[~resident]
        # LRU-refresh already-resident keys of this pass (coldest
        # first, so the hottest end up most recently used): without
        # this, mid-pass faulting under capacity pressure could evict
        # a hot resident key before the cold staged tail
        for key in uniq[resident][::-1]:
            self._slots.move_to_end(int(key))
        room = len(self._free)
        if missing.size > room:
            missing = missing[:room]
        # install least-frequent-FIRST so the hottest keys end up most
        # recently used — under capacity pressure, mid-pass faulting then
        # evicts the cold tail, not the keys staging exists to protect
        missing = missing[::-1].copy()
        if missing.size:
            self._fault_in(missing, count_miss=False)
        monitor.stat_add("hbm_cache_staged", int(missing.size))
        return int(missing.size)

    # -- lookup (differentiable; PullSparse analog) -----------------------
    def lookup(self, ids):
        """Differentiable embedding lookup served from HBM. Returns a
        Tensor shaped ``ids.shape + (dim,)``; the pulled slice is
        recorded so :meth:`apply_grads` can run the on-device optimizer
        after ``loss.backward()``.

        Every device shape here is padded to a power-of-two bucket: the
        per-batch unique-key count varies, and an unpadded slice would
        force an XLA recompile per distinct count. Padded lanes point
        at scratch row 0.
        """
        import jax.numpy as jnp

        ids_np = np.asarray(unwrap(ids)).astype(np.int64)
        shape = ids_np.shape
        uniq, inv = np.unique(ids_np.ravel(), return_inverse=True)
        with self._mu:
            self._flush_installs()  # prefetched rows become readable
            slots = self._ensure(uniq.astype(np.uint64))
            n = slots.size
            b = _bucket(n)
            slots_p = np.zeros(b, np.int32)  # padded lanes hit scratch row 0
            slots_p[:n] = slots
            rows_p = _jit_gather()(self.table,
                                   jnp.asarray(slots_p))  # (b,dim)
            slice_t = wrap(rows_p, stop_gradient=False)
            from ...core import autograd as _ag
            if _ag.grad_enabled():
                self._pending.append((slots, slots_p, slice_t))

        def _gather(rows_):
            return rows_[jnp.asarray(inv)].reshape(shape + (self.dim,))

        return call_op(_gather, slice_t, op_name="hbm_cache_lookup")

    # -- optimizer update (PushSparseGrad + optimizer.cuh.h analog) -------
    def apply_grads(self):
        """Apply every recorded slice gradient to the device table with
        the cache's optimizer rule. Call after ``loss.backward()``."""
        import jax.numpy as jnp

        with self._mu:
            self._flush_installs()
            self._apply_pending()

    def _apply_pending(self):
        import jax.numpy as jnp

        for slots, slots_p, slice_t in self._pending:
            if slice_t._grad is None:
                continue
            # the slice grad is already bucket-padded (lookup kept the
            # padded shape); padded rows are zero and target scratch
            sj = jnp.asarray(slots_p)
            gj = jnp.asarray(slice_t._grad, jnp.float32)
            if self.optimizer == "sgd":
                self.table = _jit_sgd()(self.table, sj, gj,
                                        jnp.float32(self.lr))
            else:
                self.table, self.m, self.v, self.t = _jit_adam()(
                    self.table, self.m, self.v, self.t, sj, gj,
                    jnp.float32(self.lr), jnp.float32(self.beta1),
                    jnp.float32(self.beta2), jnp.float32(self.eps))
            self._dirty[slots] = True
            self._dirty[0] = False  # scratch row never written back
        self._pending = []

    # -- fused pass (the GPUPS perf story, TPU-style) ---------------------
    def run_fused_pass(self, ids_batches, emb_loss_fn, labels=None):
        """Run a whole staged pass as ONE compiled device program.

        This is where the TPU design beats the reference's per-batch
        device round-trips: after :meth:`build_pass` stages every key,
        no host work remains mid-pass, so the full pass — gather →
        ``emb_loss_fn`` forward/backward → optimizer scatter — compiles
        into a single ``lax.scan`` over batches. One dispatch executes
        K batches; dispatch latency amortizes to ~0 per batch.

        ``ids_batches``: list of int id arrays, all the same shape.
        ``emb_loss_fn(emb[, label]) -> scalar`` must be pure jax AND a
        stable callable — the compiled pass is cached on its identity,
        so a fresh lambda per call recompiles per call.
        ``labels``: optional per-batch arrays (stacked and scanned).
        Every key must be resident (the pass contract); a miss raises.
        Returns the per-batch loss array.
        """
        import jax
        import jax.numpy as jnp

        self._flush_installs()
        shape = np.asarray(ids_batches[0]).shape
        # vectorized key->slot resolution: one sorted snapshot of the
        # resident index per pass, searchsorted per batch (the per-key
        # python dict walk would dominate the fused pass's host cost)
        if not self._slots:
            raise RuntimeError("fused pass requires every key staged "
                               "(build_pass first); cache is empty")
        res_keys, res_slots = self._resident_index()
        slots_l, inv_l = [], []
        for ids in ids_batches:
            ids_np = np.asarray(ids).astype(np.int64)
            if ids_np.shape != shape:
                raise ValueError("all fused-pass batches must share one "
                                 "shape (bucket static shapes for XLA)")
            uniq, inv = np.unique(ids_np.ravel(), return_inverse=True)
            uniq = uniq.astype(np.uint64)
            ok, pos = self._member(res_keys, uniq)
            if not ok.all():
                raise RuntimeError(
                    f"fused pass requires every key staged "
                    f"(build_pass first); key {int(uniq[~ok][0])} is not "
                    f"resident")
            slots_l.append(res_slots[pos])
            inv_l.append(inv.astype(np.int32))
        monitor.stat_add("hbm_cache_hit",
                         int(sum(s.size for s in slots_l)))
        b = _bucket(max(s.size for s in slots_l))
        K = len(ids_batches)
        slots_a = np.zeros((K, b), np.int32)
        inv_a = np.stack(inv_l)
        for i, s in enumerate(slots_l):
            slots_a[i, :s.size] = s
        lab_a = (np.stack([np.asarray(l, np.float32) for l in labels])
                 if labels is not None else np.zeros((K, 1), np.float32))
        opt_adam = self.optimizer == "adam"
        has_labels = labels is not None
        prog_key = (emb_loss_fn, shape, K, b, has_labels, lab_a.shape)
        run = self._fused_progs.get(prog_key)
        if run is None:
            lr, b1, b2, eps = (jnp.float32(self.lr),
                               jnp.float32(self.beta1),
                               jnp.float32(self.beta2),
                               jnp.float32(self.eps))
            dim = self.dim

            def body(carry, xs):
                slots_k, inv_k, lab_k = xs
                tbl = carry[0]
                rows = jnp.take(tbl, slots_k, axis=0)

                def g(rows_):
                    e = rows_[inv_k].reshape(shape + (dim,))
                    return (emb_loss_fn(e, lab_k) if has_labels
                            else emb_loss_fn(e))

                loss, dr = jax.value_and_grad(g)(rows)
                if opt_adam:
                    tbl, m, v, t = carry
                    t = t.at[slots_k].add(1.0)
                    ts = t[slots_k][:, None]
                    mn = b1 * m[slots_k] + (1.0 - b1) * dr
                    vn = b2 * v[slots_k] + (1.0 - b2) * dr * dr
                    m = m.at[slots_k].set(mn)
                    v = v.at[slots_k].set(vn)
                    tbl = tbl.at[slots_k].add(
                        -lr * (mn / (1.0 - b1 ** ts)) /
                        (jnp.sqrt(vn / (1.0 - b2 ** ts)) + eps))
                    return (tbl, m, v, t), loss
                tbl = tbl.at[slots_k].add(-lr * dr)
                return (tbl,), loss

            @functools.partial(jax.jit, donate_argnums=(0,))
            def run(carry, slots_a, inv_a, lab_a):
                return jax.lax.scan(body, carry, (slots_a, inv_a, lab_a))

            if len(self._fused_progs) >= 16:  # bound retained programs
                self._fused_progs.pop(next(iter(self._fused_progs)))
            self._fused_progs[prog_key] = run

        carry = ((self.table, self.m, self.v, self.t) if opt_adam
                 else (self.table,))
        carry, losses = run(carry, jnp.asarray(slots_a),
                            jnp.asarray(inv_a), jnp.asarray(lab_a))
        if opt_adam:
            self.table, self.m, self.v, self.t = carry
        else:
            (self.table,) = carry
        touched = np.unique(np.concatenate(slots_l))
        self._dirty[touched] = True
        self._dirty[0] = False
        return np.asarray(losses)

    # -- write-back (EndPass analog) --------------------------------------
    def end_pass(self, flush=True):
        """Push ``trained - staged`` deltas for every dirty resident row
        back to the PS and re-baseline. Rows stay resident for the next
        pass (warm cache across passes).

        With a :class:`~.async_cache.WriteBackQueue` attached the deltas
        enqueue to the background pusher; ``flush=True`` (default) then
        drains it so the EndPass contract — server rows equal device
        rows afterwards — still holds at return. ``flush=False`` lets
        the push overlap the next pass (flush once at the end of
        training)."""
        import jax.numpy as jnp

        with self._mu:
            self._flush_installs()
            dirty = np.nonzero(self._dirty)[0]
            if dirty.size:
                keys = self._key_of[dirty]
                delta = np.asarray(_jit_delta()(self.table, self.staged,
                                                jnp.asarray(dirty.astype(
                                                    np.int32))))
                self._push_delta(keys, delta)
                # re-baseline on device (a host round-trip would copy the
                # whole table to the host and un-shard it)
                self.staged = _jit_copy()(self.table)
                self._dirty[:] = False
            monitor.stat_add("hbm_cache_writeback_rows", int(dirty.size))
        if flush and self.writeback is not None:
            self.writeback.flush()
        return int(dirty.size)

    # -- scan-step integration (to_static(..., scan_steps=k)) -------------
    def enable_scan_feeds(self):
        """Expose the device table as REGISTERED framework state so
        lookups compile inside ``to_static`` scan bodies: the table
        Tensor rides the program like any parameter (read-only — the
        body never writes it), and the gather's gradient scatter-adds
        into its carried grad, which is the window's delta store
        (additive accumulation across the k inner steps is exactly the
        scan carry's grad semantics). Idempotent; returns the Tensor.
        Locked: the prefetcher thread (plan_window) and the consumer
        (scan_lookup during tracing) can both make the first call —
        racing unsynchronized, each would register its own Tensor and
        the loser's would soak up every later install."""
        with self._mu:
            if self._table_t is None:
                from ...core.tensor import Tensor
                t = Tensor(self._table, stop_gradient=False,
                           name=f"hbm_cache_table_{self.table_id}")
                t.persistable = True
                t._ledger_category = "hbm_cache"
                t._mark_stateful()
                self._table = None
                self._table_t = t
            return self._table_t

    def scan_lookup(self, slots, inv):
        """Differentiable lookup by prebuilt static-shaped feeds (from a
        :class:`~.async_cache.WindowPlan`): gathers the step's rows from
        the carried HBM table — pure jax, shape-stable, legal inside a
        ``to_static(..., scan_steps=k)`` body where the host-side
        key→slot work of :meth:`lookup` is impossible. The gradient
        scatter-adds into the table's carried grad; call
        :meth:`drain_window` after the compiled window returns."""
        import jax.numpy as jnp

        tt = self.enable_scan_feeds()
        slots_j = unwrap(slots)
        inv_j = unwrap(inv)
        dim = self.dim
        out_shape = tuple(np.shape(inv_j)) + (dim,)

        def _gather(tbl):
            rows = jnp.take(tbl, slots_j, axis=0)
            return jnp.take(rows, inv_j.reshape(-1),
                            axis=0).reshape(out_shape)

        return call_op(_gather, tt, op_name="hbm_cache_scan_lookup")

    def plan_window(self, ids, bucket=None):
        """Host half of a scan window's lookups: dedupe the ``[k, ...]``
        id block per inner step, fault every missing key in (batched,
        chunked, riding the client retry policy) and build the
        static-shaped ``(slots, inv)`` feeds. The window's keys are
        PINNED against eviction until the plan is consumed. Runs on the
        prefetcher thread in the async pipeline — i.e. while the device
        executes the previous window. Returns a
        :class:`~.async_cache.WindowPlan`.

        ``bucket`` pins the slot-feed width W (power of two >= the max
        per-step unique count) so every window of a run shares ONE
        compiled program; default: the smallest bucket for this window.

        Safe to run on a prefetcher thread concurrently with the
        consumer's compiled steps: the whole window's keys dedupe ONCE,
        slot allocation (evictions deferred) happens under the cache
        lock, but the PS pull — the long part — runs outside it and
        never touches device arrays; the pulled rows stage host-side
        until :meth:`_flush_installs` (via ``plan.feeds()`` or any
        table-reading entry point) scatters them in on the consumer
        thread.
        """
        from .async_cache import WindowPlan

        t0 = time.perf_counter()
        # the table must be registered framework state BEFORE the step
        # program builds: a Tensor registering mid-trace is invisible to
        # to_static's state snapshot and its gradient would leak a tracer
        self.enable_scan_feeds()
        ids_np = np.asarray(unwrap(ids)).astype(np.int64)
        if ids_np.ndim < 2:
            raise ValueError(
                f"plan_window expects [k, ...]-stacked ids; got shape "
                f"{ids_np.shape}")
        k = ids_np.shape[0]
        uniq_l, inv_l = [], []
        for i in range(k):
            u, inv = np.unique(ids_np[i].ravel(), return_inverse=True)
            uniq_l.append(u.astype(np.uint64))
            inv_l.append(inv.astype(np.int32))
        wmax = max(u.size for u in uniq_l)
        W = _bucket(wmax) if bucket is None else int(bucket)
        if W < wmax:
            raise ValueError(
                f"bucket {W} < max per-step unique count {wmax}")
        all_keys = np.unique(np.concatenate(uniq_l))
        window_pin = set(int(x) for x in all_keys)
        slots_a = np.zeros((k, W), np.int32)
        with self._mu:
            # window-level dedupe: classify every key once, allocate
            # slots for the misses (evictions deferred — no device
            # reads on this thread), THEN resolve the per-step feeds
            # from the now-complete index
            resident = self._resident_mask(all_keys)
            missing = all_keys[~resident].tolist()
            hits = sum(u.size for u in uniq_l) - len(missing)
            monitor.stat_add("hbm_cache_hit", hits)
            monitor.stat_add("hbm_cache_miss", len(missing))
            self._hit_ema = 0.98 * self._hit_ema + hits
            self._miss_ema = 0.98 * self._miss_ema + len(missing)
            # resurrection: a missed key whose deferred-evict delta has
            # NOT flushed yet still has its table+staged rows intact on
            # device — relocate them to a fresh slot instead of
            # re-pulling from the PS (the PS does not have the delta
            # yet; pulling would install a STALE value and violate
            # read-your-writes). The key stays dirty and its un-pushed
            # delta rides along: table-staged at the new slot is still
            # exactly the training the server has not seen.
            resurrect = {}
            if missing and self._pending_evict:
                pe = {}
                for ei, (_dv, ks) in enumerate(self._pending_evict):
                    for j, kk in enumerate(ks.tolist()):
                        pe[int(kk)] = (ei, j)
                still = []
                for kk in missing:
                    if int(kk) in pe:
                        resurrect[int(kk)] = pe[int(kk)]
                    else:
                        still.append(kk)
                missing = still
            miss_keys = np.asarray(missing, np.uint64)
            n_new = miss_keys.size + len(resurrect)
            if n_new:
                need = n_new - len(self._free)
                if need > 0:
                    self._evict(need, window_pin, defer=True)
                if n_new > len(self._free):
                    raise RuntimeError(
                        f"hbm cache over capacity: window needs "
                        f"{n_new} new slots, {len(self._free)} "
                        f"free after eviction (window working set larger "
                        f"than capacity {self.capacity}?)")
            if resurrect:
                drop = {}
                src_l, dst_l = [], []
                for kk, (ei, j) in resurrect.items():
                    dv, _ks = self._pending_evict[ei]
                    s_new = int(self._free.pop())
                    src_l.append(int(dv[j]))
                    dst_l.append(s_new)
                    self._slots[kk] = s_new
                    self._key_of[s_new] = kk
                    self._dirty[s_new] = True   # delta still local
                    self._pending_install_slots.add(s_new)
                    drop.setdefault(ei, []).append(j)
                self._pending_copy.append(
                    (np.asarray(src_l, np.int32),
                     np.asarray(dst_l, np.int32)))
                keep = []
                for ei, (dv, ks) in enumerate(self._pending_evict):
                    if ei in drop:
                        m = np.ones(len(ks), bool)
                        m[drop[ei]] = False
                        dv, ks = dv[m], ks[m]
                    if len(ks):
                        keep.append((dv, ks))
                self._pending_evict = keep
            if miss_keys.size:
                miss_slots = np.array(
                    [self._free.pop() for _ in range(miss_keys.size)],
                    np.int32)
                for kk, s in zip(miss_keys.tolist(), miss_slots.tolist()):
                    self._slots[int(kk)] = int(s)
                    self._key_of[s] = kk
                    self._pending_install_slots.add(int(s))
            # resolve feeds from the now-complete index: one O(U) pass
            # builds the window's key->slot map, each step's row is a
            # vectorized searchsorted into it (all_keys is sorted and a
            # superset of every step's uniques). LRU refresh is window-
            # granular: within one window every key is equally recent.
            slot_of = np.fromiter(
                (self._slots[int(kk)] for kk in all_keys.tolist()),
                np.int32, all_keys.size)
            for i, u in enumerate(uniq_l):
                idx = np.searchsorted(all_keys, u)
                slots_a[i, :u.size] = slot_of[idx]
            for kk in all_keys.tolist():
                self._slots.move_to_end(int(kk))
            for kk in window_pin:
                self._plan_pins[kk] = self._plan_pins.get(kk, 0) + 1
        pull_s = 0.0
        if miss_keys.size:
            # read-your-writes: deltas still queued for a re-faulted key
            # must land before the pull (see _fault_in)
            if self.writeback is not None and \
                    self.writeback.has_pending(self.table_id, miss_keys):
                self.writeback.flush()
            tp = time.perf_counter()
            rows_l = [self.client.pull_sparse(
                          self.table_id, miss_keys[i:i + self.pull_chunk])
                      for i in range(0, miss_keys.size, self.pull_chunk)]
            pull_s = time.perf_counter() - tp
            pull_ms = pull_s * 1e3 / max(
                1, -(-miss_keys.size // self.pull_chunk))
            self._pull_ms_ema = pull_ms if self._pull_ms_ema is None \
                else 0.7 * self._pull_ms_ema + 0.3 * pull_ms
            with self._mu:
                self._pending_install.append(
                    (miss_slots, np.concatenate(rows_l)))
        touched = np.unique(slots_a)
        touched = touched[touched != 0].astype(np.int32)
        inv_a = np.stack(inv_l).reshape((k,) + ids_np.shape[1:])
        return WindowPlan(self, slots_a, inv_a, touched, all_keys,
                          plan_s=time.perf_counter() - t0, pull_s=pull_s)

    def _release_pins(self, keys):
        with self._mu:
            for kk in np.asarray(keys, np.uint64).ravel().tolist():
                kk = int(kk)
                c = self._plan_pins.get(kk)
                if c is not None:
                    if c <= 1:
                        del self._plan_pins[kk]
                    else:
                        self._plan_pins[kk] = c - 1

    def drain_window(self, plan=None):
        """Consume the delta store a compiled scan window accumulated:
        apply the cache optimizer to the touched rows with the
        window-summed gradient (one update per row per window — the
        window-deferred twin of per-step :meth:`apply_grads`), clear the
        carried grad, mark the rows dirty for write-back, release the
        plan's pins and run :meth:`evict_ahead`. Returns the touched row
        count. Without ``plan`` the touched set is recovered from the
        grad's nonzero rows (a host round-trip — pass the plan)."""
        import jax.numpy as jnp

        tt = self._table_t
        if tt is None or tt._grad is None:
            if plan is not None:
                plan.release()
            return 0
        with self._mu:
            self._flush_installs()
            g = tt._grad
            if plan is not None:
                touched = plan.touched_slots
            else:
                nz = np.nonzero(np.asarray(jnp.any(g != 0.0, axis=1)))[0]
                touched = nz[nz != 0].astype(np.int32)
            n = int(touched.size)
            if n:
                b = _bucket(n)
                slots_p = np.zeros(b, np.int32)
                slots_p[:n] = touched
                sj = jnp.asarray(slots_p)
                gj = _jit_gather()(g, sj)  # (b, dim); padded lanes row 0
                if self.optimizer == "sgd":
                    self.table = _jit_sgd()(self.table, sj, gj,
                                            jnp.float32(self.lr))
                else:
                    self.table, self.m, self.v, self.t = _jit_adam()(
                        self.table, self.m, self.v, self.t, sj, gj,
                        jnp.float32(self.lr), jnp.float32(self.beta1),
                        jnp.float32(self.beta2), jnp.float32(self.eps))
                self._dirty[touched] = True
                self._dirty[0] = False  # scratch row never written back
            tt._grad = None
            monitor.stat_add("hbm_cache_window_rows", n)
        if plan is not None:
            plan.release()
        self.evict_ahead()
        return n

    @property
    def stats(self):
        return {k: monitor.stat_get(f"hbm_cache_{k}")
                for k in ("hit", "miss", "evict", "staged",
                          "writeback_rows")}

    # -- internals --------------------------------------------------------
    def _ensure(self, uniq_keys, pinned=None):
        """Map unique keys to device slots, faulting misses in (batched)
        and LRU-evicting if full. ``pinned`` widens the eviction
        exclusion set beyond this call's keys (a window planner passes
        the WHOLE window's keys so a later step's fault cannot evict an
        earlier step's rows). Returns int32 slots. Caller holds _mu."""
        slots = np.empty(uniq_keys.size, np.int32)
        misses = []
        for i, k in enumerate(uniq_keys):
            k = int(k)
            s = self._slots.get(k)
            if s is None:
                misses.append(i)
                slots[i] = -1
            else:
                self._slots.move_to_end(k)
                slots[i] = s
        hits = uniq_keys.size - len(misses)
        monitor.stat_add("hbm_cache_hit", hits)
        self._hit_ema = 0.98 * self._hit_ema + hits
        self._miss_ema = 0.98 * self._miss_ema + len(misses)
        if misses:
            missed = uniq_keys[misses]
            pin = set(uniq_keys.tolist()) | (pinned or set())
            got = self._fault_in(missed, pinned=pin)
            slots[misses] = got
        return slots

    def _fault_in(self, keys, pinned=None, count_miss=True):
        """Pull `keys` from the PS and install them, evicting LRU victims
        (with delta write-back) when the free list runs dry. Pulls are
        chunked (``pull_chunk``) so one giant pass stage never holds an
        unbounded host buffer, and each pull's wall time feeds the
        adaptive-watermark latency EMA. Caller holds _mu."""
        import jax.numpy as jnp

        if keys.size > self.pull_chunk:
            return np.concatenate(
                [self._fault_in(keys[i:i + self.pull_chunk], pinned,
                                count_miss)
                 for i in range(0, keys.size, self.pull_chunk)])
        need = keys.size - len(self._free)
        if need > 0:
            self._evict(need, pinned or set())
        if keys.size > len(self._free):
            raise RuntimeError(
                f"hbm cache over capacity: need {keys.size} slots, "
                f"{len(self._free)} free after eviction (batch working "
                f"set larger than capacity {self.capacity}?)")
        if count_miss:  # pass-level staging is counted as 'staged', not
            monitor.stat_add("hbm_cache_miss", int(keys.size))  # a miss
        # read-your-writes across the async write-back: a key evicted
        # with its delta still queued must not be re-pulled stale
        if self.writeback is not None and \
                self.writeback.has_pending(self.table_id, keys):
            # lint: blocking-call-under-lock read-your-writes: the queued delta must reach the PS before the re-pull or a stale row installs; sync fallback path only — the async pipeline (plan_window) pulls outside the lock
            self.writeback.flush()
        t0 = time.perf_counter()
        # lint: blocking-call-under-lock the SYNC fault-in path holds the cache lock across the pull by design — slot assignment, eviction and install staging must be atomic against concurrent lookups; the async pipeline (plan_window) is the unlocked fast path and the prefetcher hides this cost
        rows = self.client.pull_sparse(self.table_id, keys)
        pull_ms = (time.perf_counter() - t0) * 1e3
        self._pull_ms_ema = pull_ms if self._pull_ms_ema is None else \
            0.7 * self._pull_ms_ema + 0.3 * pull_ms
        slots = np.array([self._free.pop() for _ in range(keys.size)],
                         np.int32)
        for k, s in zip(keys.tolist(), slots.tolist()):
            self._slots[int(k)] = int(s)
            self._key_of[s] = k
        n = keys.size
        b = _bucket(n)
        slots_p = np.zeros(b, np.int32)
        slots_p[:n] = slots
        rows_p = np.zeros((b, self.dim), np.float32)
        rows_p[:n] = rows
        self.table, self.staged = _jit_install()(
            self.table, self.staged, jnp.asarray(slots_p),
            jnp.asarray(rows_p))
        return slots

    def _push_delta(self, keys, delta):
        """Route a delta push: through the bounded background queue when
        one is attached (overlaps the next window's compute; request-id
        dedup keeps retries exactly-once), else synchronously."""
        if self.writeback is not None:
            self.writeback.put(self.table_id, keys, delta)
        else:
            # lint: blocking-call-under-lock sync push fallback when no write-back queue is attached (single-thread CTR path); attach a WriteBackQueue to overlap pushes behind compute — put() above is watermark-bounded, not wire-bound
            self.client.push_sparse_delta(self.table_id, keys, delta)

    def _evict(self, n, pinned, strict=True, defer=False):
        """Free >= n slots from the LRU front, writing dirty victims'
        deltas back first. ``strict=False`` (evict_ahead) frees what it
        can instead of raising. ``defer=True`` (the prefetch thread)
        records the dirty victims instead of reading the device table —
        their rows stay intact until :meth:`_flush_installs` computes
        the deltas, BEFORE any deferred install can reuse the slots.
        Caller holds _mu."""
        import jax.numpy as jnp

        # slots with an un-applied gradient (recorded by lookup, not yet
        # consumed by apply_grads) must not be reused: the later scatter
        # would train whatever key took the slot with the WRONG grad
        pending_slots = set()
        for slots, _p, _t in self._pending:
            pending_slots.update(int(s) for s in slots)
        # a pending-install slot's device row is not written yet —
        # reusing it would let a stale install corrupt the new tenant
        pending_slots |= self._pending_install_slots
        victims, vkeys = [], []
        for k in list(self._slots):          # front of the OrderedDict =
            if (k in pinned or k in self._plan_pins       # LRU front
                    or self._slots[k] in pending_slots):
                continue
            victims.append(self._slots[k])
            vkeys.append(k)
            if len(victims) >= n:
                break
        if len(victims) < n and strict:
            # raise BEFORE touching the index — a failed eviction must
            # leave every candidate resident, not leak their slots
            raise RuntimeError(
                f"hbm cache cannot evict {n} rows: every resident key is "
                f"pinned by the current batch, a planned window, or an "
                f"un-applied gradient (capacity {self.capacity} too small "
                f"for one step's working set)")
        for k in vkeys:
            del self._slots[k]
        if not victims:
            return 0
        victims = np.asarray(victims, np.int32)
        dirty_mask = self._dirty[victims]
        if dirty_mask.any():
            dv = victims[dirty_mask]
            if defer:
                self._pending_evict.append((dv, self._key_of[dv].copy()))
            else:
                delta = np.asarray(_jit_delta()(self.table, self.staged,
                                                jnp.asarray(dv)))
                self._push_delta(self._key_of[dv], delta)
            self._dirty[dv] = False
        self._free.extend(int(s) for s in victims)
        monitor.stat_add("hbm_cache_evict", len(victims))
        return len(victims)

    def _flush_installs(self):
        """Apply the prefetch stage's deferred device work on the
        consumer thread: dirty evictions' delta write-backs first (their
        table rows are still intact), then ONE scatter install of every
        staged pulled row. Cheap when nothing is pending (every
        table-reading entry point calls it)."""
        import jax.numpy as jnp

        with self._mu:
            if self._pending_evict:
                for dv, keys in self._pending_evict:
                    delta = np.asarray(_jit_delta()(
                        self.table, self.staged, jnp.asarray(dv)))
                    self._push_delta(keys, delta)
                self._pending_evict = []
            if self._pending_copy:
                # resurrections (see plan_window): relocate the still-
                # intact rows of deferred-evicted keys that were
                # re-planned before this flush. Must run AFTER the evict
                # deltas above (a copy's destination slot may be another
                # deferred victim's freed slot) and BEFORE the installs
                # (a copy's source slot may have been handed to a
                # pending install). ONE fused move for every pending
                # pair: _jit_move's gathers all read the pre-op table,
                # so a later copy's source being an earlier copy's
                # destination (key re-planned after its old slot was
                # handed to another resurrection) cannot read a
                # partially-moved row — per-batch application in
                # recorded order would.
                src = np.concatenate(
                    [s for s, _d in self._pending_copy])
                dst = np.concatenate(
                    [d for _s, d in self._pending_copy])
                n = src.size
                b = _bucket(n)
                src_p = np.zeros(b, np.int32)
                dst_p = np.zeros(b, np.int32)
                src_p[:n] = src
                dst_p[:n] = dst
                self.table, self.staged = _jit_move()(
                    self.table, self.staged, jnp.asarray(src_p),
                    jnp.asarray(dst_p))
                for s in dst.tolist():
                    self._pending_install_slots.discard(int(s))
                self._pending_copy = []
            if self._pending_install:
                slots = np.concatenate(
                    [s for s, _r in self._pending_install])
                rows = np.concatenate(
                    [r for _s, r in self._pending_install])
                n = slots.size
                b = _bucket(n)
                slots_p = np.zeros(b, np.int32)
                slots_p[:n] = slots
                rows_p = np.zeros((b, self.dim), np.float32)
                rows_p[:n] = rows
                self.table, self.staged = _jit_install()(
                    self.table, self.staged, jnp.asarray(slots_p),
                    jnp.asarray(rows_p))
                self._pending_install = []
                # only the slots actually installed lose protection:
                # a plan_window whose PS pull is still in flight has
                # registered its slots here but not yet appended rows —
                # clearing those would let _evict hand the slot to a new
                # key that the late install then silently overwrites
                for s in slots.tolist():
                    self._pending_install_slots.discard(int(s))

    # -- telemetry-driven eviction (adaptive watermark) -------------------
    def _pull_ms(self):
        """Best available estimate of one PS pull's latency: the
        client-side EMA measured around ``pull_sparse`` (covers network
        + service; tests inject ``_pull_ms_ema`` directly), falling back
        to the service-side ``ps_server_op_ns`` export when this client
        has not pulled yet but an in-process server has history."""
        if self._pull_ms_ema is not None:
            return self._pull_ms_ema
        try:
            from .server import server_op_stats
            for r in server_op_stats():
                if (r["table"] == self.table_id
                        and r["op"] == "pull_sparse" and r["calls"]):
                    return r["ns"] / r["calls"] / 1e6
        except Exception:
            pass
        return None

    def free_target(self):
        """Adaptive eviction watermark: how many slots to keep FREE,
        in ``[watermark_min_frac, watermark_max_frac] * capacity``.

        Driven by the cache's own hit/miss pressure (decayed EMAs of the
        ``hbm_cache_hit``/``hbm_cache_miss`` counters) and the PS pull
        latency (:meth:`_pull_ms`): when pulls are expensive and misses
        are happening, future faults should find free slots waiting
        (eviction + write-back already amortized into the background)
        instead of paying evict + pull serially; when pulls are cheap or
        the working set fits, eviction stays lazy."""
        import math

        lo = int(self.watermark_min_frac * self.capacity)
        hi = int(self.watermark_max_frac * self.capacity)
        pull_ms = self._pull_ms()
        seen = self._hit_ema + self._miss_ema
        if pull_ms is None or seen <= 0.0:
            return lo
        # latency weight: <=0.1 ms (loopback, in-memory) -> 0;
        # >=10 ms (remote, loaded PS) -> 1; log-linear between
        lat = min(1.0, max(0.0,
                           (math.log10(max(pull_ms, 1e-3)) + 1.0) / 2.0))
        miss_rate = self._miss_ema / seen
        pressure = lat * min(1.0, 4.0 * miss_rate)
        return lo + int(round((hi - lo) * pressure))

    def evict_ahead(self):
        """Evict LRU rows down to :meth:`free_target` ahead of demand
        (best-effort: pinned/pending rows block silently). Called at
        window drains; callable from any maintenance point. Returns the
        number of rows freed."""
        with self._mu:
            need = self.free_target() - len(self._free)
            if need <= 0:
                return 0
            return self._evict(need, set(), strict=False)


class CachedSparseEmbedding(SparseEmbedding):
    """Drop-in :class:`SparseEmbedding` whose rows are served from an
    HBM-resident cache instead of a per-batch PS round-trip (reference:
    the PSGPUTrainer path reads `heter_ps` device tables where the
    Downpour path calls pull_sparse per batch).

    Inside a ``to_static(..., scan_steps=k)`` body, feed the layer a
    ``(slots, inv)`` pair from a prefetched
    :class:`~.async_cache.WindowPlan` (``plan.feeds()``) instead of raw
    ids — the host-side key→slot resolution cannot run under tracing,
    so the planner does it ahead of the window and the traced lookup is
    a pure static-shaped gather from the carried table."""

    def __init__(self, size, capacity=None, table_id=None, init_range=0.1,
                 optimizer="sgd", lr=0.01, beta1=0.9, beta2=0.999,
                 eps=1e-8, mesh=None, mesh_axis=None, writeback=None,
                 watermark=(0.0, 0.15), name=None):
        super().__init__(size, table_id=table_id, init_range=init_range,
                         name=name)
        num, _dim = size
        self.capacity = capacity if capacity is not None else num + 1
        self._cache_cfg = dict(optimizer=optimizer, lr=lr, beta1=beta1,
                               beta2=beta2, eps=eps, mesh=mesh,
                               mesh_axis=mesh_axis, writeback=writeback,
                               watermark=watermark)
        self.cache = None

    def bind(self, communicator):
        super().bind(communicator)
        self.cache = HbmEmbeddingCache(
            communicator.client, self.table_id, self.embedding_dim,
            self.capacity, **self._cache_cfg)

    def forward(self, ids):
        if self.cache is None:
            raise RuntimeError(
                "CachedSparseEmbedding is not bound — call "
                "fleet.init_worker() (or .bind(communicator)) first")
        if isinstance(ids, (tuple, list)) and len(ids) == 2:
            return self.cache.scan_lookup(*ids)
        from ...jit.to_static import in_tracing
        if in_tracing():
            raise RuntimeError(
                "CachedSparseEmbedding inside a to_static body needs "
                "prebuilt (slots, inv) feeds — plan the window with "
                "HbmEmbeddingCache.plan_window (or a CachePrefetcher) "
                "and pass plan.feeds(), not raw ids")
        return self.cache.lookup(ids)


class PsTpuTrainer:
    """Pass-level trainer driving cached embeddings — the PSGPUTrainer
    analog (reference: `framework/trainer.h:250`, `ps_gpu_worker.cc`).

    Per pass: stage every key the pass will touch (BuildGPUPSTask), run
    the batches with on-device sparse updates, write the trained rows
    back (EndPass). Dense parameters ride the given communicator exactly
    like the Downpour path, so a model can mix cached and direct
    embeddings freely.
    """

    def __init__(self, model, loss_fn, communicator, keys_fn=None):
        self.model = model
        self.loss_fn = loss_fn
        self.comm = communicator
        self.keys_fn = keys_fn
        self.caches = [sub.cache
                       for sub in model.sublayers(include_self=True)
                       if isinstance(sub, CachedSparseEmbedding)]
        if any(c is None for c in self.caches):
            raise RuntimeError("model has unbound CachedSparseEmbedding "
                               "layers — bind_model() first")

    def train_pass(self, batches):
        """One dataset pass. `batches` is materialized (the reference's
        LoadIntoMemory) so keys can be collected before training. Returns
        ``{"batches": n, "loss_sum": s, "losses": [...]}``."""
        from .embedding import flush_sparse_grads

        batches = list(batches)
        by_table = {}
        for batch in batches:
            for tid, keys in self._batch_keys(batch).items():
                by_table.setdefault(tid, []).append(
                    np.asarray(keys, np.uint64).ravel())
        for cache in self.caches:
            keys = by_table.get(cache.table_id)
            if keys:
                cache.build_pass(np.concatenate(keys))
        losses = []
        for batch in batches:
            loss = self.loss_fn(self.model, batch)
            loss.backward()
            for cache in self.caches:
                cache.apply_grads()
            flush_sparse_grads(self.comm)  # plain SparseEmbedding layers
            self.comm.step()
            losses.append(float(loss.numpy()))
        for cache in self.caches:
            cache.end_pass()
        return {"batches": len(batches), "loss_sum": float(sum(losses)),
                "losses": losses}

    def _batch_keys(self, batch):
        if self.keys_fn is not None:
            return self.keys_fn(batch)
        if len(self.caches) == 1 and isinstance(batch, (tuple, list)):
            return {self.caches[0].table_id:
                    np.asarray(unwrap(batch[0])).astype(np.uint64)}
        raise RuntimeError(
            "pass keys_fn(batch) -> {table_id: ids} when the model has "
            "multiple cached embeddings or a custom batch layout")
