"""Optimizers.

Reference: `python/paddle/optimizer/` (2.x rewrite of fluid/optimizer.py:59
family) and the device kernels in `operators/optimizers/` (sgd_op, momentum_op,
adam_op, lamb_op...). Here each optimizer's update is pure jnp on the raw
param/accumulator values: eager mode applies it per step; under `to_static`
the whole update fuses into the compiled training step with donated buffers
(the XLA answer to the reference's in-place param updates).

Accumulators are created eagerly at construction so they are registered
framework state before any tracing happens.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn.clip import ClipGradBase
from ..observability.scopes import scope


class _LRValue:
    """Learning rate as a stateful scalar tensor: scheduler updates don't
    retrace the compiled step (the analog of the reference's lr var in scope)."""

    def __init__(self, lr):
        from .lr import LRScheduler
        self.scheduler = None
        if isinstance(lr, LRScheduler):
            self.scheduler = lr
            lr_value = lr.get_lr()
        else:
            lr_value = float(lr)
        self.tensor = Tensor(jnp.asarray(lr_value, jnp.float32))
        self.tensor.persistable = True
        self.tensor._ledger_category = "lr"  # memory-ledger attribution
        self.tensor._mark_stateful()
        if self.scheduler is not None:
            self.scheduler._bind(self)

    def value(self):
        return self.tensor._value

    def set(self, v):
        self.tensor.set_value(jnp.asarray(v, jnp.float32))


_FLAT_LANES = 1024  # row width: multiple of the (8,128) f32 tile

# gradient dtypes a ZeRO bucket exchanges as they are (summed in float32
# where they land) instead of reducing in float32 on the wire
_NARROW_FLOATS = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


class _FlatSlot:
    """Per-param view into a coalesced accumulator buffer: reads slice the
    flat tensor lazily; writes are staged and flushed once per step (the
    TPU analog of the reference's fuse_all_optimizer_ops /
    coalesce_tensor pass — one jit boundary crossing per slot instead of
    one per (slot, param); trades extra in-program update-slice traffic
    for fewer dispatch arguments, so it pays off when per-call dispatch
    dominates, i.e. small models). The store is [rows, 1024] with aligned
    per-param row segments — a giant 1-D buffer provokes pathological
    re-tiling on TPU (observed: [55M, 2] padded 64x to 28 GB)."""

    __slots__ = ("store", "row_off", "n_rows", "size", "shape", "out_dtype")

    def __init__(self, store, row_off, n_rows, size, shape, out_dtype=None):
        self.store = store
        self.row_off = row_off
        self.n_rows = n_rows
        self.size = size
        self.shape = shape
        self.out_dtype = out_dtype

    @property
    def _value(self):
        buf = self.store.tensor._value
        rows = jax.lax.dynamic_slice(buf, (self.row_off, 0),
                                     (self.n_rows, _FLAT_LANES))
        out = rows.reshape(-1)[:self.size].reshape(self.shape)
        if self.out_dtype is not None and out.dtype != self.out_dtype:
            out = out.astype(self.out_dtype)
        return out

    @_value.setter
    def _value(self, new):
        self.store.pending.append((self, new))

    def set_value(self, value):
        self.store.pending.append((self, jnp.asarray(value)))
        self.store.flush()


class _FlatStore:
    """One [rows, 1024] buffer per accumulator slot name (f32 for
    moments/masters; ZeRO-3 parameter stores keep the params' own dtype).
    ``pad_rows`` appends zero rows so the row count divides the ZeRO shard
    degree (each rank then owns a contiguous, equally-sized row range)."""

    def __init__(self, fills, pad_rows=0, dtype=jnp.float32):
        assert fills, "a flat store always covers at least one param"
        rows = []
        for n_rows, size, fill in fills:
            seg = jnp.full((n_rows * _FLAT_LANES,), fill, dtype)
            rows.append(seg.reshape(n_rows, _FLAT_LANES))
        if pad_rows:
            rows.append(jnp.zeros((pad_rows, _FLAT_LANES), dtype))
        self.tensor = Tensor(jnp.concatenate(rows))
        self.tensor.persistable = True
        self.tensor._mark_stateful()
        self.pending = []
        # eager-write notification: the ZeRO-3 prefetch slot is a derived
        # cache of the bucket-0 param store and must track out-of-band
        # writes (load_state_dict, user set_value)
        self.on_flush = None

    def flush(self):
        if not self.pending:
            return
        buf = self.tensor._value
        for view, new in self.pending:
            flat = jnp.ravel(new).astype(buf.dtype)
            pad = view.n_rows * _FLAT_LANES - view.size
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), buf.dtype)])
            buf = jax.lax.dynamic_update_slice(
                buf, flat.reshape(view.n_rows, _FLAT_LANES),
                (view.row_off, 0))
        if (self.tensor.pspec is not None
                and not isinstance(buf, jax.core.Tracer)):
            # eager write into a mesh-resident sharded store: keep the
            # 1/degree layout instead of letting the update replicate it
            from ..distributed import parallel_env
            mesh = parallel_env.current_mesh()
            if mesh is not None:
                from jax.sharding import NamedSharding
                buf = jax.device_put(
                    buf, NamedSharding(mesh, self.tensor.pspec))
        self.tensor._value = buf
        self.pending = []
        if self.on_flush is not None \
                and not isinstance(buf, jax.core.Tracer):
            self.on_flush()


class _ZeroBucket:
    """Flat row layout of one gradient-reduction bucket (ZeRO-1/2).

    All of the bucket's per-param tensors (grads, moments, fp32 masters,
    params during the update) share this [rows, 1024] layout: per-param
    row-aligned segments, total rows padded to a multiple of the shard
    degree so ``lax.psum_scatter(..., scatter_dimension=0, tiled=True)``
    — or the ``all_to_all`` of the bucket viewed [degree, rows/degree,
    1024] — hands each rank a contiguous [rows/degree, 1024] shard that
    lines up exactly with its shard of the bucket's moment/master
    stores."""

    __slots__ = ("index", "params", "sizes", "shapes", "n_rows", "row_offs",
                 "rows", "pad_rows", "degree", "has_master", "param_dtype",
                 "l2_rows", "l1_rows", "lr_rows")

    def __init__(self, index, params, degree):
        self.index = index
        self.params = list(params)
        self.degree = max(int(degree), 1)
        self.sizes, self.shapes, self.n_rows, self.row_offs = [], [], [], []
        self.has_master = False
        self.param_dtype = None  # stage-3 flat param store dtype
        self.l2_rows = None  # [rows,1] decay coeff per segment (or None)
        self.l1_rows = None
        self.lr_rows = None  # [rows,1] per-param lr scale (or None)
        off = 0
        for p in self.params:
            shape = tuple(p._value.shape)
            size = int(np.prod(shape)) if shape else 1
            n_rows = -(-size // _FLAT_LANES)
            self.sizes.append(size)
            self.shapes.append(shape)
            self.n_rows.append(n_rows)
            self.row_offs.append(off)
            off += n_rows
        self.pad_rows = (-off) % self.degree
        self.rows = off + self.pad_rows

    @property
    def shard_rows(self):
        return self.rows // self.degree

    def fills(self, fill=0.0):
        """_FlatStore fill spec covering this bucket's param segments."""
        return [(n, s, fill) for n, s in zip(self.n_rows, self.sizes)]

    def flatten(self, vals, dtype=jnp.float32):
        """Per-param arrays -> the [rows, 1024] bucket layout in ``dtype``
        (f32 for moments and float32 gradients, the gradients' own type
        for a 16-bit bucket, the param dtype for stage-3 stores)."""
        segs = []
        with scope("zero.bucket_copy"):
            for v, n_rows, size in zip(vals, self.n_rows, self.sizes):
                flat = jnp.ravel(v)
                if flat.dtype != dtype:
                    flat = flat.astype(dtype)
                pad = n_rows * _FLAT_LANES - size
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
                segs.append(flat.reshape(n_rows, _FLAT_LANES))
            if self.pad_rows:
                segs.append(jnp.zeros((self.pad_rows, _FLAT_LANES), dtype))
            return segs[0] if len(segs) == 1 else jnp.concatenate(segs)

    def unflatten(self, rows):
        """[rows, 1024] bucket layout -> per-param arrays (store dtype)."""
        with scope("zero.bucket_copy"):
            return [rows[off:off + n].reshape(-1)[:size].reshape(shape)
                    for off, n, size, shape in zip(
                        self.row_offs, self.n_rows, self.sizes, self.shapes)]

    def shard_of(self, rows_full, axis, bound):
        """This rank's [rows/degree, width] shard of a full row-aligned
        array (the [rows, 1024] bucket or a [rows, 1] row mask). With the
        axis bound (inside shard_map) the rank index is dynamic; in the
        abstract analysis trace rank 0's slice stands in (shape is all
        that matters there)."""
        if bound:
            idx = jax.lax.axis_index(axis)
            return jax.lax.dynamic_slice(
                rows_full, (idx * self.shard_rows, 0),
                (self.shard_rows, rows_full.shape[1]))
        return jax.lax.slice_in_dim(rows_full, 0, self.shard_rows, axis=0)

    def row_mask(self, flags):
        """[rows, 1] bool numpy mask, True over the segments of params
        whose flag is set (padding rows False)."""
        parts = [np.full((n, 1), bool(f)) for n, f in zip(self.n_rows, flags)]
        if self.pad_rows:
            parts.append(np.zeros((self.pad_rows, 1), bool))
        return np.concatenate(parts)


def _zero_sum_pieces(raw):
    """The float32 tail of one bucket's narrow exchange: ``raw`` is the
    ``[degree, rows/degree, 1024]`` stack ``all_to_all`` delivered
    (piece j = rank j's 16-bit gradient rows for this rank); each piece
    is converted to float32 — exact — and the pieces are added in rank
    order by explicit adds, so the order of summation is the program's
    and every rank's, not the backend's. A float32 ``psum_scatter``
    result (2-D) is already that sum and passes through."""
    if raw.ndim == 2:
        return raw
    total = raw[0].astype(jnp.float32)
    for j in range(1, raw.shape[0]):
        total = total + raw[j].astype(jnp.float32)
    return total


class _ZeroView:
    """Stands in for a parameter during the flat shard update: carries the
    flat param shard as ``_value`` and the markers that keep per-param
    decay out of the (already pre-decayed) flat path."""

    def __init__(self, value, name, decay_mask=None):
        self._value = value
        self.name = name
        self._zero_predecayed = True
        if decay_mask is not None:
            self._zero_decay_mask = decay_mask


class _Box:
    """Minimal settable accumulator proxy for ``_apply_one``."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value


_MISSING = object()
_ZERO3_CLASSES = {}


def _zero3_class(cls):
    """Subclass of a parameter class whose ``_value`` is a property over a
    ZeRO-3 flat-store row segment. Inside a traced step, reads return the
    just-in-time materialized (all_gathered) value the step hook installed
    and writes stage a per-trace override; eagerly, reads slice the
    sharded store on demand — no full-size parameter buffer stays
    resident — and writes go through to the store rows. Instances are
    converted in place (``__class__`` reassignment), so every existing
    reference — layer attributes, optimizer param groups, state_dict
    walks — sees the sharded layout without relinking."""
    sub = _ZERO3_CLASSES.get(cls)
    if sub is not None:
        return sub

    class _Zero3Param(cls):
        @property
        def _value(self):
            d = self.__dict__
            ov = d.get("_zero3_ov", _MISSING)
            if ov is not _MISSING:
                return ov
            lazy = d.get("_zero3_lazy")
            if lazy is not None:
                # first in-trace read of this bucket: gather it and
                # install overrides for every param it covers
                lazy()
                return d["_zero3_ov"]
            return d["_zero3_slot"]._value

        @_value.setter
        def _value(self, new):
            from ..jit.to_static import in_tracing
            if in_tracing():
                self.__dict__["_zero3_ov"] = new
            else:
                self.__dict__.pop("_zero3_ov", None)
                self.__dict__.pop("_zero3_lazy", None)
                slot = self.__dict__["_zero3_slot"]
                slot.store.pending.append((slot, new))
                slot.store.flush()

    _Zero3Param.__name__ = cls.__name__
    _Zero3Param.__qualname__ = cls.__qualname__
    _ZERO3_CLASSES[cls] = _Zero3Param
    return _Zero3Param


class Optimizer:
    # ZeRO sharded-step support: None until _zero_enable() partitions the
    # state. _zero_compatible=False marks optimizers whose update is not
    # elementwise (norm-trust-ratio / RNG updates can't run on a flat
    # shard and reassemble to the replicated answer).
    _zero = None
    _zero_compatible = True

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, fuse_accumulators=False):
        if parameters is None:
            # static-graph style: parameters resolved at minimize() time from
            # the current Program (reference: fluid Optimizer.minimize)
            parameters = []
        parameters = list(parameters)
        if parameters and isinstance(parameters[0], dict):
            self._param_groups = []
            for g in parameters:
                group = dict(g)
                group["params"] = list(g["params"])
                self._param_groups.append(group)
        else:
            self._param_groups = [{"params": parameters}]
        self._lr = _LRValue(learning_rate)
        self._weight_decay = self._wd_value(weight_decay)
        self._grad_clip = grad_clip
        assert grad_clip is None or isinstance(grad_clip, ClipGradBase)
        self._accumulators = {}  # (slot, param_id) -> Tensor or _FlatSlot
        self._fuse_acc = fuse_accumulators
        self._flat_stores = {}  # slot -> _FlatStore
        self._flat_pending = []  # (slot, param, fill) until finalized
        self._step_count = Tensor(jnp.zeros((), jnp.int32))
        self._step_count._ledger_category = "lr"
        self._step_count._mark_stateful()
        for group in self._param_groups:
            for p in group["params"]:
                self._create_accumulators(p)
        self._finalize_flat()

    def _finalize_flat(self):
        if not self._flat_pending:
            return
        by_slot = {}
        for slot, p, fill in self._flat_pending:
            by_slot.setdefault(slot, []).append((p, fill))
        for slot, items in by_slot.items():
            row_off = 0
            fills = []
            views = []
            for p, fill in items:
                size = int(np.prod(p._value.shape)) if p._value.shape else 1
                n_rows = -(-size // _FLAT_LANES)
                views.append((p, row_off, n_rows, size,
                              tuple(p._value.shape)))
                fills.append((n_rows, size, fill))
                row_off += n_rows
            store = _FlatStore(fills)
            store.tensor._ledger_category = ("master" if slot == "master"
                                             else "opt_moment")
            self._flat_stores[slot] = store
            for p, ro, n_rows, size, shape in views:
                self._accumulators[(slot, id(p))] = _FlatSlot(
                    store, ro, n_rows, size, shape)
        self._flat_pending = []

    @staticmethod
    def _wd_value(weight_decay):
        from ..regularizer import L2Decay, L1Decay
        if weight_decay is None:
            return 0.0
        if isinstance(weight_decay, (L2Decay, L1Decay)):
            return weight_decay
        return float(weight_decay)

    # -- accumulator management ------------------------------------------
    def _add_accumulator(self, slot, param, fill=0.0, dtype=None):
        key = (slot, id(param))
        if key not in self._accumulators:
            if self._fuse_acc and dtype is None:
                self._flat_pending.append((slot, param, fill))
                return None  # view created in _finalize_flat
            t = Tensor(jnp.full(param._value.shape, fill,
                                dtype or jnp.float32))
            t.persistable = True
            t._ledger_category = "opt_moment"
            t._mark_stateful()
            self._accumulators[key] = t
        return self._accumulators[key]

    def _get_accumulator(self, slot, param):
        return self._accumulators[(slot, id(param))]

    def _maybe_master(self, param):
        """Create the fp32 master copy for a low-precision parameter
        (reference: multi_precision in adam/adamw/momentum ops — the O2
        mixed-precision contract: params live in bf16/f16 for fwd/bwd
        HBM traffic, the optimizer updates an fp32 master and casts)."""
        if not getattr(self, "_multi_precision", False):
            return None
        if param._value.dtype not in (jnp.bfloat16, jnp.float16):
            return None
        key = ("master", id(param))
        t = self._accumulators.get(key)
        if t is None:
            t = Tensor(param._value.astype(jnp.float32))
            t.persistable = True
            t._ledger_category = "master"
            t._mark_stateful()
            self._accumulators[key] = t
        return t

    def _create_accumulators(self, param):
        pass  # subclasses pre-create slots here

    # -- API --------------------------------------------------------------
    def get_lr(self):
        return float(self._lr.value())

    def set_lr(self, value):
        self._lr.set(value)

    def _parameters(self):
        for group in self._param_groups:
            yield from group["params"]

    def clear_grad(self, set_to_zero=False):
        from ..distributed import parallel_env
        acc = parallel_env.current_accum()
        if acc is not None and acc[0] == "accum":
            return  # accumulation window: @GRAD survives the micro step
        for p in self._parameters():
            p._grad = None

    clear_gradients = clear_grad

    def _decayed_grad(self, p, g):
        """Apply L2/L1 'regularizer-style' decay into the gradient (the
        reference's regularizer path; AdamW-style decoupled decay overrides)."""
        from ..regularizer import L1Decay, L2Decay
        if getattr(p, "_zero_predecayed", False):
            # flat ZeRO view: decay was already applied per-param on the
            # full gradient before bucketing (per-param regularizers can't
            # be expressed on the concatenated shard)
            return g
        wd = self._weight_decay
        reg = getattr(p, "regularizer", None) or wd
        if isinstance(reg, L2Decay):
            return g + reg.coeff * p._value
        if isinstance(reg, L1Decay):
            return g + reg.coeff * jnp.sign(p._value)
        if isinstance(reg, float) and reg != 0.0:
            return g + reg * p._value
        return g

    # -- ZeRO-1/2 sharded step --------------------------------------------
    def _zero_enable(self, axis=None, mesh=None, stage=1,
                     comm_buffer_mb=None, last_comm_buffer_mb=None,
                     prefetch=None):
        """Partition this optimizer's state for ZeRO data parallelism over
        one mesh axis: moments (and fp32 masters under multi_precision)
        move into per-bucket flat [rows, 1024] stores sharded 1/degree per
        rank (PartitionSpec(axis, None)); ``step()`` switches to the
        sharded update — bucketed gradient reduction to a float32 shard
        per rank, shard-local update math (global-norm/value grad
        clipping, decay and per-param lr scales applied on the flat
        shard views), all_gather of refreshed params. What a bucket
        puts on the wire follows its gradients' dtype, observed at trace
        time (no argument selects it): 16-bit float gradients (bf16 /
        fp16 parameters) cross as they are — one ``all_to_all`` of the
        16-bit bucket, each rank receiving every rank's rows of its
        shard — and are converted and summed in float32 where they
        land; float32 gradients are reduced by a float32
        ``psum_scatter``. Either way the shard is the float32 sum of the
        gradients backward produced: nothing is rounded below float32
        before it is summed (``_zero_reduced_shard``). Buckets are sized from
        ``comm_buffer_mb`` (the DataParallel ``comm_buffer_size`` knob) so
        the reduction of bucket i can overlap the backward compute of
        bucket i+1.

        Stages: 1 and 2 differ only in gradient lifetime — both reduce
        the same way, but stage 2 frees (clears) each param's full
        gradient the moment its bucket shard is consumed, so no full
        gradient outlives the update. Stage 3 additionally moves the
        PARAMETERS into per-bucket flat stores sharded 1/degree (their own
        dtype; fp32 only for mixed-dtype buckets): the live ``Parameter``
        objects become views, full values are materialized just-in-time
        inside the compiled step by a per-bucket ``all_gather`` before the
        forward pass and dropped after the body, and the update writes
        back only the local shard rows — per-chip param + optimizer HBM is
        O(params/degree). Stages 2/3 also allocate a sharded per-bucket
        gradient accumulator ridden by ``to_static(accumulate_steps=a)``
        windows. Returns the number of accumulator views sharded.

        ``prefetch`` (default on) selects the latency-hiding step
        schedule: the sharded update software-pipelines each bucket's
        reduction collective (``psum_scatter`` or the 16-bit
        ``all_to_all``; its float32 convert-and-add stays with the
        deferred mean divide) ahead of the previous bucket's update
        math, and
        stage 3 double-buffers the parameter gathers — bucket i+1's
        ``all_gather`` issues while bucket i computes, with bucket 0
        arriving through a full-bucket prefetch carry slot that the
        step's tail refills for step N+1 (warm-started across scan
        iterations and accumulation windows). Collective payloads and
        per-bucket math are unchanged — only the emission order moves —
        so the pipelined step stays bitwise-equal to the serial one;
        ``prefetch=False`` keeps the on-demand serial schedule (the A/B
        control). The slot costs one full bucket of parameter bytes on
        the carry."""
        from jax.sharding import PartitionSpec
        from ..core import state as state_mod
        from ..distributed import bucketing, parallel_env
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue
        from ..regularizer import L1Decay, L2Decay
        if self._zero is not None:
            same = (axis in (None, self._zero["axis"])
                    and int(stage) == self._zero["stage"]
                    and (comm_buffer_mb is None
                         or float(comm_buffer_mb)
                         == self._zero["comm_buffer_mb"])
                    and (prefetch is None
                         or bool(prefetch) == self._zero["prefetch"]))
            if not same:
                raise RuntimeError(
                    f"ZeRO already enabled with axis="
                    f"{self._zero['axis']!r} stage={self._zero['stage']} "
                    f"comm_buffer_mb={self._zero['comm_buffer_mb']}; "
                    f"re-enabling with (axis={axis!r}, stage={stage}, "
                    f"comm_buffer_mb={comm_buffer_mb}) would silently "
                    "keep the old layout — build a fresh optimizer")
            return self._zero["n_sharded"]
        if not self._zero_compatible:
            raise NotImplementedError(
                f"{type(self).__name__} has a non-elementwise update "
                "(norm/trust-ratio or RNG terms) and cannot run sharded; "
                "ZeRO supports SGD/Momentum/Adam/AdamW-family optimizers "
                "(per-tensor-norm optimizers stay out of scope of ISSUE 5: "
                "ZeRO-3 parameter sharding)")
        if self._grad_clip is not None and not isinstance(
                self._grad_clip, (ClipGradByGlobalNorm, ClipGradByValue)):
            raise NotImplementedError(
                f"{type(self._grad_clip).__name__} needs per-parameter "
                "norms, which a flat bucket shard cannot reassemble; ZeRO "
                "composes with ClipGradByGlobalNorm (psum of per-shard "
                "square sums) and ClipGradByValue (elementwise) — "
                "per-tensor-norm clip stays out of scope of ISSUE 5")
        mesh = mesh if mesh is not None else parallel_env.current_mesh()
        if mesh is None:
            raise RuntimeError(
                "ZeRO needs an active device mesh (fleet.init or "
                "paddle_tpu.distributed.parallel_env.set_mesh)")
        axis = axis or "dp"
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no axis {axis!r}")
        if int(stage) not in (1, 2, 3):
            raise ValueError(f"ZeRO stage must be 1, 2 or 3, got {stage}")
        degree = parallel_env.axis_degree(mesh, axis)
        params = [p for p in self._parameters() if not p.stop_gradient]
        if not params:
            raise ValueError("ZeRO sharding needs trainable parameters")
        lp = (jnp.bfloat16, jnp.float16)
        for p in params:
            if p.pspec is not None and any(s is not None for s in p.pspec):
                raise NotImplementedError(
                    f"param {p.name} already carries layout {p.pspec}; "
                    "ZeRO shards REPLICATED parameters (tensor-parallel "
                    "params go through the GSPMD annotation path)")
        if comm_buffer_mb is None:
            comm_buffer_mb = bucketing.DEFAULT_COMM_BUFFER_MB
        pids = {id(p) for p in params}
        slots = sorted({s for (s, pid) in self._accumulators
                        if pid in pids and s != "master"})

        def _drop(t):
            if getattr(t, "_state_uid", None) is not None:
                state_mod.unregister(t._state_uid)

        buckets, stores = [], []
        wd = self._weight_decay
        for bi, bparams in enumerate(bucketing.bucket_params(
                params, comm_buffer_mb, last_comm_buffer_mb,
                counter_prefix="zero")):
            zb = _ZeroBucket(bi, bparams, degree)
            zb.has_master = (bool(getattr(self, "_multi_precision", False))
                             and any(p._value.dtype in lp for p in bparams))
            # flat-view row metadata: regularizer decay and per-param lr
            # scales become [rows, 1] arrays over the row-aligned segments
            # (padding rows: coeff 0 / scale 1) so the shard update can
            # apply them elementwise, matching the per-param control
            l2 = np.zeros((zb.rows, 1), np.float32)
            l1 = np.zeros((zb.rows, 1), np.float32)
            lrs = np.ones((zb.rows, 1), np.float32)
            any_l2 = any_l1 = any_lr = False
            for p, off, n in zip(zb.params, zb.row_offs, zb.n_rows):
                reg = getattr(p, "regularizer", None) or wd
                if isinstance(reg, L2Decay) and reg.coeff:
                    l2[off:off + n] = reg.coeff
                    any_l2 = True
                elif isinstance(reg, L1Decay) and reg.coeff:
                    l1[off:off + n] = reg.coeff
                    any_l1 = True
                elif isinstance(reg, float) and reg != 0.0:
                    l2[off:off + n] = reg
                    any_l2 = True
                scale = p.__dict__.get("optimize_attr", {}).get(
                    "learning_rate", 1.0)
                if scale != 1.0:
                    lrs[off:off + n] = scale
                    any_lr = True
            zb.l2_rows = l2 if any_l2 else None
            zb.l1_rows = l1 if any_l1 else None
            zb.lr_rows = lrs if any_lr else None
            sdict = {}
            for slot in slots + (["master"] if zb.has_master else []):
                store = _FlatStore(zb.fills(), pad_rows=zb.pad_rows)
                store.tensor.pspec = PartitionSpec(axis, None)
                store.tensor.name = f"zero_{slot}_b{bi}"
                store.tensor._ledger_category = (
                    "zero_master" if slot == "master" else "zero_moment")
                sdict[slot] = store
            if int(stage) >= 2:
                # sharded window accumulator for to_static's
                # accumulate_steps: micro-step mean shards fold in here so
                # no full gradient survives a micro step. Zeros until an
                # accumulation window runs; carry-optional so a
                # non-accumulating step skipping it is not a hazard.
                store = _FlatStore(zb.fills(0.0), pad_rows=zb.pad_rows)
                store.tensor.pspec = PartitionSpec(axis, None)
                store.tensor.name = f"zero_gacc_b{bi}"
                store.tensor._ledger_category = "gacc"
                store.tensor._carry_optional = True
                sdict["gacc"] = store
            if int(stage) == 3:
                pdtypes = {p._value.dtype for p in bparams}
                zb.param_dtype = (pdtypes.pop() if len(pdtypes) == 1
                                  else jnp.dtype(jnp.float32))
                store = _FlatStore(zb.fills(), pad_rows=zb.pad_rows,
                                   dtype=zb.param_dtype)
                store.tensor.pspec = PartitionSpec(axis, None)
                store.tensor.name = f"zero_param_b{bi}"
                store.tensor._ledger_category = "zero_param"
                store.tensor._value = zb.flatten(
                    [p._value for p in bparams], dtype=zb.param_dtype)
                sdict["param"] = store
            # migrate existing accumulator/master values into the sharded
            # views (warm restarts / loaded state survive the re-layout)
            for p, off, n_rows, size, shape in zip(
                    zb.params, zb.row_offs, zb.n_rows, zb.sizes, zb.shapes):
                for slot in slots:
                    view = _FlatSlot(sdict[slot], off, n_rows, size, shape)
                    old = self._accumulators.get((slot, id(p)))
                    if old is not None:
                        view.set_value(old._value)
                        if not isinstance(old, _FlatSlot):
                            _drop(old)
                    self._accumulators[(slot, id(p))] = view
                if zb.has_master:
                    view = _FlatSlot(sdict["master"], off, n_rows, size,
                                     shape)
                    old = self._accumulators.pop(("master", id(p)), None)
                    view.set_value(old._value if old is not None
                                   else p._value.astype(jnp.float32))
                    if old is not None and not isinstance(old, _FlatSlot):
                        _drop(old)
                    self._accumulators[("master", id(p))] = view
            from jax.sharding import NamedSharding
            for store in sdict.values():
                # resident sharded from day one: the 1/degree HBM saving
                # is a property of the layout, not of the first step
                store.flush()
                store.tensor._value = jax.device_put(
                    store.tensor._value,
                    NamedSharding(mesh, store.tensor.pspec))
            if int(stage) == 3:
                # convert the live Parameter objects into store views:
                # drop the full replicated buffer (the HBM saving), swap
                # in the view class, and take the params out of the
                # framework-state registry — from here on the only
                # parameter residency is the 1/degree flat store riding
                # the compiled step's donated carry
                for p, off, n_rows, size, shape in zip(
                        zb.params, zb.row_offs, zb.n_rows, zb.sizes,
                        zb.shapes):
                    slot = _FlatSlot(sdict["param"], off, n_rows, size,
                                     shape, out_dtype=p._value.dtype)
                    if p._state_uid is not None:
                        state_mod.unregister(p._state_uid)
                        p._state_uid = None
                    p.__dict__.pop("_value", None)
                    p.__class__ = _zero3_class(type(p))
                    p.__dict__["_zero3_slot"] = slot
            buckets.append(zb)
            stores.append(sdict)
        if int(stage) == 3:
            from ..jit.to_static import register_step_hook
            register_step_hook(self._zero3_materialize)
        for store in self._flat_stores.values():  # superseded fused stores
            _drop(store.tensor)
        self._flat_stores = {}
        n_sharded = sum(len(sd) for sd in stores)
        prefetch_on = bool(prefetch) if prefetch is not None else True
        self._zero = {
            "axis": axis, "mesh": mesh, "stage": int(stage),
            "degree": degree, "buckets": buckets, "stores": stores,
            "slots": slots, "n_sharded": n_sharded,
            "comm_buffer_mb": float(comm_buffer_mb),
            "prefetch": prefetch_on,
        }
        if int(stage) == 3 and prefetch_on:
            # the double-buffer carry slot: bucket 0's FULL [rows, 1024]
            # flat rows, replicated per rank, riding the donated scan
            # carry (carry-optional: a program that never steps this
            # optimizer skips it). The step's tail all_gather refills it
            # for step N+1, so the first bucket's params are already
            # resident when the next forward starts — one bucket of
            # parameter bytes is the whole memory cost.
            slot_t = Tensor(jnp.zeros((buckets[0].rows, _FLAT_LANES),
                                      buckets[0].param_dtype))
            slot_t.persistable = True
            slot_t.name = "zero3_prefetch_slot"
            slot_t._ledger_category = "zero_prefetch"
            slot_t._carry_optional = True
            slot_t._mark_stateful()
            self._zero["prefetch_slot"] = slot_t
            # eager writers of the bucket-0 param store (state_dict
            # loads, user set_value) invalidate the cached gather
            stores[0]["param"].on_flush = self._zero3_prefetch_refresh
            self._zero3_prefetch_refresh()
        return n_sharded

    def _zero_state_bytes(self):
        """Per-rank bytes of the sharded optimizer-state stores (the HBM
        the ZeRO layout actually costs one chip): sum of shard sizes."""
        cfg = self._zero
        if cfg is None:
            return sum(
                int(np.prod(t._value.shape) if t._value.shape else 1)
                * t._value.dtype.itemsize
                for t in self._accumulators.values()
                if not isinstance(t, _FlatSlot)) + sum(
                int(np.prod(s.tensor._value.shape))
                * s.tensor._value.dtype.itemsize
                for s in self._flat_stores.values())
        return sum(zb.shard_rows * _FLAT_LANES
                   * np.dtype(sd.tensor._value.dtype).itemsize
                   for zb, sdict in zip(cfg["buckets"], cfg["stores"])
                   for sd in sdict.values())

    def zero_layout(self):
        """Bucket-layout metadata of the active ZeRO config, or ``None``
        when ZeRO is off — the structured description the sharding
        checker (``paddle_tpu.analysis.shardcheck``) budgets collectives
        against: one all-gather / reduce-scatter pair per bucket per
        window is a claim about exactly these buckets. Keys: ``stage``,
        ``axis``, ``degree``, ``n_buckets``, ``prefetch``,
        ``comm_buffer_mb``, ``bucket_rows`` (full flat rows per bucket),
        ``shard_rows`` (per-rank rows per bucket), ``store_names``
        (flat-store tensor names, ``zero_<slot>_b<bucket>``), and
        ``state_bytes`` (per-rank bytes, ``_zero_state_bytes``)."""
        cfg = self._zero
        if cfg is None:
            return None
        names = [sd.tensor.name for sdict in cfg["stores"]
                 for sd in sdict.values()]
        if "prefetch_slot" in cfg:
            names.append(cfg["prefetch_slot"].name)
        return {
            "stage": cfg["stage"], "axis": cfg["axis"],
            "degree": cfg["degree"], "n_buckets": len(cfg["buckets"]),
            "prefetch": cfg["prefetch"],
            "comm_buffer_mb": cfg["comm_buffer_mb"],
            "bucket_rows": [zb.rows for zb in cfg["buckets"]],
            "shard_rows": [zb.shard_rows for zb in cfg["buckets"]],
            "store_names": names,
            "state_bytes": self._zero_state_bytes(),
        }

    def _reduce_dp_grads(self, axis):
        """The replicated (non-ZeRO) control under a manual dp axis: one
        full-tensor pmean per parameter gradient — exactly the per-param
        psum the bucketed psum_scatter path replaces."""
        from ..core.selected_rows import SelectedRows
        from ..distributed import parallel_env
        bound = parallel_env.axis_bound(axis)
        for p in self._parameters():
            g = p._grad
            if g is None:
                continue
            if isinstance(g, SelectedRows):
                raise NotImplementedError(
                    "sparse (SelectedRows) gradients cannot be reduced "
                    "over a manual dp axis; use the GSPMD path")
            if g.dtype != jnp.float32:
                g = g.astype(jnp.float32)
            if bound:
                g = jax.lax.pmean(g, axis)
            p._grad = g

    def _zero3_prefetch_refresh(self):
        """Re-derive the stage-3 prefetch carry slot from the bucket-0
        param store. Eager writers go through here (enable-time init,
        checkpoint restore, out-of-band ``set_value`` via the store's
        ``on_flush``); inside a traced step the tail of ``_zero_step``
        refreshes the slot in-trace instead, so a tracer-valued store
        is left alone."""
        cfg = self._zero
        if (not cfg or cfg["stage"] != 3 or not cfg["prefetch"]
                or "prefetch_slot" not in cfg):
            return
        val = cfg["stores"][0]["param"].tensor._value
        if isinstance(val, jax.core.Tracer):
            return
        from jax.sharding import NamedSharding, PartitionSpec
        cfg["prefetch_slot"]._value = jax.device_put(
            val, NamedSharding(cfg["mesh"], PartitionSpec()))

    def _zero3_materialize(self):
        """to_static step hook (registered at stage-3 enable): arm LAZY
        just-in-time parameter materialization — the first in-trace read
        of any param in a bucket installs full-value overrides for every
        param the bucket covers, consumed by forward/backward and
        dropped when the step body ends. Laziness keeps unrelated
        programs free: a trace that never touches this model's params
        issues no gathers and never reads the stores (they stay skipped
        state instead of being threaded into someone else's compiled
        step). The gathered full parameters exist only inside the step;
        the donated carry holds 1/degree shards.

        With ``prefetch`` on (the default) the gathers are
        double-buffered instead of on-demand: bucket 0's full rows
        arrive through the warm-started prefetch carry slot (no gather
        at all — the previous step's tail already issued it), and
        materializing bucket i immediately issues bucket i+1's
        ``all_gather`` into a pending buffer, so each gather is emitted
        BEFORE the compute that consumes bucket i — the between-compute
        the latency-hiding scheduler needs. Payloads and values are
        identical to the serial schedule (``all_gather`` of the same
        shard rows), so the step stays bitwise-equal. An out-of-order
        first read (bucket j before j-1) falls back to an on-demand
        gather for that bucket."""
        from ..distributed import parallel_env
        cfg = self._zero
        if cfg is None or cfg["stage"] != 3:
            return None
        axis, degree = cfg["axis"], cfg["degree"]
        prefetch = cfg["prefetch"]
        buckets, stores = cfg["buckets"], cfg["stores"]
        pending = {}  # bucket index -> prefetched full rows (per trace)

        def full_rows(sdict):
            dp_mode = parallel_env.current_dp_axis() == axis
            bound = dp_mode and parallel_env.axis_bound(axis)
            shard = sdict["param"].tensor._value
            if bound:
                with scope("zero.gather"):
                    return jax.lax.all_gather(shard, axis, axis=0,
                                              tiled=True)
            if dp_mode:
                # abstract analysis trace: shape-only stand-in
                return jnp.concatenate([shard] * degree, axis=0)
            # GSPMD/eager: the store tracer/array is global
            return shard

        def make_gather(i, zb, sdict):
            def gather():
                dp_mode = parallel_env.current_dp_axis() == axis
                use_pf = prefetch and dp_mode
                full = pending.pop(i, None) if use_pf else None
                if full is None:
                    if use_pf and i == 0:
                        # warm start: step N-1's tail (or the eager
                        # refresh) left bucket 0 gathered on the carry
                        full = cfg["prefetch_slot"]._value
                    else:
                        full = full_rows(sdict)
                for p, seg in zip(zb.params, zb.unflatten(full)):
                    slot = p.__dict__["_zero3_slot"]
                    if (slot.out_dtype is not None
                            and seg.dtype != slot.out_dtype):
                        seg = seg.astype(slot.out_dtype)
                    p.__dict__["_zero3_ov"] = seg
                if use_pf and i + 1 < len(buckets) \
                        and (i + 1) not in pending:
                    nxt = buckets[i + 1]
                    if nxt.params[0].__dict__.get("_zero3_lazy") \
                            is not None:
                        # bucket i+1 not yet materialized: issue its
                        # gather now, while bucket i's compute runs
                        pending[i + 1] = full_rows(stores[i + 1])
            return gather

        touched = []
        for i, (zb, sdict) in enumerate(zip(buckets, stores)):
            gather = make_gather(i, zb, sdict)
            for p in zb.params:
                p.__dict__["_zero3_lazy"] = gather
                touched.append(p)

        def cleanup():
            pending.clear()
            for p in touched:
                p.__dict__.pop("_zero3_ov", None)
                p.__dict__.pop("_zero3_lazy", None)
        return cleanup

    def _zero_reduced_shard(self, zb, axis, degree, bound, dp_mode,
                            constrain=None, defer_mean=False):
        """One bucket's gradient reduction, shared by the boundary step
        and the accumulation fold (they MUST agree on these semantics):
        flatten the current per-param grads (zeros for absent) into the
        bucket layout and hand back this rank's mean-reduced float32
        [rows/degree, 1024] shard plus the per-param presence flags.

        What crosses the wire follows the gradients' dtype, read off the
        trace and nothing else. A bucket whose gradients all arrive in
        one 16-bit float type (bf16 parameters: backward produces bf16
        gradients) is flattened in that type, and each rank sends every
        other rank that rank's rows of it — ONE ``all_to_all`` of the
        narrow bucket, (degree-1)/degree of its bytes a rank on the
        links; the receiver converts the ``degree`` pieces to float32
        and adds them in rank order (:func:`_zero_sum_pieces`). The
        conversion is exact and the adds are float32, so this is the
        float32 sum of the same gradients a float32 ``psum_scatter``
        computes, to float32's order of summation, at a quarter of the
        all-reduce's wire bytes XLA:TPU lowers that reduce-scatter to;
        no partial sum is ever rounded to 16 bits. Buckets with float32
        (or mixed) gradients keep the float32 ``psum_scatter``: the
        exchange would save them no bytes against a true
        reduce-scatter.

        ``defer_mean=True`` returns the collective's RAW result instead
        (the manual-axis branches only — GSPMD grads arrive
        pre-reduced): the float32 scatter SUM, or the received narrow
        pieces ``[degree, rows/degree, 1024]``. The pipelined step runs
        :func:`_zero_sum_pieces` and the divide by ``degree`` later, so
        the collective's first consumer is not emitted adjacent to
        it."""
        from ..core.selected_rows import SelectedRows
        from ..jit.to_static import note_structure
        grads = []
        for p in zb.params:
            g = p._grad
            if isinstance(g, SelectedRows):
                raise NotImplementedError(
                    "ZeRO sharded step does not support sparse "
                    "(SelectedRows) gradients (out of scope of ISSUE 5: "
                    "ZeRO-3 parameter sharding)")
            grads.append(g)
        present = [g is not None for g in grads]
        dtypes = {jnp.dtype(g.dtype) for g in grads if g is not None}
        narrow = (dp_mode and len(dtypes) == 1
                  and next(iter(dtypes)) in _NARROW_FLOATS)
        wire = dtypes.pop() if narrow else jnp.dtype(jnp.float32)
        gfull = zb.flatten(
            [jnp.zeros(shape, wire) if g is None else g
             for g, shape in zip(grads, zb.shapes)], dtype=wire)
        if not dp_mode:
            # GSPMD/eager world: gradients are already globally reduced;
            # the constraint shards the update compute (and lets the
            # partitioner fold the grad all-reduce into a reduce-scatter
            # on backends that support it)
            return constrain(gfull), present
        if narrow:
            note_structure("zero_exchanged_buckets")
            with scope("zero.bucket_copy"):  # one relayout with flatten's
                gred = gfull.reshape(degree, zb.shard_rows, _FLAT_LANES)
            if bound:
                # piece j of the result is rank j's rows for this rank
                with scope("zero.reduce_scatter"):
                    gred = jax.lax.all_to_all(gred, axis, 0, 0)
            # (unbound: the abstract analysis trace — the local pieces
            # stand in, shape and dtype are all that matter there)
        elif bound:
            with scope("zero.reduce_scatter"):
                gred = jax.lax.psum_scatter(
                    gfull, axis, scatter_dimension=0, tiled=True)
        else:
            # abstract analysis trace: rank-0-shaped stand-in
            gred = zb.shard_of(gfull, axis, bound=False)
        if not defer_mean:
            gred = _zero_sum_pieces(gred) / degree
        return gred, present

    def _zero_accum_fold(self):
        """A non-boundary micro step of a ``to_static(accumulate_steps=a)``
        window. Stage 1 returns immediately: the full local gradients keep
        accumulating on the params through the scan carry and the single
        bucketed reduction fires at the window boundary (collective bytes
        per optimizer step drop ~a×). Stages 2/3 instead reduce the micro
        gradient now (one collective per bucket, as the boundary step
        does it: ``_zero_reduced_shard``) and fold the mean shard
        into the sharded ``gacc`` window accumulator, so no full gradient
        outlives its micro step — the DeepSpeed-style trade of per-micro
        reduction traffic for 1/degree accumulation memory."""
        from ..distributed import parallel_env
        cfg = self._zero
        if cfg["stage"] < 2:
            return
        axis, degree = cfg["axis"], cfg["degree"]
        if parallel_env.current_dp_axis() != axis:
            raise NotImplementedError(
                "ZeRO stage>=2 gradient accumulation runs inside the "
                "dp-sharded scan step (to_static(..., scan_steps=k, "
                f"dp_axis={axis!r}, accumulate_steps=a))")
        bound = parallel_env.axis_bound(axis)
        for zb, sdict in zip(cfg["buckets"], cfg["stores"]):
            gred, _present = self._zero_reduced_shard(
                zb, axis, degree, bound, dp_mode=True)
            sdict["gacc"].tensor._value = \
                sdict["gacc"].tensor._value + gred
            for p in zb.params:
                p._grad = None

    def _zero_step(self):
        """The sharded update: per bucket, reduce the flat gradient so
        that each rank keeps the float32 mean-reduced [rows/degree,
        1024] shard (``_zero_reduced_shard``: 16-bit gradients are
        exchanged in their own dtype by ``all_to_all`` and summed in
        float32 on arrival, float32 ones go through a float32
        ``psum_scatter``), clip/decay/scale it on the shard, run the
        optimizer's elementwise
        update against the sharded moment/master stores, and publish the
        refreshed parameters — stage 1/2 ``all_gather`` them back into
        every rank's full params, stage 3 writes only the local rows of
        the sharded param store (the next step's hook re-gathers).
        Elementwise math on a shard equals elementwise math on the whole,
        so losses and params match the replicated control bit-for-bit;
        the global-norm clip scale is a psum of per-shard square sums
        (summation order differs from the per-param control by design —
        parity there is tolerance-level, not bitwise)."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed import parallel_env
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue
        cfg = self._zero
        axis, degree, stage = cfg["axis"], cfg["degree"], cfg["stage"]
        mesh = cfg["mesh"]
        cur = parallel_env.current_dp_axis()
        if cur is not None and cur != axis:
            raise RuntimeError(
                f"ZeRO state is sharded over {axis!r} but the step program "
                f"binds dp axis {cur!r}")
        dp_mode = cur == axis  # manual-axis (shard_map) trace, local shapes
        bound = dp_mode and parallel_env.axis_bound(axis)
        acc = parallel_env.current_accum()
        accum_a = int(acc[1]) if acc is not None else 1
        use_gacc = stage >= 2 and acc is not None
        scaler_pending = cfg.pop("pending_scaler", False)
        pending_found = cfg.pop("pending_found", None)
        pending_inv_scale = cfg.pop("pending_inv_scale", None)
        prev_step = self._step_count._value
        self._step_count._value = prev_step + 1
        lr = self._lr.value()
        shard_spec = NamedSharding(mesh, PartitionSpec(axis, None))
        repl_spec = NamedSharding(mesh, PartitionSpec())

        def _constrain(v, spec):
            # traced: a GSPMD layout hint; eager: an actual device_put so
            # the stores stay resident in their sharded layout
            if isinstance(v, jax.core.Tracer):
                return jax.lax.with_sharding_constraint(v, spec)
            return jax.device_put(v, spec)

        def _shard_rows(arr, zb):
            """Localize a [rows, 1] numpy row-metadata array."""
            v = jnp.asarray(arr)
            return zb.shard_of(v, axis, bound) if dp_mode else v

        clip = self._grad_clip
        prefetch = cfg.get("prefetch", False)

        def _rs_bucket(zb, sdict):
            """Just the collective half of one bucket's reduction: the
            psum_scatter that produces this rank's raw reduced shard,
            or the all_to_all that delivers its 16-bit pieces. Kept
            free of any elementwise follow-up (the pieces' float32
            convert-and-add and the mean divide included, via
            ``defer_mean``) so the pipelined schedule can issue it
            early — every op that would consume the result immediately
            lives in :func:`_norm_bucket`."""
            return self._zero_reduced_shard(
                zb, axis, degree, bound, dp_mode,
                constrain=lambda v: _constrain(v, shard_spec),
                defer_mean=True)

        def _norm_bucket(sdict, gred):
            """Float32 sum of an exchanged bucket's pieces + mean divide
            + accumulation-window fold + pending-scaler/window scaling
            of one reduced shard — the elementwise tail
            of the bucket's gradient production, deferred to just
            before the update in the pipelined schedule (same
            per-bucket op order either way, so values are untouched)."""
            if dp_mode:
                # the deferred half of the scatter-mean: the narrow
                # exchange's float32 convert-and-add, then the divide
                # (the GSPMD branch returns grads already reduced,
                # nothing to do)
                gred = _zero_sum_pieces(gred) / degree
            if use_gacc:
                gacc = sdict["gacc"].tensor._value
                if not dp_mode:
                    gacc = _constrain(gacc, shard_spec)
                gred = gred + gacc
            if pending_inv_scale is not None:
                # stage-2/3 windows accumulated SCALED mean-shards; the
                # scaler deferred the whole-window unscale to this shard
                gred = gred * pending_inv_scale
            if accum_a > 1:
                gred = gred / accum_a
            return gred

        def _reduce_bucket(zb, sdict):
            """One bucket's complete gradient production (collective +
            fold/scale), emitted adjacently — the serial schedule."""
            gred, present = _rs_bucket(zb, sdict)
            return _norm_bucket(sdict, gred), present

        # A cross-bucket reduction over the reduced shards (global-norm
        # clip, or shard-derived overflow detection) is a barrier: every
        # bucket's reduction must land before any update math can
        # start, so those configs keep the two-pass schedule. Without
        # one, the reduce/update loop software-pipelines: bucket i+1's
        # reduction issues BEFORE bucket i's update math, giving the
        # scheduler real compute to hide each collective behind.
        barrier = (isinstance(clip, ClipGradByGlobalNorm)
                   or (scaler_pending and pending_found is None))

        clip_scale, all_ok, sq_sum = None, None, None
        reduced = None
        if barrier:
            reduced = [_reduce_bucket(zb, sdict)
                       for zb, sdict in zip(cfg["buckets"], cfg["stores"])]
            for gred, _present in reduced:
                if scaler_pending and pending_found is None:
                    ok = jnp.all(jnp.isfinite(gred))
                    all_ok = ok if all_ok is None else (all_ok & ok)
                if isinstance(clip, ClipGradByGlobalNorm):
                    with scope("clip"):
                        s = jnp.sum(jnp.square(gred))
                        sq_sum = s if sq_sum is None else sq_sum + s
        if sq_sum is not None:
            with scope("clip"):
                if bound:  # each rank holds 1/degree of the rows
                    sq_sum = jax.lax.psum(sq_sum, axis)
                global_norm = jnp.sqrt(sq_sum)
                clip_scale = clip.clip_norm / jnp.maximum(global_norm,
                                                          clip.clip_norm)

        found_inf = None
        if scaler_pending:
            found_inf = (pending_found if pending_found is not None
                         else ~all_ok)
            if bound:  # a shard-local inf must skip the update everywhere
                found_inf = jax.lax.psum(
                    found_inf.astype(jnp.float32), axis) > 0
            # a skipped step does not exist: bias correction must not
            # advance past it (reference SkipUpdate leaves beta-pows)
            self._step_count._value = jnp.where(found_inf, prev_step,
                                                self._step_count._value)

        # shard-local clip/decay + update of one bucket, then publish its
        # params (stage 3: write the local shard rows; stage <=2: gather)

        def _apply_bucket(zb, sdict, gred, present):
            with scope("update"):
                _update_bucket(zb, sdict, gred, present)

        def _update_bucket(zb, sdict, gred, present):
            if clip_scale is not None:
                with scope("clip"):
                    gred = gred * clip_scale
            elif isinstance(clip, ClipGradByValue):
                with scope("clip"):
                    gred = jnp.clip(gred, clip.min, clip.max)
            if stage == 3:
                pstore = sdict["param"]
                pshard = pstore.tensor._value
                if not dp_mode:
                    pshard = _constrain(pshard, shard_spec)
                if zb.has_master:
                    psrc = sdict["master"].tensor._value
                    if not dp_mode:
                        psrc = _constrain(psrc, shard_spec)
                elif pshard.dtype != jnp.float32:
                    psrc = pshard.astype(jnp.float32)
                else:
                    psrc = pshard
            elif zb.has_master:
                psrc = sdict["master"].tensor._value
                if not dp_mode:
                    psrc = _constrain(psrc, shard_spec)
            else:
                pfull = zb.flatten([p._value.astype(jnp.float32)
                                    if p._value.dtype != jnp.float32
                                    else p._value for p in zb.params])
                psrc = (zb.shard_of(pfull, axis, bound) if dp_mode
                        else _constrain(pfull, shard_spec))
            # regularizer-style decay on the shard, AFTER clipping (the
            # per-param control's order: reduce -> clip -> decay -> update)
            if zb.l2_rows is not None:
                gred = gred + _shard_rows(zb.l2_rows, zb) * psrc
            if zb.l1_rows is not None:
                gred = gred + _shard_rows(zb.l1_rows, zb) * jnp.sign(psrc)
            lr_b = lr
            if zb.lr_rows is not None:
                lr_b = lr * _shard_rows(zb.lr_rows, zb)
            dmask = None
            if getattr(self, "_decay_fn", None) is not None:
                dm = zb.row_mask([self._decay_fn(p.name)
                                  for p in zb.params]).astype(np.float32)
                dmask = jnp.asarray(dm)
                if dp_mode:
                    dmask = zb.shard_of(dmask, axis, bound)
            view = _ZeroView(psrc, f"zero_b{zb.index}", decay_mask=dmask)
            boxes = {}
            for slot in cfg["slots"]:
                boxes[slot] = _Box(sdict[slot].tensor._value
                                   if dp_mode else
                                   _constrain(sdict[slot].tensor._value,
                                              shard_spec))
                self._accumulators[(slot, id(view))] = boxes[slot]
            try:
                new_p = self._apply_one(view, gred, lr_b)
            finally:
                for slot in cfg["slots"]:
                    del self._accumulators[(slot, id(view))]
            if not all(present):
                # params without a grad this step hold still (the control
                # skips them entirely); row-granular because segments are
                # row-aligned
                keep = jnp.asarray(zb.row_mask(present))
                if dp_mode:
                    keep = zb.shard_of(keep, axis, bound)
                new_p = jnp.where(keep, new_p, psrc)
                for slot in cfg["slots"]:
                    boxes[slot]._value = jnp.where(
                        keep, boxes[slot]._value,
                        sdict[slot].tensor._value if dp_mode else
                        _constrain(sdict[slot].tensor._value, shard_spec))
            if found_inf is not None:
                # overflow skips the WHOLE update — moments and master
                # included, or one inf gradient poisons the optimizer
                # state for every later step (reference adam SkipUpdate)
                new_p = jnp.where(found_inf, psrc, new_p)
                for slot in cfg["slots"]:
                    boxes[slot]._value = jnp.where(
                        found_inf,
                        sdict[slot].tensor._value if dp_mode else
                        _constrain(sdict[slot].tensor._value, shard_spec),
                        boxes[slot]._value)
            for slot in cfg["slots"]:
                sdict[slot].tensor._value = (
                    boxes[slot]._value if dp_mode
                    else _constrain(boxes[slot]._value, shard_spec))
            if zb.has_master:
                sdict["master"].tensor._value = (
                    new_p if dp_mode else _constrain(new_p, shard_spec))
            if use_gacc:
                # the window is consumed: next window accumulates from
                # zeros (overflow steps too — the reference SkipUpdate
                # drops the window's gradients with the update)
                z = jnp.zeros_like(sdict["gacc"].tensor._value)
                sdict["gacc"].tensor._value = (
                    z if dp_mode else _constrain(z, shard_spec))
            if stage == 3:
                # no consumer-side re-gather: the refreshed rows stay
                # sharded in the param store (the next step's
                # materialize hook covers the full value) — full params
                # never re-enter the carry
                new_store = (new_p if new_p.dtype == pstore.tensor.dtype
                             else new_p.astype(pstore.tensor.dtype))
                pstore.tensor._value = (
                    new_store if dp_mode
                    else _constrain(new_store, shard_spec))
                if prefetch and zb.index == 0 \
                        and "prefetch_slot" in cfg:
                    # tail of the double buffer: gather the refreshed
                    # bucket-0 rows NOW, while the remaining buckets'
                    # update math still runs — step N+1's forward reads
                    # the slot off the carry instead of gathering.
                    # Deterministic all_gather of the same rows a fresh
                    # gather would move: bitwise-identical, one step
                    # early.
                    if bound:
                        with scope("zero.gather"):
                            nxt = jax.lax.all_gather(new_store, axis,
                                                     axis=0, tiled=True)
                    elif dp_mode:  # analysis stand-in: shape only
                        nxt = jnp.concatenate([new_store] * degree,
                                              axis=0)
                    else:
                        nxt = _constrain(new_store, repl_spec)
                    cfg["prefetch_slot"]._value = nxt
                for p in zb.params:
                    p._grad = None
            else:
                if bound:
                    with scope("zero.gather"):
                        full_new = jax.lax.all_gather(new_p, axis, axis=0,
                                                      tiled=True)
                elif dp_mode:  # analysis stand-in: shape only
                    full_new = jnp.concatenate([new_p] * degree, axis=0)
                else:
                    full_new = _constrain(new_p, repl_spec)
                for p, seg in zip(zb.params, zb.unflatten(full_new)):
                    # found_inf already gated new_p shard-side: on
                    # overflow the gathered rows reassemble the pre-step
                    # values
                    p._value = (seg.astype(p._value.dtype)
                                if seg.dtype != p._value.dtype else seg)
                    if stage >= 2 or dp_mode:
                        # stage 2: no full gradient outlives its bucket.
                        # Any stage under a manual dp axis: the un-reduced
                        # LOCAL grads must never escape the step (they are
                        # rank-divergent and would poison a replicated
                        # carry)
                        p._grad = None

        if barrier or not prefetch:
            # two-pass serial schedule: reduce every bucket, then update
            # every bucket (the pre-pipeline emission order; also the
            # ``prefetch=False`` A/B control)
            if reduced is None:
                reduced = [_reduce_bucket(zb, sdict)
                           for zb, sdict in zip(cfg["buckets"],
                                                cfg["stores"])]
            for zb, sdict, (gred, present) in zip(
                    cfg["buckets"], cfg["stores"], reduced):
                _apply_bucket(zb, sdict, gred, present)
        else:
            # double-buffered reduce/update pipeline: rs(b0), then for
            # each bucket i issue rs(b_{i+1}) BEFORE update(b_i) — the
            # reduction of the next bucket rides the update math of the
            # current one. Per-bucket dataflow is untouched (no bucket
            # reads another's shard), so the emission reorder cannot
            # change a single value.
            items = list(zip(cfg["buckets"], cfg["stores"]))
            nxt = _rs_bucket(*items[0])
            for i, (zb, sdict) in enumerate(items):
                gred, present = nxt
                nxt = (_rs_bucket(*items[i + 1])
                       if i + 1 < len(items) else None)
                _apply_bucket(zb, sdict, _norm_bucket(sdict, gred),
                              present)
        if scaler_pending:
            cfg["last_found_inf"] = found_inf

    def step(self):
        """One update. In a compiled step its device time goes under the
        scope `optimizer`: beneath it `update` (the elementwise rule and
        the master-to-parameter cast), `clip`, and for ZeRO
        `zero.reduce_scatter` (the reduction's collective, `psum_scatter`
        or `all_to_all`), `zero.gather`, `zero.bucket_copy`."""
        with scope("optimizer"):
            return self._step()

    def _step(self):
        from ..distributed import parallel_env
        acc = parallel_env.current_accum()
        if self._zero is not None:
            if acc is not None and acc[0] == "accum":
                return self._zero_accum_fold()
            return self._zero_step()
        if acc is not None and acc[0] == "accum":
            # non-boundary micro step of an accumulation window: backward
            # keeps summing into p._grad through the scan carry; the
            # update fires once at the window boundary
            return
        dp_axis = parallel_env.current_dp_axis()
        if dp_axis is not None:
            self._reduce_dp_grads(dp_axis)
        from ..core.selected_rows import SelectedRows
        params_grads = [(p, p._grad) for p in self._parameters()
                        if not p.stop_gradient and p._grad is not None]
        if acc is not None and acc[1] > 1:
            # window boundary: the carried gradients are sums of a
            # micro-batch means — scale to the big-batch mean BEFORE
            # clipping (same order as the sharded path)
            a = acc[1]
            params_grads = [
                (p, SelectedRows(g.rows, g.values / a, g.height)
                 if isinstance(g, SelectedRows) else g / a)
                for p, g in params_grads]
        if self._grad_clip is not None:
            # sparse grads participate: they contribute their row values to
            # the global norm and get scaled as SelectedRows
            with scope("clip"):
                params_grads = self._grad_clip(params_grads)
        dense = [(p, g) for p, g in params_grads
                 if not isinstance(g, SelectedRows)]
        sparse = [(p, g) for p, g in params_grads
                  if isinstance(g, SelectedRows)]
        self._step_count._value = self._step_count._value + 1
        lr = self._lr.value()
        with scope("update"):
            self._update(dense, sparse, lr)

    def _update(self, dense, sparse, lr):
        for p, g in dense:
            if g is None:
                continue
            if g.dtype in (jnp.bfloat16, jnp.float16):
                g = g.astype(jnp.float32)
            plr = lr * p.__dict__.get("optimize_attr", {}).get("learning_rate", 1.0)
            master = self._maybe_master(p)
            if master is not None:
                # run the update math on the fp32 master; the bf16 param
                # only receives the cast result
                saved_dtype = p._value.dtype
                p._value = master._value
                new_val = self._apply_one(p, g, plr)
                master._value = new_val
                p._value = new_val.astype(saved_dtype)
            else:
                new_val = self._apply_one(p, g, plr)
                p._value = new_val.astype(p._value.dtype)
        for store in self._flat_stores.values():
            store.flush()
        for p, g in sparse:
            plr = lr * p.__dict__.get("optimize_attr", {}).get("learning_rate", 1.0)
            master = self._maybe_master(p)
            if master is not None:
                # sparse rows update the fp32 master too, or the next
                # dense step would reset the param from a stale master
                saved_dtype = p._value.dtype
                p._value = master._value
                self._apply_sparse(p, g, plr)
                master._value = p._value
                p._value = master._value.astype(saved_dtype)
            else:
                self._apply_sparse(p, g, plr)
        for store in self._flat_stores.values():
            store.flush()

    def _apply_sparse(self, p, sr, lr):
        """Row-wise update for a SelectedRows grad (reference: the sparse
        branches of sgd_op.h / adam_op.h lazy_mode). Default: run the dense
        update formula on the gathered rows only, scatter back — touched
        rows see exactly the dense math; untouched rows (and their
        accumulators) are untouched, which is lazy_mode semantics."""
        rows, vals = sr.rows, sr.values.astype(jnp.float32)
        valid = rows < sr.height
        safe_rows = jnp.where(valid, rows, 0)  # gather side: clamped reads
        # scatter side: invalid (merge_add padding) entries must be DROPPED,
        # not redirected — a clamped index would overwrite row 0's real
        # update with the stale gathered value
        scatter_rows = jnp.where(valid, rows, sr.height)

        class _RowView:
            """Stands in for the param/accumulator during _apply_one."""
            pass

        full = p._value
        gathered = full[safe_rows].astype(jnp.float32)
        view = _RowView()
        view._value = gathered
        view.__dict__["optimize_attr"] = p.__dict__.get("optimize_attr", {})
        view.regularizer = getattr(p, "regularizer", None)
        view.name = p.name
        # accumulator row views, scattered back after the update
        acc_keys = [k for k in self._accumulators if k[1] == id(p)]
        saved = {}
        for k in acc_keys:
            acc = self._accumulators[k]
            saved[k] = acc._value
            row_acc = Tensor(acc._value[safe_rows])
            self._accumulators[(k[0], id(view))] = row_acc
        try:
            new_rows = self._apply_one(view, vals, lr)
            p._value = full.at[scatter_rows].set(
                new_rows.astype(full.dtype), mode="drop")
            for k in acc_keys:
                row_acc = self._accumulators.pop((k[0], id(view)))
                acc = self._accumulators[k]
                acc._value = saved[k].at[scatter_rows].set(
                    row_acc._value.astype(saved[k].dtype), mode="drop")
        finally:
            for k in list(self._accumulators):
                if k[1] == id(view):
                    del self._accumulators[k]

    minimize_step = step

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..core.dispatch import _STATIC_HOOK
        if _STATIC_HOOK[0] is not None:
            if self._fuse_acc:
                raise NotImplementedError(
                    "fuse_accumulators=True is a dygraph/to_static feature; "
                    "the static Program executor threads per-param "
                    "accumulator tensors and cannot use coalesced views")
            from ..static import program as prog_mod
            prog = prog_mod.default_main_program()
            # adopt the program's trainable parameters
            from ..core.tensor import Parameter as _Param
            train_params = [p for p in prog.params.values()
                            if isinstance(p, _Param) and not p.stop_gradient]
            known = {id(p) for p in self._parameters()}
            fresh = [p for p in train_params if id(p) not in known]
            if fresh:
                self._param_groups.append({"params": fresh})
                for p in fresh:
                    self._create_accumulators(p)
            prog._optimizer = self
            prog._loss_slot = prog._slot_of(loss, create=False)
            return None, None
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    def _apply_one(self, p, g, lr):
        raise NotImplementedError

    def state_dict(self):
        out = {}
        for (slot, pid), t in self._accumulators.items():
            if isinstance(t, _FlatSlot):
                t = Tensor(t._value)  # materialized copy of the flat view
            # keyed by param name for portability
            for p in self._parameters():
                if id(p) == pid:
                    out[f"{p.name}.{slot}"] = t
                    break
        out["@step"] = self._step_count
        out["@lr"] = self._lr.tensor
        if self._lr.scheduler is not None:
            out["LR_Scheduler"] = self._lr.scheduler.state_dict()
        return out

    def set_state_dict(self, state):
        name_to_key = {}
        for (slot, pid), t in self._accumulators.items():
            for p in self._parameters():
                if id(p) == pid:
                    name_to_key[f"{p.name}.{slot}"] = (slot, pid)
        for k, v in state.items():
            if k == "@step":
                self._step_count.set_value(v.numpy() if hasattr(v, "numpy") else v)
            elif k == "@lr":
                self._lr.set(v.numpy() if hasattr(v, "numpy") else v)
            elif k == "LR_Scheduler" and self._lr.scheduler is not None:
                self._lr.scheduler.set_state_dict(v)
            elif k in name_to_key:
                t = self._accumulators[name_to_key[k]]
                t.set_value(v.numpy() if hasattr(v, "numpy") else v)


class SGD(Optimizer):
    """reference: operators/optimizers/sgd_op.cc"""

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        return p._value - lr * g


class Momentum(Optimizer):
    """reference: operators/optimizers/momentum_op.h"""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        self._momentum = momentum
        self._nesterov = use_nesterov
        self._multi_precision = multi_precision
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("velocity", param)
        self._maybe_master(param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        v = self._get_accumulator("velocity", p)
        new_v = self._momentum * v._value + g
        v._value = new_v
        if self._nesterov:
            return p._value - lr * (g + self._momentum * new_v)
        return p._value - lr * new_v


class Adam(Optimizer):
    """reference: operators/optimizers/adam_op.h (beta-power accumulators and
    all) — the pow-correction is folded analytically instead of stored."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None, fuse_accumulators=False):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._multi_precision = multi_precision
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         fuse_accumulators=fuse_accumulators)

    def _create_accumulators(self, param):
        self._add_accumulator("moment1", param)
        self._add_accumulator("moment2", param)
        self._maybe_master(param)

    def _bias_corrected_lr(self, lr):
        t = self._step_count._value.astype(jnp.float32)
        return lr * jnp.sqrt(1.0 - self._beta2 ** t) / (1.0 - self._beta1 ** t)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        new_m = self._beta1 * m._value + (1 - self._beta1) * g
        new_v = self._beta2 * v._value + (1 - self._beta2) * jnp.square(g)
        m._value, v._value = new_m, new_v
        lr_t = self._bias_corrected_lr(lr)
        return p._value - lr_t * new_m / (jnp.sqrt(new_v) + self._eps)


class AdamW(Adam):
    """reference: python/paddle/optimizer/adamw.py — decoupled weight decay."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 grad_clip=None, apply_decay_param_fun=None,
                 multi_precision=False, lazy_mode=False, name=None,
                 fuse_accumulators=False):
        self._coeff = (weight_decay if isinstance(weight_decay, float)
                       else getattr(weight_decay, "coeff", 0.01))
        self._decay_fn = apply_decay_param_fun
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, multi_precision=multi_precision,
                         fuse_accumulators=fuse_accumulators)

    def _apply_one(self, p, g, lr):
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        new_m = self._beta1 * m._value + (1 - self._beta1) * g
        new_v = self._beta2 * v._value + (1 - self._beta2) * jnp.square(g)
        m._value, v._value = new_m, new_v
        lr_t = self._bias_corrected_lr(lr)
        out = p._value - lr_t * new_m / (jnp.sqrt(new_v) + self._eps)
        mask = getattr(p, "_zero_decay_mask", None)
        if mask is not None:
            # flat ZeRO shard: apply_decay_param_fun becomes a per-row
            # 0/1 mask (segments are row-aligned); x*1.0 and x-0.0 are
            # exact, so this matches the per-param branch bit-for-bit
            return out - lr * self._coeff * (mask * p._value)
        if self._decay_fn is None or self._decay_fn(p.name):
            out = out - lr * self._coeff * p._value
        return out


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 name=None):
        self._eps = epsilon
        self._init_acc = initial_accumulator_value
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("moment", param, fill=self._init_acc)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        acc = self._get_accumulator("moment", p)
        new_acc = acc._value + jnp.square(g)
        acc._value = new_acc
        return p._value - lr * g / (jnp.sqrt(new_acc) + self._eps)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("mean_square", param)
        self._add_accumulator("momentum", param)
        if self._centered:
            self._add_accumulator("mean_grad", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        ms = self._get_accumulator("mean_square", p)
        mom = self._get_accumulator("momentum", p)
        new_ms = self._rho * ms._value + (1 - self._rho) * jnp.square(g)
        ms._value = new_ms
        denom = new_ms
        if self._centered:
            mg = self._get_accumulator("mean_grad", p)
            new_mg = self._rho * mg._value + (1 - self._rho) * g
            mg._value = new_mg
            denom = new_ms - jnp.square(new_mg)
        new_mom = (self._momentum * mom._value
                   + lr * g / jnp.sqrt(denom + self._eps))
        mom._value = new_mom
        return p._value - new_mom


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        self._rho, self._eps = rho, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("avg_squared_grad", param)
        self._add_accumulator("avg_squared_update", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        asg = self._get_accumulator("avg_squared_grad", p)
        asu = self._get_accumulator("avg_squared_update", p)
        new_asg = self._rho * asg._value + (1 - self._rho) * jnp.square(g)
        update = (jnp.sqrt(asu._value + self._eps)
                  / jnp.sqrt(new_asg + self._eps)) * g
        new_asu = self._rho * asu._value + (1 - self._rho) * jnp.square(update)
        asg._value, asu._value = new_asg, new_asu
        return p._value - lr * update


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("moment", param)
        self._add_accumulator("inf_norm", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        m = self._get_accumulator("moment", p)
        u = self._get_accumulator("inf_norm", p)
        new_m = self._beta1 * m._value + (1 - self._beta1) * g
        new_u = jnp.maximum(self._beta2 * u._value, jnp.abs(g))
        m._value, u._value = new_m, new_u
        t = self._step_count._value.astype(jnp.float32)
        lr_t = lr / (1.0 - self._beta1 ** t)
        return p._value - lr_t * new_m / (new_u + self._eps)


class Lamb(Optimizer):
    """reference: operators/optimizers/lamb_op.h + fleet lamb_optimizer.py."""

    _zero_compatible = False  # per-param trust ratio needs whole-tensor norms

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn
        super().__init__(learning_rate, parameters, None, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("moment1", param)
        self._add_accumulator("moment2", param)

    def _apply_one(self, p, g, lr):
        m = self._get_accumulator("moment1", p)
        v = self._get_accumulator("moment2", p)
        new_m = self._beta1 * m._value + (1 - self._beta1) * g
        new_v = self._beta2 * v._value + (1 - self._beta2) * jnp.square(g)
        m._value, v._value = new_m, new_v
        t = self._step_count._value.astype(jnp.float32)
        m_hat = new_m / (1.0 - self._beta1 ** t)
        v_hat = new_v / (1.0 - self._beta2 ** t)
        r = m_hat / (jnp.sqrt(v_hat) + self._eps)
        if self._exclude_fn is None or not self._exclude_fn(p):
            r = r + self._lamb_wd * p._value
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p._value)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return p._value - lr * ratio * r


class Lars(Momentum):
    """LARS (reference: operators/optimizers/lars_momentum_op.cc)."""

    _zero_compatible = False  # local-lr needs whole-tensor norms

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 exclude_from_weight_decay=None, multi_precision=False,
                 name=None):
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        super().__init__(learning_rate, momentum, parameters, False, None,
                         grad_clip, multi_precision=multi_precision)

    def _apply_one(self, p, g, lr):
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p._value)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._lars_coeff * w_norm
            / (g_norm + self._lars_wd * w_norm + 1e-12), 1.0)
        v = self._get_accumulator("velocity", p)
        new_v = self._momentum * v._value + lr * local_lr * (
            g + self._lars_wd * p._value)
        v._value = new_v
        return p._value - new_v


class DecayedAdagrad(Optimizer):
    """reference: operators/optimizers/decayed_adagrad_op.h:
    acc = decay*acc + (1-decay)*g²; p -= lr * g / (sqrt(acc) + eps)."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        self._decay, self._eps = decay, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("moment", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        acc = self._get_accumulator("moment", p)
        new_acc = self._decay * acc._value + (1 - self._decay) * \
            jnp.square(g)
        acc._value = new_acc
        return p._value - lr * g / (jnp.sqrt(new_acc) + self._eps)


class ProximalGD(Optimizer):
    """reference: operators/optimizers/proximal_gd_op.h — gradient step
    followed by the l1/l2 proximal operator."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        self._l1, self._l2 = l1, l2
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _prox(self, prox, step_lr):
        return (jnp.sign(prox)
                * jnp.maximum(jnp.abs(prox) - step_lr * self._l1, 0.0)
                / (1.0 + step_lr * self._l2))

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        return self._prox(p._value - lr * g, lr)


class ProximalAdagrad(ProximalGD):
    """reference: operators/optimizers/proximal_adagrad_op.h — the
    proximal step with an adagrad-scaled learning rate."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, epsilon=1e-10,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        self._eps = epsilon
        super().__init__(learning_rate, l1, l2, parameters, weight_decay,
                         grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("moment", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        acc = self._get_accumulator("moment", p)
        new_acc = acc._value + jnp.square(g)
        acc._value = new_acc
        lr_t = lr / (jnp.sqrt(new_acc) + self._eps)
        return self._prox(p._value - lr_t * g, lr_t)


class Ftrl(Optimizer):
    """reference: operators/optimizers/ftrl_op.h (lr_power branch
    folded: the general-power update with the -0.5 shortcut's math)."""

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        self._l1, self._l2, self._lr_power = l1, l2, lr_power
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _create_accumulators(self, param):
        self._add_accumulator("squared", param)
        self._add_accumulator("linear", param)

    def _apply_one(self, p, g, lr):
        g = self._decayed_grad(p, g)
        sq = self._get_accumulator("squared", p)
        lin = self._get_accumulator("linear", p)
        new_sq = sq._value + jnp.square(g)
        pw = -self._lr_power
        sigma = (new_sq ** pw - sq._value ** pw) / lr
        new_lin = lin._value + g - sigma * p._value
        sq._value, lin._value = new_sq, new_lin
        x = self._l1 * jnp.sign(new_lin) - new_lin
        y = new_sq ** pw / lr + 2.0 * self._l2
        return jnp.where(jnp.abs(new_lin) > self._l1, x / y, 0.0)


class Dpsgd(Optimizer):
    """reference: operators/optimizers/dpsgd_op.h — differentially
    private SGD: per-step l2 clip to `clip`, gaussian noise of scale
    sigma/batch_size, then the sgd step. Noise draws ride the global
    functional RNG, so runs are reproducible under paddle.seed."""

    _zero_compatible = False  # per-param clip norm + RNG draws

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, parameters=None, grad_clip=None, name=None):
        self._clip, self._bs, self._sigma = clip, batch_size, sigma
        super().__init__(learning_rate, parameters, None, grad_clip)

    def _apply_one(self, p, g, lr):
        import jax

        from ..core import random as core_random
        norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        scale = jnp.minimum(1.0, self._clip / (norm + 1e-12))
        noise = jax.random.normal(core_random.next_key(), g.shape,
                                  jnp.float32) * (self._sigma / self._bs)
        return p._value - lr * (g * scale + noise)
