"""GPT-style decoder (GPT-3 1.3B, Fleet sharding + PP).

TPU-first: causal flash attention, GSPMD mp sharding on qkv/ffn, ZeRO via
optimizer-state specs, and a PipelineLayer description for pp segmentation.
"""
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import nn, ops
from ..nn import functional as F
from ..observability.scopes import scope
from .decoder import DecoderBlock, DecoderConfig


class GPTConfig(DecoderConfig):
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_seq_len=1024,
                 hidden_dropout=0.1, attention_dropout=0.1, use_mp=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.use_mp = use_mp


def gpt3_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)


def gpt_small(**kw):
    return GPTConfig(**kw)


# the one decoder block (models/decoder.py) as GPT-2/3 configures it:
# pre-LN, learned positions, a fused biased qkv, a GELU FFN
GPTBlock = DecoderBlock


class GPTModel(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or GPTConfig(**kwargs)
        self.config = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.hidden_dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        if cfg.use_mp:
            self.wte.weight.pspec = P("mp", None)

    def forward(self, input_ids):
        s = input_ids.shape[1]
        pos = ops.arange(s, dtype="int32")
        x = self.drop(self.wte(input_ids) + self.wpe(pos))
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg=None, **kwargs):
        super().__init__()
        cfg = cfg or GPTConfig(**kwargs)
        self.config = cfg
        self.gpt = GPTModel(cfg)

    def forward(self, input_ids):
        hidden = self.gpt(input_ids)
        # weight-tied LM head
        with scope("head"):
            return ops.matmul(hidden, self.gpt.wte.weight, transpose_y=True)

    def loss(self, logits, labels):
        b, s, v = logits.shape
        with scope("loss"):
            return F.cross_entropy(ops.reshape(logits[:, :-1], [-1, v]),
                                   ops.reshape(labels[:, 1:], [-1]))

    def flops_per_token(self, seq_len=None):
        cfg = self.config
        n = sum(p.size for p in self.parameters())
        s = seq_len or cfg.max_seq_len
        return 6 * n + 12 * cfg.num_layers * cfg.hidden_size * s


def build_pipeline_layer(cfg, num_stages, loss_fn=None):
    """GPT as a reference-style PipelineLayer (LayerDesc segmentation)."""
    from ..distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    class _EmbedStage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
            self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)

        def forward(self, input_ids):
            s = input_ids.shape[1]
            pos = ops.arange(s, dtype="int32")
            return self.wte(input_ids) + self.wpe(pos)

    class _HeadStage(nn.Layer):
        def __init__(self):
            super().__init__()
            self.ln_f = nn.LayerNorm(cfg.hidden_size)
            self.head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False)

        def forward(self, x):
            return self.head(self.ln_f(x))

    descs = ([LayerDesc(_EmbedStage)]
             + [LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)]
             + [LayerDesc(_HeadStage)])
    return PipelineLayer(descs, num_stages=num_stages, loss_fn=loss_fn)


def build_gpt_1f1b_step(model, mesh, axis_pp="pp", axis_dp=None):
    """Fused dp x pp 1F1B training step over the REAL model's parameters
    (the reference's PipelineOptimizer + sharding
    hybrid, as one XLA program via parallel.spmd_pipeline_1f1b).

    The per-stage computation reuses GPTBlock.forward itself: block
    parameters stack [pp, layers_per_stage, ...] (sharded over 'pp'), and a
    template block re-runs with its values bound to the traced slices, so
    the pipelined math IS the model's math. Embedding (wte+wpe) runs on
    stage 0, final-LN + tied LM head + shifted CE on the last stage.

    Returns (step, params) where step(ids [M,mb,T], labels [M,mb,T]) ->
    (loss, (stage_grads, first_grads, last_grads)) and params is the
    matching (stacked, first, last) value pytree. Tied wte grads =
    first_grads[0] + last_grads[2].
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..core import autograd as _ag
    from ..core.dispatch import bind_values, unwrap
    from ..core.tensor import Tensor
    from ..parallel import spmd_pipeline_1f1b

    cfg = model.config
    # train-mode dropout: per-microbatch threefry keys thread through the
    # pipeline so the recompute-based backward replays the forward's masks
    # exactly (reference: fleet/utils/recompute.py:63 RNG-state replay)
    use_rng = model.training and (cfg.hidden_dropout > 0
                                  or cfg.attention_dropout > 0)
    pp = mesh.shape[axis_pp]
    L = cfg.num_layers
    if L % pp != 0:
        raise ValueError(f"num_layers {L} must divide by pp {pp}")
    per = L // pp
    template = model.gpt.blocks[0]
    leaf_names = sorted(template.state_dict().keys())
    leaf_tensors = [template.state_dict()[k] for k in leaf_names]

    def _block_leaves(blk):
        sd = blk.state_dict()
        return [unwrap(sd[k]) for k in leaf_names]

    def snapshot_params():
        """Re-read the model's CURRENT parameter values (call after each
        optimizer update and pass the result to step — jnp arrays are
        immutable, so the build-time snapshot never tracks the model)."""
        stacked = tuple(
            jnp.stack([jnp.stack(
                [_block_leaves(model.gpt.blocks[s * per + i])[j]
                 for i in range(per)]) for s in range(pp)])
            for j in range(len(leaf_names)))
        first = (unwrap(model.gpt.wte.weight), unwrap(model.gpt.wpe.weight))
        last = (unwrap(model.gpt.ln_f.weight), unwrap(model.gpt.ln_f.bias),
                unwrap(model.gpt.wte.weight))  # tied head
        return stacked, first, last

    stacked, first_params, last_params = snapshot_params()

    from ..core import random as core_random

    def stage_fn(params, x, key=None):
        def body(h, xs):
            if key is None:
                leaves = xs
                with bind_values(leaf_tensors, list(leaves)), _ag.no_grad():
                    out = template(Tensor(h))
            else:
                leaves, idx = xs[:-1], xs[-1]
                # distinct key per layer position: masks must not repeat
                # across the stage's layers (the scan body traces once)
                with core_random.scoped_key(jax.random.fold_in(key, idx)), \
                        bind_values(leaf_tensors, list(leaves)), \
                        _ag.no_grad():
                    out = template(Tensor(h))
            return unwrap(out), None

        xs = params if key is None else tuple(params) + (
            jnp.arange(per, dtype=jnp.int32),)
        h, _ = lax.scan(body, x, xs)
        return h

    def first_fn(fp, ids, key=None):
        wte, wpe = fp
        emb = wte[ids] + wpe[jnp.arange(ids.shape[-1])]
        if key is not None and cfg.hidden_dropout > 0:
            # the model's post-embedding dropout (model.gpt.drop) replayed
            # through the ONE dropout implementation via a scoped key
            from ..nn import functional as F
            with core_random.scoped_key(jax.random.fold_in(key, 997)), \
                    _ag.no_grad():
                emb = unwrap(F.dropout(Tensor(emb), p=cfg.hidden_dropout,
                                       training=True))
        return emb

    # the head/loss re-runs the model's own code (ln_f + tied matmul +
    # GPTForCausalLM.loss) with values bound, so the pipelined path cannot
    # drift from the eager semantics (epsilon, label shift, ...)
    head_tensors = [model.gpt.ln_f.weight, model.gpt.ln_f.bias,
                    model.gpt.wte.weight]

    def last_fn(lp, h, labels, key=None):
        with bind_values(head_tensors, list(lp)), _ag.no_grad():
            norm = model.gpt.ln_f(Tensor(h))
            from .. import ops as _ops
            logits = _ops.matmul(norm, model.gpt.wte.weight,
                                 transpose_y=True)
            loss = model.loss(logits, Tensor(labels))
            return unwrap(loss)

    def inner(sp, fp, lp, ids, labels, rng_keys=None):
        if rng_keys is not None and axis_dp is not None:
            # decorrelate dropout across data-parallel replicas: each dp
            # rank processes different samples and must draw different
            # masks (reference: per-data-rank seed offsets)
            di = jax.lax.axis_index(axis_dp)
            rng_keys = jax.vmap(lambda kd: jax.random.key_data(
                jax.random.fold_in(jax.random.wrap_key_data(kd), di)))(
                    rng_keys)
        loss, gP, gF, gL = spmd_pipeline_1f1b(
            stage_fn, last_fn, sp, lp, ids, labels,
            first_fn=first_fn, first_params=fp, axis_name=axis_pp,
            rng_keys=rng_keys)
        if axis_dp is not None:
            loss = jax.lax.pmean(loss, axis_dp)
            gP = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis_dp), gP)
            gF = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis_dp), gF)
            gL = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, axis_dp), gL)
        return loss, (gP, gF, gL)

    batch_spec = P(None, axis_dp) if axis_dp is not None else P(None)
    pp_tree = jax.tree_util.tree_map(lambda _: P(axis_pp), stacked)
    rep = jax.tree_util.tree_map(lambda _: P(), first_params)
    rep_l = jax.tree_util.tree_map(lambda _: P(), last_params)
    in_specs = (pp_tree, rep, rep_l, batch_spec, batch_spec) + (
        (P(None),) if use_rng else ())
    step = jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs,
        out_specs=(P(), (pp_tree, rep, rep_l))))

    def run(ids_micro, labels_micro, params=None, rng_key=None):
        """params: (stacked, first, last) from run.snapshot_params(); the
        build-time snapshot is used when omitted (fine for a single step or
        eval, NOT for a training loop — snapshot after each update).
        In train mode with dropout, per-microbatch keys are split from
        `rng_key` (or the framework generator when omitted)."""
        sp, fp, lp = params if params is not None else (
            stacked, first_params, last_params)
        if not use_rng:
            return step(sp, fp, lp, ids_micro, labels_micro)
        base = rng_key if rng_key is not None else core_random.next_key()
        keys = jax.random.key_data(
            jax.random.split(base, ids_micro.shape[0]))
        return step(sp, fp, lp, ids_micro, labels_micro, keys)

    run.snapshot_params = snapshot_params
    return run, (stacked, first_params, last_params, leaf_names)


def synthetic_lm_batch(batch_size, seq_len, vocab_size=50304, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab_size, (batch_size, seq_len)).astype("int32")
    return ids
