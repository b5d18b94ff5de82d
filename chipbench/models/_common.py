"""What the model families share: seeded weights from a table of shapes,
seeded integer batches, and the user's training step built through the
program's public API (the body of `chip_smoke.build_bert_step`)."""
import numpy as np

# a stacked key whose last axis holds several leaves side by side: the
# fused q/k/v projection is three leaves for the comparison, because the
# key's bias has no gradient under softmax and the other two have
LEAF_SPLITS = {"qkv_w": 3, "qkv_b": 3}


def seed_words(seed):
    """`--seed` may exceed 32 signed bits: two uint32 words of it."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF]


def init_weights(shapes, init_std, seed, dtype, out_dtype=None,
                 shardings=None):
    """Every weight of `shapes` ({key: (shape, kind)}) in one jitted
    call on the device: `normal` is N(0, init_std), `ones`/`zeros` are
    constants. The draw is made in float32 and rounded to `dtype` once,
    so the program and the reference start from the same numbers; the
    reference asks for them back in `out_dtype` float32, and on several
    chips placed by `shardings` ({key: sharding}). That conversion is a
    call of its own on the stored `dtype` arrays: inside one program the
    TPU's compiler drops a float32 -> bfloat16 -> float32 round trip,
    and the reference then started from weights the program never had
    (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    def make(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        out = {}
        for i, name in enumerate(names):
            shape, kind = shapes[name]
            if kind == "normal":
                w = init_std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                w = jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                             jnp.float32)
            out[name] = w.astype(dtype)
        return out

    weights = jax.jit(make, out_shardings=shardings)(
        jnp.asarray(seed_words(seed), jnp.uint32))
    if out_dtype is None or jnp.dtype(out_dtype) == jnp.dtype(dtype):
        return weights
    return jax.jit(
        lambda tree: {k: v.astype(out_dtype) for k, v in tree.items()},
        out_shardings=shardings)(weights)


def batch_rng(seed, step_index):
    return np.random.default_rng([int(seed), int(step_index), 0xC41B])


def stack_steps(make_batch, cfg, cell, seed, first_step, k):
    """`k` consecutive steps' batches, each array stacked [k, ...]."""
    per_step = [make_batch(cfg, cell, seed, first_step + i)
                for i in range(k)]
    return tuple(np.stack(arrs) for arrs in zip(*per_step))


def set_program_weights(model, names, weights):
    """Write the seeded weights through the public parameter API.
    `names` maps each parameter's structured name to (key, layer)."""
    seen = set()
    for pname, param in model.named_parameters():
        key, layer = names[pname]
        value = weights[key] if layer is None else weights[key][layer]
        param.set_value(value)
        seen.add(pname)
    missing = set(names) - seen
    if missing:
        raise RuntimeError(f"the model has no parameter {sorted(missing)}")


def build_train_step(model, forward_loss, training, cell):
    """The step a user's loop calls: bf16 autocast forward and loss,
    backward, `optimization_barrier` over the gradients, AdamW with fp32
    masters, `clear_grad`, compiled by `paddle.jit.to_static(one_step,
    scan_steps=k[, dp_axis])`. Returns (step, optimizer)."""
    import jax.lax as lax

    import paddle_tpu as paddle

    if training["optimizer"] != "adamw":
        raise ValueError(f"unknown optimizer {training['optimizer']!r}")
    opt = paddle.optimizer.AdamW(
        parameters=model.parameters(),
        learning_rate=training["learning_rate"], beta1=training["beta1"],
        beta2=training["beta2"], epsilon=training["epsilon"],
        weight_decay=training["weight_decay"], multi_precision=True)
    dp_axis = cell.get("dp_axis")
    if cell.get("zero_stage"):
        opt._zero_enable(axis=dp_axis, stage=cell["zero_stage"])
    params = list(model.parameters())

    def one_step(*batch):
        with paddle.amp.auto_cast(enable=True,
                                  dtype=training["compute_dtype"]):
            loss = forward_loss(*batch)
        loss.backward()
        withg = [p for p in params if p._grad is not None]
        barred = lax.optimization_barrier(tuple(p._grad for p in withg))
        for p, v in zip(withg, barred):
            p._grad = v
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(one_step, scan_steps=cell["k"],
                                dp_axis=dp_axis)
    return step, opt
