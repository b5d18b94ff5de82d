"""The comparison that decides `correct` for a training cell.

The timed step's first call is held against the plain reference (see
PERF.md section 2, "How correct is decided"). Three kinds of number:

  loss    each followed step's loss: |program - reference| / |reference|,
          the worst step;
  moment  the norm of AdamW's first moment after the call — the
          gradients as the optimizer got them, 0.1 * (0.9 g1 + g2) for a
          call of two steps — leaf by leaf;
  change  the norm of each leaf's change over the call (fp32 masters
          against the seeded start), leaf by leaf.

`moment` and `change` are taken by the worst leaf: the gap between the
program's norm and the reference's (not the norm of a difference),
against the reference's norm of that leaf or of the median leaf,
whichever is larger. A leaf whose reference gradient is under a
thousandth of the median leaf's (a key's bias under softmax) moves
under Adam by round-off alone and is left out of `change`.
"""
import math

import numpy as np

NUMBERS = ("loss", "moment", "change")
DEAD_GRADIENT = 1e-3  # of the median leaf's gradient norm


def leaf_norms(tree, stacked, splits):
    """{key: L2 norms}: a key in `stacked` ([layers, ...]) gives one
    norm a layer, a key in `splits` one for each part of its last axis.
    jax arrays in, jax arrays out (jittable)."""
    import jax.numpy as jnp

    out = {}
    for key, x in tree.items():
        sq = jnp.square(x.astype(jnp.float32))
        parts = splits.get(key, 1)
        sq = sq.reshape(sq.shape[:-1] + (parts, sq.shape[-1] // parts))
        keep = ({0} if key in stacked else set()) | {sq.ndim - 2}
        sq = jnp.sum(sq, axis=tuple(a for a in range(sq.ndim)
                                    if a not in keep))
        out[key] = jnp.sqrt(sq)
    return out


def flatten(norms):
    """{key: array} -> ({leaf name: float}) in a fixed order."""
    flat = {}
    for key in sorted(norms):
        arr = np.asarray(norms[key], np.float64)
        for idx in np.ndindex(arr.shape):
            flat[key + "".join(f"[{i}]" for i in idx)] = float(arr[idx])
    return flat


def leaf_gaps(program, reference, leave_out=()):
    """[(gap, leaf)], widest first: each leaf's |program - reference|
    norm gap against max(reference's norm, median reference norm)."""
    names = [n for n in reference if n not in leave_out]
    if not names:
        raise ValueError("no leaf left to compare")
    median = float(np.median([reference[n] for n in names]))
    gaps = [(abs(program[n] - reference[n]) / max(reference[n], median)
             if math.isfinite(program[n]) else math.inf, n) for n in names]
    return sorted(gaps, key=lambda g: -g[0])


def dead_leaves(reference_grad):
    median = float(np.median(list(reference_grad.values())))
    return {n for n, g in reference_grad.items()
            if g < DEAD_GRADIENT * median}


def compare(program, reference):
    """`program` and `reference`: {"losses": [...], "moment": {leaf:
    norm}, "change": {...}}, the reference also "grad". Returns
    {number: {"value", "at"[, "next": the three next-widest leaves]}}."""
    ref_losses = reference["losses"]
    got_losses = program["losses"][:len(ref_losses)]
    if len(got_losses) != len(ref_losses):
        raise ValueError("fewer program losses than reference steps")
    loss_gaps = [abs(g - r) / abs(r) if math.isfinite(g) else math.inf
                 for g, r in zip(got_losses, ref_losses)]
    step = int(np.argmax(loss_gaps))
    moment = leaf_gaps(program["moment"], reference["moment"])
    change = leaf_gaps(program["change"], reference["change"],
                       leave_out=dead_leaves(reference["grad"]))
    return {"loss": {"value": loss_gaps[step], "at": f"step{step + 1}"},
            "moment": {"value": moment[0][0], "at": moment[0][1],
                       "next": moment[1:4]},
            "change": {"value": change[0][0], "at": change[0][1],
                       "next": change[1:4]}}


def verdict(compared, limits):
    """Each number beside its limit, and whether all hold. A number
    with no limit in the cell's file is reported and not held."""
    rows, ok = {}, True
    for name in NUMBERS:
        value, limit = compared[name]["value"], limits.get(name)
        rows[name] = {"value": value, "limit": limit,
                      "at": compared[name]["at"]}
        if limit is not None and not value <= limit:
            ok = False
    return ok, rows
