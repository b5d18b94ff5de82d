"""Mixture-of-Experts with expert parallelism over a mesh axis.

Beyond the reference's capability bar (the snapshot has no MoE /
global_scatter-gather, SURVEY.md §1 L3) but first-class here per the
TPU-native design: experts shard over the 'ep' mesh axis and tokens move
through ONE all_to_all each way over ICI — the XLA-collective form of the
later reference releases' global_scatter/global_gather op pair.

Switch-style top-1 routing with a static per-expert capacity (XLA needs
static shapes; overflow tokens fall through with their residual, the
standard capacity-factor semantics). Everything is differentiable jnp, so
the same code runs single-device (no mesh) or inside shard_map with the
'ep' axis bound.
"""
import jax
import jax.numpy as jnp


def switch_route(x, gate_w, num_experts, capacity):
    """Top-1 routing. x: [T, D]; gate_w: [D, E].
    Returns (dispatch [T] expert ids, pos [T] slot ids (capacity-clipped,
    -1 = dropped), prob [T] gate prob of the chosen expert,
    probs [T, E] full routing distribution)."""
    logits = x @ gate_w                      # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)      # [T]
    prob = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based slot per expert
    pos = jnp.sum(pos, axis=-1) - 1            # [T], 0-based
    pos = jnp.where(pos < capacity, pos, -1)   # overflow -> dropped
    return expert, pos, prob, probs


def moe_ffn(x, gate_w, w1, b1, w2, b2, axis_name=None, capacity_factor=1.25,
            activation=jax.nn.gelu):
    """Switch-FFN layer. x: [T, D] local tokens; experts:
    w1 [E_local, D, F], w2 [E_local, F, D] (the full expert set when
    axis_name is None). Returns (y [T, D], aux_loss) where aux_loss is the
    Switch load-balancing loss (fraction * mean-prob dot product).

    With axis_name bound (inside shard_map), each device owns E_local
    experts of E = E_local * ep_size and tokens are exchanged with one
    all_to_all per direction."""
    T, D = x.shape
    e_local = w1.shape[0]
    if axis_name is None:
        ep = 1
        my = 0
    else:
        ep = jax.lax.psum(1, axis_name)
        my = jax.lax.axis_index(axis_name)
    E = e_local * ep
    # per-expert capacity for the LOCAL token batch
    cap = max(1, int(capacity_factor * T / E))

    expert, pos, prob, probs_f = switch_route(x, gate_w, E, cap)

    # Switch aux loss: E * sum_e fraction_e * mean_prob_e, with the
    # routing statistics averaged over the ep group first so every device
    # sees the same GLOBAL load-balance objective (pmean of per-device aux
    # would optimize local balance only)
    frac = jnp.mean(jax.nn.one_hot(expert, E, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs_f, axis=0)
    if axis_name is not None:
        frac = jax.lax.pmean(frac, axis_name)
        mean_p = jax.lax.pmean(mean_p, axis_name)
    aux = E * jnp.sum(frac * mean_p)

    # dispatch: [E, cap, D], dropped tokens scatter nowhere
    keep = pos >= 0
    slot = jnp.where(keep, pos, cap)  # out-of-range -> dropped by mode
    disp = jnp.zeros((E, cap + 1, D), x.dtype)
    disp = disp.at[expert, slot].set(x, mode="drop")[:, :cap]

    if axis_name is not None:
        # [E, cap, D] -> [ep, E_local, cap, D]; all_to_all swaps the ep
        # shard axis for the peer axis: afterwards each device holds its
        # E_local experts' slots from EVERY peer -> [E_local, ep*cap, D]
        disp = disp.reshape(ep, e_local, cap, D)
        disp = jax.lax.all_to_all(disp, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
        disp = jnp.swapaxes(disp, 0, 1).reshape(e_local, ep * cap, D)
    else:
        disp = disp.reshape(e_local, cap, D)

    # expert FFN, batched over local experts
    h = activation(jnp.einsum("ecd,edf->ecf", disp, w1) + b1[:, None, :])
    y = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]

    if axis_name is not None:
        y = jnp.swapaxes(y.reshape(e_local, ep, cap, D), 0, 1)
        y = jax.lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)
        y = y.reshape(E, cap, D)
    # gather back to token order; dropped tokens get 0 (residual passes x)
    safe_slot = jnp.where(keep, pos, 0)
    out = y[expert, safe_slot]
    out = jnp.where(keep[:, None], out, 0.0)
    return out * prob[:, None].astype(out.dtype), aux


# the sorted pair slots are worked through in this many equal chunks, a
# chunk that holds no routed pair skipped at run time: what a step costs
# follows the pairs routed here, and every slot has a place
_CHUNKS = 4


def held_experts_ffn(x, router_w, select_bias, w_gate, w_up, w_down, *,
                     top_k, first_expert, scale):
    """Sigmoid-scored top-k routing over ALL experts and the part of the
    result that the experts held here give, with no pair dropped.

    x [T, D]; router_w [D, E] over the whole expert set; select_bias [E]
    joins the scores for the selection only; w_gate / w_up [H, D, F] and
    w_down [H, F, D] are experts `first_expert .. first_expert + H - 1`,
    gated SiLU units. With s = sigmoid(x router_w) in float32 and `sel`
    the top_k of s + select_bias (ties to the lower index),

        g_i = scale * s_i / (sum_{j in sel} s_j + 1e-20)      i in sel
        y   = sum_{i in sel, i held here} g_i E_i(x)

    The token-expert pairs routed to a held expert are sorted expert by
    expert, their rows gathered, the three products run as grouped
    products (`jax.lax.ragged_dot`) and the results are added back
    weighted by g. All T * top_k pair slots exist, so the result is the
    same whether every token chooses held experts or none does; the
    slots are worked through in `_CHUNKS` chunks under `lax.cond`, and a
    chunk past the last routed pair does nothing.

    Returns (y [T, D] in x's dtype, routed pairs, the busiest held
    expert's pairs), the two counts int32 scalars. In a compiled step
    the device time goes under the scopes `router`, `dispatch`,
    `experts` and `combine`."""
    from ..observability.scopes import scope
    from ..recompute import checkpoint_arrays

    tokens, _width = x.shape
    held = w_gate.shape[0]
    slots = tokens * top_k
    chunks = _CHUNKS if slots % _CHUNKS == 0 else 1
    size = slots // chunks

    with scope("router"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(s + select_bias.astype(jnp.float32), top_k)
        # the gates as a dense [T, E] array, 0 where an expert was not
        # chosen: elementwise work, where picking T * top_k scores out
        # and putting their gradients back are scalar gathers and
        # scatters, which the chip runs one element at a time
        experts = s.shape[1]
        chosen = jnp.sum(sel[:, :, None] == jnp.arange(experts), axis=1)
        picked = s * chosen
        gates = scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

    with scope("dispatch"):
        local = (sel - first_expert).reshape(-1)
        key = jnp.where((local >= 0) & (local < held), local, held)
        # held pairs first, expert by expert (a stable sort: token order
        # within an expert), each slot with the pair it holds
        key, order = jax.lax.sort(
            (key, jnp.arange(slots, dtype=jnp.int32)), num_keys=1)
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        starts, routed = ends - sizes, ends[-1]
        token = (order // top_k).reshape(chunks, size)
        expert = (key + first_expert).reshape(chunks, size)

    def chunk(acc, xs):
        tok, exp, first = xs

        def live(acc):
            with scope("dispatch"):
                alive = (first + jnp.arange(size) < routed)[:, None]
                rows = jnp.where(alive, x[tok], 0)
                # a slot's gate: its token's row of gates, at its expert
                g = jnp.sum(jnp.where(exp[:, None] == jnp.arange(experts),
                                      gates[tok], 0), axis=-1)
                here = jnp.clip(jnp.minimum(ends, first + size)
                                - jnp.maximum(starts, first), 0, size)
            with scope("experts"):
                h = (jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, here))
                     * jax.lax.ragged_dot(rows, w_up, here))
                out = jax.lax.ragged_dot(h, w_down, here)
            with scope("combine"):
                out = jnp.where(alive, out, 0).astype(jnp.float32)
                return acc.at[tok].add(out * g[:, None])

        return jax.lax.cond(first < routed, live, lambda acc: acc, acc), None

    # a chunk keeps nothing for its backward but its indices: kept, every
    # chunk's rows, products and masks (and a copy of the weights a
    # chunk, which `cond` cannot tell from a chunk's own) outlive the
    # loop, 1.4 GiB more at 4 x 4,096 tokens of width 2,048, which the
    # chip has not got beside a layer's other replayed activations
    y, _ = jax.lax.scan(checkpoint_arrays(chunk),
                        jnp.zeros(x.shape, jnp.float32),
                        (token, expert, jnp.arange(chunks) * size))
    return y.astype(x.dtype), routed, jnp.max(sizes)
