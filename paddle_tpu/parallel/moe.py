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
import functools

import jax
import jax.numpy as jnp

from ..kernels import put_rows as _put_rows


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


# the sorted pair slots are worked through in blocks of this many rows,
# as many blocks as hold a routed pair: what a layer costs, in every
# pass, follows the pairs routed here, and every slot has a place
_BLOCK = 1024
# the backward keeps this many blocks' rows side by side and forms the
# weights' gradients over them in one grouped product a weight: such a
# product writes every held expert's [D, F] result whatever rows it was
# given, 100 MB in float32 at 16 x 2048 x 768, so it runs once a layer
# where the routing is the usual one and not once a block. A share that
# uniform routing sends more than half as many blocks stages twice its
# usual blocks (`_staged_blocks`)
_STAGED = 16

# the grouped product of a weight's gradient, as `ragged_dot`'s own
# transpose forms it: [B, k] rows against [B, n] rows over the ragged B,
# a [G, k, n] result. (A rows' gradient is `ragged_dot` against the
# weight transposed: other dimension numbers leave the chip's grouped
# matmul for a dense product over every group.)
_ROWS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=(0,), rhs_group_dimensions=())


def _block_rows(slots):
    return _BLOCK if slots % _BLOCK == 0 else slots


def _live_blocks(block, routed):
    return (routed + block - 1) // block


def _staged_blocks(slots, block, held, experts):
    """How many blocks' rows the backward stages for a share of `held`
    of `experts`: `_STAGED`, or twice the blocks uniform routing sends
    here where that is more. At exactly `_STAGED` usual blocks (top-4 of
    64 with 8 held over 32,768 tokens) every other layer application
    would spill one block into a second pass over the weights'
    gradients, and which ones do turns on the drawn router."""
    usual = -(-slots * held // (experts * block))
    return min(max(_STAGED, 2 * usual), slots // block)


def rows_worked(routed, slots):
    """The rows `held_experts_ffn` works in a pass over `slots` pair
    slots of which `routed` hold a pair routed here: its live blocks'."""
    block = _block_rows(slots)
    return block * _live_blocks(block, routed)


def _dispatch(first, block, x, gates, token, expert, starts, ends):
    """The block of sorted slots from `first` on: its tokens, which of
    its rows hold a routed pair, those tokens' rows of x, where a row's
    expert lies among all experts, its gate, and how many of the block's
    rows each held expert has."""
    tok = jax.lax.dynamic_slice_in_dim(token, first, block)
    exp = jax.lax.dynamic_slice_in_dim(expert, first, block)
    alive = (first + jnp.arange(block) < ends[-1])[:, None]
    rows = jnp.where(alive, x[tok], 0)
    # a slot's gate: its token's row of gates, at its expert
    chosen = exp[:, None] == jnp.arange(gates.shape[1])
    g = jnp.sum(jnp.where(chosen, gates[tok], 0), axis=-1, keepdims=True)
    here = jnp.clip(jnp.minimum(ends, first + block)
                    - jnp.maximum(starts, first), 0, block)
    return tok, alive, rows, chosen, g, here


def _by_token(first, block, by_token, in_order):
    """The block of sorted slots from `first` on in token order: where
    each of its rows lies in the block, and their tokens (`tokens` for a
    slot past the routed pairs, after every token)."""
    return (jax.lax.dynamic_slice_in_dim(by_token, first, block),
            jax.lax.dynamic_slice_in_dim(in_order, first, block))


# rows of a routed layer's float32 sums past its tokens: where a block's
# rows that are summed into another row of their token go
_SPARE = 8


def _sum_rows(tokens, width):
    """A float32 [tokens, width] sum laid out as [tokens + _SPARE, n, L]:
    a row whole lane tiles (L = 128) where the width allows, so that one
    DMA writes it (`kernels.put_rows`)."""
    lanes = 128 if width % 128 == 0 else width
    return jnp.zeros((tokens + _SPARE, width // lanes, lanes), jnp.float32)


def _add_by_token(acc, rows, tok, repeats):
    """acc (`_sum_rows`) plus a block's rows [B, n, L] float32, which are
    in token order with `tok` [B] their tokens (T: a row to leave out),
    each token's rows summed in float32 into its first and the sums added
    with indices that are truly unique. A token repeats at most `repeats`
    times in a block: ceil(log2(repeats)) shifted adds sum its rows. On
    the chip the sums' rows of acc are gathered, added and written back
    by one DMA a row (`kernels.put_rows`): XLA:TPU's scatter adds a row
    at a time, unique indices or not."""
    tokens, ahead = acc.shape[0] - _SPARE, 1
    while ahead < repeats:
        same = jnp.concatenate([tok[ahead:] == tok[:-ahead],
                                jnp.zeros((ahead,), bool)])
        later = jnp.concatenate([rows[ahead:],
                                 jnp.zeros((ahead,) + rows.shape[1:],
                                           rows.dtype)])
        rows = rows + jnp.where(same[:, None, None], later, 0)
        ahead *= 2
    at = jnp.arange(tok.shape[0])
    first = (tok < tokens) & ((at == 0) | (tok != jnp.roll(tok, 1)))
    if _put_rows.is_available() and _put_rows.supports(acc.shape,
                                                       rows.shape):
        # a row summed into its token's first, or past the routed pairs,
        # goes to the spare row `tokens`
        dest = jnp.where(first, tok, tokens)
        return _put_rows.put_rows(acc, dest, acc[dest] + rows)
    # ... to an index of its own, among the spare rows or past them
    return acc.at[jnp.where(first, tok, tokens + at)].add(
        rows, unique_indices=True, mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed_blocks(block, staged, repeats, x, gates, w_gate, w_up, w_down,
                   token, expert, starts, ends, by_token, in_order):
    """sum over the routed pairs of gate x expert(row), added by token:
    [T, D] in x's dtype. One loop over the live blocks forward, one
    backward; nothing of a block is kept between them. A block's rows are
    added in token order (`by_token`, `in_order`: `held_experts_ffn`),
    each token once."""
    from ..observability.scopes import scope

    def one_block(i, acc):
        with scope("dispatch"):
            _tok, _alive, rows, _chosen, g, here = _dispatch(
                i * block, block, x, gates, token, expert, starts, ends)
        with scope("experts"):
            h = (jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, here))
                 * jax.lax.ragged_dot(rows, w_up, here))
            out = jax.lax.ragged_dot(h, w_down, here)
        with scope("combine"):
            # a row past the routed pairs, whatever the grouped product
            # left in it, is summed with no routed row and dropped
            order, order_tok = _by_token(i * block, block, by_token,
                                         in_order)
            out = out.reshape(block, -1, acc.shape[2])[order]
            return _add_by_token(
                acc, out.astype(jnp.float32) * g[order][:, :, None],
                order_tok, repeats)

    y = jax.lax.fori_loop(0, _live_blocks(block, ends[-1]), one_block,
                          _sum_rows(*x.shape))
    return y[:x.shape[0]].reshape(x.shape).astype(x.dtype)


def _routed_blocks_fwd(block, staged, repeats, *operands):
    return _routed_blocks(block, staged, repeats, *operands), operands


def _routed_blocks_bwd(block, staged, repeats, operands, dy):
    from ..observability.scopes import scope

    (x, gates, w_gate, w_up, w_down, token, expert, starts, ends, by_token,
     in_order) = operands
    f32 = jnp.float32
    live = _live_blocks(block, ends[-1])
    span = staged * block
    with scope("experts"):
        gate_t, up_t, down_t = (jnp.swapaxes(w, 1, 2)
                                for w in (w_gate, w_up, w_down))

    def one_block(i, carry, base):
        """Block i's part of dx and of the gates' gradient, and its rows
        and the factors of the weights' gradients put beside the other
        blocks' from block `base` on."""
        dx, dgates, (s_rows, s_dy, s_da, s_du, s_h) = carry
        with scope("dispatch"):
            tok, alive, rows, chosen, g, here = _dispatch(
                i * block, block, x, gates, token, expert, starts, ends)
            dy_rows = jnp.where(alive, dy[tok], 0)
        with scope("experts"):
            # the forward's first two products and activation again: the
            # one replay a block costs. The chip's grouped product
            # writes the rows of its groups and no others: what it
            # leaves in a row past the last routed pair is not a number
            # to multiply by 0
            a, u, dh = (
                jnp.where(alive, product, 0) for product in (
                    jax.lax.ragged_dot(rows, w_gate, here).astype(f32),
                    jax.lax.ragged_dot(rows, w_up, here).astype(f32),
                    # d(out) = g dy: the gate joins on the narrow side
                    jax.lax.ragged_dot(dy_rows, down_t, here,
                                       preferred_element_type=f32)))
            gate = jax.nn.sigmoid(a)
            act = a * gate
            dg = jnp.sum(dh * (act * u), axis=-1, keepdims=True)
            dh = dh * g
            da = (dh * u * gate * (1 + a * (1 - gate))).astype(x.dtype)
            du = (dh * act).astype(x.dtype)
            hg = (act * u * g).astype(x.dtype)
            d_rows = (jax.lax.ragged_dot(da, gate_t, here).astype(f32)
                      + jax.lax.ragged_dot(du, up_t, here))
        with scope("dispatch"):
            dgates = dgates.at[tok].add(jnp.where(chosen, dg, 0))
        with scope("combine"):
            order, order_tok = _by_token(i * block, block, by_token,
                                         in_order)
            dx = _add_by_token(
                dx, d_rows.reshape(block, -1, dx.shape[2])[order],
                order_tok, repeats)
            at = (i - base) * block
            put = jax.lax.dynamic_update_slice_in_dim
            staging = (put(s_rows, rows, at, 0), put(s_dy, dy_rows, at, 0),
                       put(s_da, da, at, 0), put(s_du, du, at, 0),
                       put(s_h, hg, at, 0))
        return dx, dgates, staging

    def staged_blocks(j, carry):
        """Blocks j * staged .. of the live ones, and the weights'
        gradients over their rows."""
        base = j * staged
        dx, dgates, staging = jax.lax.fori_loop(
            base, jnp.minimum(base + staged, live),
            functools.partial(one_block, base=base), carry)
        s_rows, s_dy, s_da, s_du, s_h = staging
        with scope("experts"):
            first = base * block
            here = jnp.clip(jnp.minimum(ends, first + span)
                            - jnp.maximum(starts, first), 0, span)
            grads = tuple(
                jax.lax.ragged_dot_general(
                    lhs, rhs, here, _ROWS_BY_ROWS, preferred_element_type=f32)
                for lhs, rhs in ((s_rows, s_da), (s_rows, s_du),
                                 (s_h, s_dy)))
        return (dx, dgates, staging), grads

    width, hidden = w_gate.shape[1:]
    carry = (_sum_rows(*x.shape), jnp.zeros(gates.shape, f32),
             tuple(jnp.zeros((span, n), x.dtype)
                   for n in (width, width, hidden, hidden, hidden)))
    # the first `staged` blocks' weight gradients start the sums; where
    # more blocks are live the others' are added in float32
    carry, grads = staged_blocks(0, carry)

    def more(j, state):
        carry, grads = state
        carry, new = staged_blocks(j, carry)
        with scope("experts"):
            return carry, tuple(a + b for a, b in zip(grads, new))

    (dx, dgates, _staging), grads = jax.lax.fori_loop(
        1, (live + staged - 1) // staged, more, (carry, grads))
    dx = dx[:x.shape[0]].reshape(x.shape)
    return (dx.astype(x.dtype), dgates.astype(gates.dtype)) + tuple(
        g.astype(w.dtype) for g, w in zip(grads, (w_gate, w_up, w_down))
    ) + (None,) * 6


_routed_blocks.defvjp(_routed_blocks_fwd, _routed_blocks_bwd)


def held_experts_ffn(x, router_w, select_bias, w_gate, w_up, w_down, *,
                     top_k, first_expert, scale, norm_eps=1e-20,
                     scoring="sigmoid"):
    """Top-k routing over ALL experts and the part of the result that the
    experts held here give, with no pair dropped.

    x [T, D]; router_w [D, E] over the whole expert set; select_bias [E]
    (or None: zeros) joins the scores for the selection only; w_gate /
    w_up [H, D, F] and w_down [H, F, D] are experts `first_expert ..
    first_expert + H - 1`, gated SiLU units. With s = sigmoid(x router_w)
    (`scoring="sigmoid"`) or softmax(x router_w) over all E experts
    (`"softmax"`), in float32, and `sel` the top_k of s + select_bias
    (ties to the lower index),

        g_i = scale * s_i / (sum_{j in sel} s_j + norm_eps)   i in sel
        y   = sum_{i in sel, i held here} g_i E_i(x)

    The token-expert pairs routed to a held expert are sorted expert by
    expert and worked through in blocks of `_BLOCK` sorted slots by a
    loop whose trip count, ceil(routed / `_BLOCK`), is read on the device
    at run time: a block's rows are gathered, the three products run as
    grouped products (`jax.lax.ragged_dot`) and the results, weighted by
    g, are added back by token into a float32 sum, each token once: put
    in token order (a second sort of the slots by block and token, once a
    call), a token's rows of the block summed in float32, and the sums
    added at indices that do not repeat; on the chip the sum's rows are
    gathered, added and written back by one DMA a row
    (`kernels.put_rows`), where XLA:TPU's scatter adds a row at a time.
    All T * top_k pair slots have a place, so the result is the same
    whether every token chooses held experts (every block runs) or none
    does (none runs). The backward is a second loop over the same blocks
    (`jax.custom_vjp`): it gathers a block's rows again, repeats its
    first two products and forms the gradients, which meet in float32
    (dx added by token as the forward adds y); no pass keeps anything of
    a block for another. Where `_BLOCK` does not divide the slots they
    are one block.

    Returns (y [T, D] in x's dtype, routed pairs, the busiest held
    expert's pairs, the routed pairs summed into another row of their
    token before the add), the three counts int32 scalars. In a compiled
    step the device time goes under the scopes `router`, `dispatch`,
    `experts` and `combine`."""
    from ..observability.scopes import scope

    tokens, _width = x.shape
    held = w_gate.shape[0]
    slots = tokens * top_k

    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown scoring {scoring!r}")
    with scope("router"):
        s = (jax.nn.sigmoid if scoring == "sigmoid" else jax.nn.softmax)(
            jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST))
        _, sel = jax.lax.top_k(s if select_bias is None else
                               s + select_bias.astype(jnp.float32), top_k)
        # the gates as a dense [T, E] array, 0 where an expert was not
        # chosen: elementwise work, where picking T * top_k scores out
        # and putting their gradients back are scalar gathers and
        # scatters, which the chip runs one element at a time
        experts = s.shape[1]
        chosen = jnp.sum(sel[:, :, None] == jnp.arange(experts), axis=1)
        picked = s * chosen
        gates = scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + norm_eps)

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
        # every block's slots in token order, once for the forward, its
        # replay and the backward: where each lies in its block, and its
        # token (`tokens` past the routed pairs, after every token)
        block = _block_rows(slots)
        at = jnp.arange(slots, dtype=jnp.int32)
        token = order // top_k
        _, in_order, by_token = jax.lax.sort(
            (at // block, jnp.where(at < routed, token, tokens), at % block),
            num_keys=2)
        # the held slots summed into their token's first of the block
        firsts = (in_order < tokens) & ((at % block == 0) | (
            in_order != jnp.roll(in_order, 1)))
        folded = routed - jnp.sum(firsts, dtype=jnp.int32)

    y = _routed_blocks(block, _staged_blocks(slots, block, held, experts),
                       min(top_k, held), x, gates, w_gate, w_up, w_down,
                       token, key + first_expert, starts, ends, by_token,
                       in_order)
    return y, routed, jnp.max(sizes), folded
