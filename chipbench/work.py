"""Operations and bytes a kernel needs, from its call's shapes alone."""


def attention_work(batch, seq, heads, head_dim, causal,
                   bytes_per_element=2, **_):
    """Forward and backward of one scaled dot-product attention call.
    Forward: QK^T and PV, 2*s*s*d each a head. Backward: dV, dP, dQ, dK,
    twice the forward's products (recomputing the scores is not
    required work). Causal attention needs half of every square.
    Bytes: q, k, v read and o written forward (4 tensors); q, k, v, o,
    do read and dq, dk, dv written backward (8)."""
    square = batch * heads * seq * seq * head_dim
    flops = 3 * 2 * 2 * square
    if causal:
        flops //= 2
    tensor = batch * seq * heads * head_dim * bytes_per_element
    return {"flops": flops, "bytes": 12 * tensor}


def attention_least_seconds(call, peaks):
    """The least time the chip could take for one call, and which of
    the two peaks bounds it."""
    w = attention_work(**call)
    by_flops = w["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory",
            **w}
