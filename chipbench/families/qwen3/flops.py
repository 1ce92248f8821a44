"""Operations and bytes that each layer's work needs, from the shapes.

Counted from the model's sizes and the tokens served, the same whatever
implements the work: padding, recomputation and the masked entries of a
kernel are not counted.  A multiply-add is 2 operations.  All activations,
weights and K/V are bfloat16 (2 bytes).
"""

from __future__ import annotations

from .weights import Dims

BYTES = 2


def layer_matmul_params(dims: Dims) -> int:
    """Weights of one layer's matrix products: q, k, v, o projections and
    the SwiGLU gate, up and down."""
    q, kv = dims.n_heads * dims.d_head, dims.n_kv * dims.d_head
    return dims.d * (q + 2 * kv) + q * dims.d + 3 * dims.d * dims.d_ff


def attn_flops(dims: Dims, ctx: int) -> float:
    """One token's attention over ``ctx`` positions, all layers: scores and
    the weighted sum of values, 2 * ctx * d_head each per query head."""
    return 4.0 * ctx * dims.n_heads * dims.d_head * dims.n_layers


def head_flops(dims: Dims) -> float:
    """One token's logits over the vocabulary."""
    return 2.0 * dims.vocab * dims.d


def token_flops(dims: Dims, ctx: int, *, logits: bool) -> float:
    """One token through every layer, attending to ``ctx`` positions (its
    own included), with or without the LM head."""
    f = 2.0 * layer_matmul_params(dims) * dims.n_layers + attn_flops(dims, ctx)
    return f + (head_flops(dims) if logits else 0.0)


def decode_flops(dims: Dims, ctxs) -> float:
    """Decoded tokens, one per entry of ``ctxs`` (the positions each
    attended to)."""
    return sum(token_flops(dims, c, logits=True) for c in ctxs)


def prefill_flops(dims: Dims, start: int, end: int) -> float:
    """Prompt positions ``[start, end)`` computed by one admission (the
    positions before ``start`` came from the prefix cache), causal, and the
    logits of the last position."""
    n = end - start
    ctx_sum = (start + 1 + end) * n // 2        # sum of (p + 1), p in range
    return (2.0 * layer_matmul_params(dims) * dims.n_layers * n
            + 4.0 * ctx_sum * dims.n_heads * dims.d_head * dims.n_layers
            + head_flops(dims))


def decode_attn_work(dims: Dims, ctx: int, page_size: int):
    """(operations, bytes) of one decoded token's paged attention, all
    layers: the K and V of every page the slot holds (``ceil(ctx /
    page_size)`` pages), the query read and the output written."""
    pages = -(-ctx // page_size)
    kv = 2 * pages * page_size * dims.n_kv * dims.d_head * BYTES
    qo = 2 * dims.n_heads * dims.d_head * BYTES
    return attn_flops(dims, ctx), float((kv + qo) * dims.n_layers)
