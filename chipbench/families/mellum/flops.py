"""Operations and bytes that each layer's work needs, from the shapes.

Counted from the model's sizes and the tokens served, the same whatever
implements the work: padding, recomputation and masked entries are not
counted.  A multiply-add is 2 operations; weights, activations and K/V are
bfloat16 (2 bytes).

A ``sliding_attention`` layer attends to the last ``min(ctx, window)``
positions and needs only the pages that overlap them; a full layer attends
to all ``ctx``.  The expert term of a token counts the assignments routed to
the experts held here: ``top_k * held / n_experts`` of them on average (the
router's load over the published experts is even in expectation; the
batcher's counters give the window's actual count, which
``metrics/moe_gmm_roofline.py`` reads).
"""

from __future__ import annotations

from .weights import Dims

BYTES = 2


def attn_params(dims: Dims) -> int:
    """Weights of one layer's q, k, v and o projections."""
    q, kv = dims.n_heads * dims.d_head, dims.n_kv * dims.d_head
    return dims.d * (q + 2 * kv) + q * dims.d


def expert_params(dims: Dims) -> int:
    """Weights of one expert: gate, up, down."""
    return 3 * dims.d * dims.d_expert


def held_assignments_per_token(dims: Dims) -> float:
    """Assignments of one token, in one layer, routed to a held expert."""
    return dims.top_k * dims.held / dims.n_experts


def layer_matmul_flops(dims: Dims) -> float:
    """One token through one layer's matrix products: projections, the
    router over all published experts, the held experts' share."""
    return 2.0 * (attn_params(dims) + dims.d * dims.n_experts
                  + held_assignments_per_token(dims) * expert_params(dims))


def _seen(dims: Dims, layer: int, ctx: int) -> int:
    w = dims.windows[layer]
    return ctx if w is None else min(ctx, w)


def attn_flops(dims: Dims, ctx: int) -> float:
    """One token's attention over ``ctx`` positions (its own included), all
    layers: scores and the weighted sum of values, 2 * seen * d_head each per
    query head."""
    return sum(4.0 * _seen(dims, i, ctx) * dims.n_heads * dims.d_head
               for i in range(dims.n_layers))


def head_flops(dims: Dims) -> float:
    return 2.0 * dims.vocab * dims.d


def token_flops(dims: Dims, ctx: int, *, logits: bool) -> float:
    """One token through every layer, attending to ``ctx`` positions (its
    own included), with or without the LM head."""
    f = layer_matmul_flops(dims) * dims.n_layers + attn_flops(dims, ctx)
    return f + (head_flops(dims) if logits else 0.0)


def decode_flops(dims: Dims, ctxs) -> float:
    """Decoded tokens, one per entry of ``ctxs``."""
    return sum(token_flops(dims, c, logits=True) for c in ctxs)


def prefill_flops(dims: Dims, start: int, end: int) -> float:
    """Prompt positions ``[start, end)`` computed by one admission (those
    before ``start`` came from the prefix cache), causal (and windowed), and
    the logits of the last position."""
    n = end - start
    seen = sum(_seen(dims, i, p + 1) for i in range(dims.n_layers)
               for p in range(start, end))
    return (layer_matmul_flops(dims) * dims.n_layers * n
            + 4.0 * seen * dims.n_heads * dims.d_head + head_flops(dims))


def decode_attn_work(dims: Dims, ctx: int, page_size: int):
    """(operations, bytes) of one decoded token's paged attention, all
    layers: the K and V of every page that overlaps the positions a layer
    attends to, the query read and the output written."""
    last = ctx - 1                                  # the token's position
    qo = 2 * dims.n_heads * dims.d_head * BYTES
    nbytes = 0
    for i in range(dims.n_layers):
        first = last - _seen(dims, i, ctx) + 1
        pages = last // page_size - first // page_size + 1
        nbytes += 2 * pages * page_size * dims.n_kv * dims.d_head * BYTES + qo
    return attn_flops(dims, ctx), float(nbytes)


def gmm_work(dims: Dims, assignments: int, experts_hit: int):
    """(operations, bytes) of the held experts' grouped products for
    ``assignments`` tokens routed to held experts, which hit
    ``experts_hit`` (layer, expert) pairs: 6 d d_expert operations per
    assignment; each hit expert's weights read once, and per assignment
    the token's row read, its gate/up product written and read back as
    the activation, and its output row written."""
    d, f = dims.d, dims.d_expert
    act = assignments * (d + 2 * f + f + d) * BYTES
    return (6.0 * d * f * assignments,
            float(experts_hit * expert_params(dims) * BYTES + act))
