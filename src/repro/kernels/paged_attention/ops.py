"""jit'd public wrapper for paged decode attention (model layout adapter).

``paged_decode_attention`` is what
``models.attention.paged_decode_attention(impl="pallas")`` calls: the raw
page table (-1 = unmapped) goes to the kernel as it is, and the kernel
copies only the mapped pages each slot's queries can see; the
(B, max_pages*page_size) gather of the XLA path is never materialized.
Single-token decode is the one-token window of the verify leg: both run
the same kernel.

The pools are either one layer's, ``(n_pages + 1, page_size, Hkv, dh)``,
or the stacked pools of every layer, ``(n_layers, n_pages + 1, page_size,
Hkv, dh)``, with ``layer`` naming the one to attend.  The stacked form lets
a layer scan carry the whole pool and write it in place: the kernel walks
``(layer, page)`` itself, so no layer's pool is sliced out of the stack.
A single-layer pool is the one-layer stack.

A windowed layer (``window`` = W) masks keys at positions ``<= pos - W``
for the query at ``pos``, and the kernel's walk starts at the page holding
``cur_pos - W + 1``: at most ``ceil((W + Wq - 1) / page_size) + 1`` pages
(``Wq`` query tokens) instead of the whole row.  The kernel's cost follows
the pages it moves.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import default_interpret
from .kernel import paged_attention_kernel


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(
    q, k_pool, v_pool, page_table, cur_pos, layer=None, *,
    window: Optional[int] = None, interpret: Optional[bool] = None,
):
    """q: (B, H, dh); k_pool/v_pool: (n_pages + 1, page_size, Hkv, dh) with
    the trash page at index ``n_pages``, or the stacked
    (n_layers, n_pages + 1, page_size, Hkv, dh) with ``layer`` an int32
    scalar; page_table: (B, max_pages) int32, -1 = unmapped; cur_pos: (B,)
    int32; ``window``: the layer's sliding window, None = full.
    Returns (B, H, dh)."""
    return paged_verify_attention(
        q[:, None], k_pool, v_pool, page_table, cur_pos, layer,
        window=window, interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_verify_attention(
    q, k_pool, v_pool, page_table, cur_pos, layer=None, *,
    window: Optional[int] = None, interpret: Optional[bool] = None,
):
    """Multi-query verify leg (draft-and-verify window).  q: (B, W, H, dh)
    — W query tokens per slot at absolute positions ``cur_pos + [0, W)``,
    K/V (including the window's own) already written into the pool by the
    caller; pools/page_table/cur_pos/layer as in
    :func:`paged_decode_attention`.  Returns (B, W, H, dh)."""
    interpret = default_interpret() if interpret is None else interpret
    if layer is None:                     # one layer's pool: a stack of one
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    B, W, H, dh = q.shape
    Hkv = k_pool.shape[3]
    group = H // Hkv
    # window-major rows per kv head: row = w * group + q-head-in-group, so
    # the kernel recovers the query position as cur_pos + row // group
    qr = q.reshape(B, W, Hkv, group, dh).transpose(0, 2, 1, 3, 4)
    out = paged_attention_kernel(
        qr.reshape(B, Hkv, W * group, dh), k_pool, v_pool, page_table,
        cur_pos.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
        group=group, window=window, interpret=interpret,
    )
    out = out.reshape(B, Hkv, W, group, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, W, H, dh)
