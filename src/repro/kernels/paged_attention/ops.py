"""jit'd public wrapper for paged decode attention (model layout adapter).

``paged_decode_attention`` is what
``models.attention.paged_decode_attention(impl="pallas")`` calls: the raw
page table (-1 = unmapped) is sanitized to trash-page redirects on the way
in — the only per-call host-side work; the (B, max_pages*page_size) gather
of the XLA path is never materialized.  Single-token decode is the
one-token window of the verify leg: both run the same kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import default_interpret
from .kernel import paged_attention_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q, k_pool, v_pool, page_table, cur_pos, *,
    interpret: Optional[bool] = None,
):
    """q: (B, H, dh); k_pool/v_pool: (n_pages + 1, page_size, Hkv, dh) with
    the trash page at index ``n_pages``; page_table: (B, max_pages) int32,
    -1 = unmapped; cur_pos: (B,) int32.  Returns (B, H, dh)."""
    return paged_verify_attention(
        q[:, None], k_pool, v_pool, page_table, cur_pos,
        interpret=interpret)[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(
    q, k_pool, v_pool, page_table, cur_pos, *,
    interpret: Optional[bool] = None,
):
    """Multi-query verify leg (draft-and-verify window).  q: (B, W, H, dh)
    — W query tokens per slot at absolute positions ``cur_pos + [0, W)``,
    K/V (including the window's own) already written into the pool by the
    caller; pools/page_table/cur_pos as in :func:`paged_decode_attention`.
    Returns (B, W, H, dh)."""
    interpret = default_interpret() if interpret is None else interpret
    B, W, H, dh = q.shape
    Hkv = k_pool.shape[2]
    group = H // Hkv
    n_pages = k_pool.shape[0] - 1
    gather = jnp.where(page_table >= 0, page_table, n_pages).astype(jnp.int32)
    # window-major rows per kv head: row = w * group + q-head-in-group, so
    # the kernel recovers the query position as cur_pos + row // group
    qr = q.reshape(B, W, Hkv, group, dh).transpose(0, 2, 1, 3, 4)
    out = paged_attention_kernel(
        qr.reshape(B, Hkv, W * group, dh), k_pool, v_pool, gather,
        cur_pos.astype(jnp.int32), group=group, interpret=interpret,
    )
    out = out.reshape(B, Hkv, W, group, dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, W, H, dh)
