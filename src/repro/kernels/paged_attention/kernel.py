"""Paged decode-attention Pallas TPU kernel (flash-decoding over a page pool).

New tokens attend to K/V scattered across a shared pool of fixed-size
pages, ``(n_pages + 1, page_size, Hkv, dh)`` with a trash page at index
``n_pages``, one such pool per layer stacked as
``(n_layers, n_pages + 1, page_size, Hkv, dh)``.  The XLA path
materializes a gathered ``(B, max_pages*page_size, Hkv, dh)`` view of the
pool before attending — the same bytes twice (pool -> gather copy ->
attention read).  This kernel
walks the slot's **page table inside the kernel** instead:

* the page table, ``cur_pos`` and the layer index ride in as
  *scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``), so the
  K/V BlockSpec index maps can pick the physical page
  ``(layer, table[b, j])`` of the stacked pool for grid step ``(b, j)`` —
  the gather becomes the DMA schedule, not a materialized array, and the
  layer's pool is never sliced out of the stack.  Pallas's
  pipeline double-buffers these page loads across the innermost grid axis
  (page ``j+1`` streams into VMEM while page ``j`` is being reduced);
* unmapped logical pages are redirected to the trash page for the *load*
  (never out of bounds) and masked out of the softmax for the *math*;
* validity is fused into the online softmax exactly like
  ``kernels/decode_attention``: paged placement is position-indexed
  (logical page j, offset o IS absolute position ``j*page_size + o``), so a
  key is attendable iff its page is mapped and its position is not beyond
  the query's — no per-token ``pos`` array needed.

A windowed layer (``window`` = W) gets a fourth scalar-prefetch operand,
``first`` (B,): the logical page of the table's first entry, which the
wrapper sliced to the pages the window overlaps.  Grid step ``(b, j)``
then holds logical page ``first[b] + j``, and keys at positions
``<= qpos - W`` are masked.  Those lie at the start of the walk; their
masked scores leave ``m`` at ``NEG_INF`` until the first key in the window
arrives, whose rescale (``exp(NEG_INF - m)`` = 0) clears what they added.

Grid = (B, max_pages): each cell owns one slot; the logical-page axis is
innermost and carries the (m, l, acc) online-softmax scratch across steps.
One block is a whole page with **all kv heads**: the pool is viewed as
``(n_layers, n_pages + 1, page_size * Hkv, dh)`` (a free reshape), so the
block's last two dims are ``(page_size * Hkv, dh)`` — legal for the TPU's (8, 128)
tiling, where a one-head slice ``(…, 1, dh)`` against ``Hkv`` is not.  The
query rows of every kv head ride in the same cell as one
``(Hkv * R, dh)`` matrix, and the scores are one
``(Hkv * R, page_size * Hkv)`` matmul whose cross-head entries are masked
out: ``Hkv`` times the minimal FLOPs, which decode's arithmetic intensity
(``Hkv * R`` FLOP per KV byte, far below the chip's ridge) leaves free.

VMEM per cell, with ``R`` query rows per kv head (``group`` for decode,
``W * group`` for a W-token verify window) and ``E`` the pool's bytes per
element: K and V blocks double-buffered ``4 * page_size * Hkv * dh * E``,
their f32 copies ``2 * page_size * Hkv * dh * 4``, scores and
probabilities ``2 * Hkv * R * page_size * Hkv * 4``, and the q/out blocks
plus scratch ``~6 * Hkv * R * dh * 4``.  At qwen3-0.6b widths (Hkv 8,
dh 128, group 2) and page_size 128: ~1 MiB (bf16) + 1 MiB + 0.5 MiB (W 4)
+ 0.2 MiB, inside the default scoped VMEM limit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF


def _paged_kernel(
    gather_ref, cur_ref, layer_ref, *refs,    # scalar prefetch (SMEM)
    page_size: int, n_pages: int, max_pages: int, n_kv: int,
    rows: int, group: int, window,
):
    """Query row ``h * rows + w * group + g`` is q-head ``g`` of kv head
    ``h`` at window position ``w``: absolute position ``cur_pos[b] + w``,
    attending keys at positions ``<= cur_pos[b] + w`` — which includes a
    verify window's own K/V written by the caller before the kernel runs
    (within-window causality falls out of the same position check).  Key
    column ``o * n_kv + h`` is offset ``o`` of the page, kv head ``h``."""
    if window is None:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        page = pl.program_id(1)
    else:
        first_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        page = first_ref[pl.program_id(0)] + pl.program_id(1)
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (n_kv*rows, dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (ps*n_kv, dh)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(q.shape[-1]))             # (n_kv*rows, ps*n_kv)

    shape = s.shape
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    same_head = row // rows == col % n_kv
    pos = page * page_size + col // n_kv
    qpos = cur_ref[b] + (row % rows) // group
    mapped = gather_ref[b, j] < n_pages
    valid = same_head & mapped & (pos <= qpos)
    if window is not None:
        valid = valid & (pos > qpos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # (n_kv*rows, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    scale = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * scale + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * scale + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )

    @pl.when(j == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(
    q, k_pool, v_pool, gather, cur_pos, layer, *, group: int,
    first=None, window=None, interpret: bool = False,
):
    """q: (B, Hkv, R, dh) — R = W*group window-major query rows per kv
    head (R = group for single-token decode); k_pool/v_pool: the stacked
    pools (n_layers, n_pages + 1, ps, Hkv, dh); gather: (B, max_pages)
    int32 physical page per logical page, already sanitized (unmapped ->
    n_pages, the trash page); cur_pos: (B,) int32 position of the first
    query row; layer: (1,) int32, the pool of the stack to attend;
    ``window`` with ``first`` (B,) int32, the logical page of ``gather``'s
    first entry: a windowed layer's walk.  Returns (B, Hkv, R, dh)."""
    B, Hkv, rows, dh = q.shape
    n_layers, n_pages, page_size = k_pool.shape[:3]
    n_pages -= 1                                       # the trash page
    max_pages = gather.shape[1]
    nq = Hkv * rows

    kern = functools.partial(
        _paged_kernel, page_size=page_size, n_pages=n_pages,
        max_pages=max_pages, n_kv=Hkv, rows=rows, group=group, window=window,
    )
    page = (1, 1, page_size * Hkv, dh)
    prefetch = (gather, cur_pos, layer) + (() if window is None else (first,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, nq, dh), lambda b, j, g, c, l, *_: (b, 0, 0)),
            # the page walk: physical page id from the prefetched table
            pl.BlockSpec(page, lambda b, j, g, c, l, *_: (l[0], g[b, j], 0, 0)),
            pl.BlockSpec(page, lambda b, j, g, c, l, *_: (l[0], g[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nq, dh), lambda b, j, g, c, l, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, 1), jnp.float32),          # m
            pltpu.VMEM((nq, 1), jnp.float32),          # l
            pltpu.VMEM((nq, dh), jnp.float32),         # acc
        ],
    )
    view = (n_layers, n_pages + 1, page_size * Hkv, dh)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nq, dh), q.dtype),
        interpret=interpret,
    )(*prefetch, q.reshape(B, nq, dh),
      k_pool.reshape(view), v_pool.reshape(view))
    return out.reshape(B, Hkv, rows, dh)
