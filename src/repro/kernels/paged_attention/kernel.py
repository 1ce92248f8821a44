"""Paged decode-attention Pallas TPU kernel (flash-decoding over a page pool).

New tokens attend to K/V scattered across a shared pool of fixed-size
pages, ``(n_pages + 1, page_size, Hkv, dh)`` with a trash page at index
``n_pages``, one such pool per layer stacked as
``(n_layers, n_pages + 1, page_size, Hkv, dh)``.  The XLA path
materializes a gathered ``(B, max_pages*page_size, Hkv, dh)`` view of the
pool before attending — the same bytes twice (pool -> gather copy ->
attention read).  This kernel walks each slot's **page table inside the
kernel** and moves only the pages the slot's queries can see, once:

* Grid = (B,): one cell per slot.  The page table (-1 = unmapped),
  ``cur_pos`` and the layer index ride in as *scalar-prefetch* operands
  (SMEM); the stacked pool stays in HBM (``memory_space=pl.ANY``), viewed
  as ``(n_layers, n_pages + 1, page_size * Hkv, dh)`` (a free reshape), so
  a page is one ``(page_size * Hkv, dh)`` slab holding all kv heads and
  no layer's pool is sliced out of the stack.
* The walk: a slot's live logical pages are ``[lo, hi)``, ``hi - 1`` the
  page of its last query ``cur_pos + Wq - 1`` (``Wq`` query tokens) and
  ``lo`` 0, or, for a windowed layer (``window`` = W), the page of the
  first query's oldest key in the window, ``cur_pos - W + 1``.  The trip
  count is read from SMEM, so it is dynamic.  The cell walks them in
  blocks of ``P`` pages (:func:`pages_per_block`): a block is one async
  copy per page and pool, from ``(layer, table[b, j])`` into a
  ``(P * page_size * Hkv, dh)`` VMEM buffer.  An unmapped entry, a hole
  in the middle of a row included, issues no copy, and its keys are
  masked; so are pages past ``hi`` in the last block.  A slot with no
  live mapped page (an idle slot) issues no copy and no matmul, and
  writes zeros.
* Double buffering runs across the whole grid: before block ``i`` is
  waited for and reduced, block ``i + 1`` — or, after the slot's last
  block, the first block of slot ``b + 1`` — is started into the other
  buffer, so the copies stream while the MXU works and no cell starts
  cold.  The grid axis is therefore sequential (``"arbitrary"``).
* The math: validity is fused into the online softmax like
  ``kernels/decode_attention``.  Paged placement is position-indexed
  (logical page j, offset o IS absolute position ``j*page_size + o``), so
  a key is attendable iff its page is mapped and its position is not
  beyond the query's (and, windowed, not ``<= qpos - W``).  The query rows
  of every kv head ride in the same cell as one ``(Hkv * R, dh)`` matrix,
  and the scores are one ``(Hkv * R, P * page_size * Hkv)`` matmul whose
  cross-head entries are masked out: ``Hkv`` times the minimal FLOPs,
  which decode's arithmetic intensity leaves free.  K and V enter the
  matmuls in the pool's dtype, accumulating in float32.

The kernel's cost follows the pages it moves: a block's copies are issued
back to back and overlap the previous block's math, so a step is bound by
the live K/V bytes, not by a fixed cost per grid step.

VMEM per call, with ``R`` query rows per kv head (``group`` for decode,
``W * group`` for a W-token verify window), ``E`` the pool's bytes per
element and ``C = P * page_size * Hkv`` key columns a block: K and V
buffers ``4 * C * dh * E`` (two of each), the block's float32 scores and
probabilities ``2 * Hkv * R * C * 4``, a column mask and the q/out blocks
and softmax state (small).  :func:`pages_per_block` picks ``P`` so the
first two stay inside ``VMEM_BUDGET``, half of v5e's 16 MiB default
scoped limit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF, cdiv, round_up

# bytes of VMEM a block's K/V buffers and score temporaries may take
VMEM_BUDGET = 8 * 2**20


def pages_per_block(walk: int, page_bytes: int, score_bytes: int,
                    span: int) -> int:
    """Pages one block of a walk of at most ``walk`` pages moves, from static
    shapes only: as many as ``VMEM_BUDGET`` holds at ``4 * page_bytes``
    (K and V, double-buffered) plus ``2 * score_bytes`` (a page's float32
    scores and probabilities) each, evened out over the walk so that its
    last block is not mostly empty, and rounded up so that a block's
    ``P * span`` key columns fill whole 128-lane vregs."""
    most = max(1, VMEM_BUDGET // (4 * page_bytes + 2 * score_bytes))
    even = cdiv(walk, cdiv(walk, most))
    return round_up(even, 128 // math.gcd(span, 128))


def _paged_kernel(
    table_ref, cur_ref, layer_ref,                 # scalar prefetch (SMEM)
    q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, live_ref, m_ref, l_ref, acc_ref, sem, slot_ref, *,
    page_size: int, n_pages: int, max_pages: int, n_kv: int, rows: int,
    group: int, window, block: int,
):
    """Query row ``h * rows + w * group + g`` is q-head ``g`` of kv head
    ``h`` at window position ``w``: absolute position ``cur_pos[b] + w``,
    attending keys at positions ``<= cur_pos[b] + w`` — which includes a
    verify window's own K/V written by the caller before the kernel runs
    (within-window causality falls out of the same position check).  Key
    column ``(p * page_size + o) * n_kv + h`` of a block is offset ``o`` of
    its page ``p``, kv head ``h``."""
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    span = page_size * n_kv                        # key columns a page
    n_q = rows // group                            # query tokens a slot

    def walk(s):
        """``[lo, hi)``: the live logical pages of slot ``s``."""
        cur = cur_ref[s]
        hi = jnp.minimum((cur + n_q - 1) // page_size + 1, max_pages)
        if window is None:
            return jnp.int32(0), hi
        return jnp.minimum(jnp.maximum(cur - window + 1, 0) // page_size,
                           hi), hi

    def pages(s, i):
        """(place in the block, physical page, whether it is copied) for
        each page of block ``i`` of slot ``s``'s walk."""
        lo, hi = walk(s)
        for p in range(block):
            j = lo + i * block + p
            page = table_ref[s, jnp.minimum(j, max_pages - 1)]
            yield p, page, (j < hi) & (page >= 0) & (page < n_pages)

    def copies(p, page, buf):
        layer = layer_ref[0]
        dst = pl.ds(p * span, span)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[buf, dst], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[buf, dst], sem.at[1, buf]))

    def start(s, i, buf):
        for p, page, copied in pages(s, i):
            @pl.when(copied)
            def _():
                for c in copies(p, page, buf):
                    c.start()

    @pl.when(b == 0)
    def _prologue():
        # stale buffer columns are masked, but 0 * NaN is NaN: never let
        # the math read VMEM that no copy has written
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(b, 0, 0)

    first = slot_ref[0]                            # buffer of block 0
    lo, hi = walk(b)
    # at least one block: the chain of prefetches runs through every cell
    n_blk = jnp.maximum(cdiv(hi - lo, block), 1)
    q = q_ref[0].astype(jnp.float32) / jnp.sqrt(jnp.float32(q_ref.shape[-1]))
    q = q.astype(k_buf.dtype)                      # (n_kv*rows, dh)
    shape = (q.shape[0], block * span)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    same_head = row // rows == col % n_kv
    key = col // n_kv                              # position in the block
    qpos = cur_ref[b] + (row % rows) // group
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, carry):
        buf = (first + i) % 2
        last = i + 1 == n_blk
        nxt = jnp.minimum(jnp.where(last, b + 1, b), n_slots - 1)

        @pl.when(jnp.logical_not(last) | (b + 1 < n_slots))
        def _prefetch():
            start(nxt, jnp.where(last, 0, i + 1), 1 - buf)

        n_copied = jnp.int32(0)
        for p, page, copied in pages(b, i):
            @pl.when(copied)
            def _():
                for c in copies(p, page, buf):
                    c.wait()
            live_ref[:, p * span:(p + 1) * span] = jnp.broadcast_to(
                copied.astype(jnp.int32), (1, span))
            n_copied += copied.astype(jnp.int32)

        @pl.when(n_copied > 0)
        def _reduce():
            k = k_buf[buf]                         # (block*span, dh)
            v = v_buf[buf]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            base = (lo + i * block) * page_size    # position of key 0
            valid = same_head & (live_ref[...] != 0) & (key + base <= qpos)
            if window is not None:
                valid = valid & (key + base > qpos - window)
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[...]                    # (n_kv*rows, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            scale = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * scale + p.sum(axis=-1, keepdims=True)
            m_ref[...] = m_new
            acc_ref[...] = acc_ref[...] * scale + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        return carry

    jax.lax.fori_loop(0, n_blk, body, 0)
    slot_ref[0] = (first + n_blk) % 2
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_kernel(
    q, k_pool, v_pool, page_table, cur_pos, layer, *, group: int,
    window=None, interpret: bool = False,
):
    """q: (B, Hkv, R, dh) — R = W*group window-major query rows per kv
    head (R = group for single-token decode); k_pool/v_pool: the stacked
    pools (n_layers, n_pages + 1, ps, Hkv, dh); page_table: (B, max_pages)
    int32 physical page per logical page, -1 = unmapped; cur_pos: (B,)
    int32 position of the first query row; layer: (1,) int32, the pool of
    the stack to attend; ``window``: the layer's sliding window, None =
    full.  Returns (B, Hkv, R, dh)."""
    B, Hkv, rows, dh = q.shape
    n_layers, n_pages, page_size = k_pool.shape[:3]
    n_pages -= 1                                   # the trash page
    max_pages = page_table.shape[1]
    nq = Hkv * rows
    span = page_size * Hkv
    walk = max_pages
    if window is not None:                         # pages a window overlaps
        walk = min(cdiv(window + rows // group - 1, page_size) + 1, walk)
    block = pages_per_block(walk, span * dh * k_pool.dtype.itemsize,
                            nq * span * 4, span)

    kern = functools.partial(
        _paged_kernel, page_size=page_size, n_pages=n_pages,
        max_pages=max_pages, n_kv=Hkv, rows=rows, group=group, window=window,
        block=block,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, nq, dh), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),     # the pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nq, dh), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block * span, dh), k_pool.dtype),   # K blocks
            pltpu.VMEM((2, block * span, dh), v_pool.dtype),   # V blocks
            pltpu.VMEM((1, block * span), jnp.int32),  # column copied?
            pltpu.VMEM((nq, 1), jnp.float32),      # m
            pltpu.VMEM((nq, 1), jnp.float32),      # l
            pltpu.VMEM((nq, dh), jnp.float32),     # acc
            pltpu.SemaphoreType.DMA((2, 2)),       # (K|V, buffer)
            pltpu.SMEM((1,), jnp.int32),           # buffer of next block 0
        ],
    )
    view = (n_layers, n_pages + 1, span, dh)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cur_pos, layer, q.reshape(B, nq, dh),
      k_pool.reshape(view), v_pool.reshape(view))
    return out.reshape(B, Hkv, rows, dh)
