"""Decode-attention (flash-decoding) Pallas TPU kernel.

One new token per sequence attends to a ring-buffer KV cache.  Decode is
memory-bandwidth-bound (every KV byte is read once per token), so the kernel
is organized to stream K/V through VMEM in large contiguous blocks:

Grid = (B, Hkv, nC): each cell owns one (batch, kv-head) pair; the C
(cache-slot) axis is innermost and carries online-softmax scratch across
steps exactly like the prefill kernel.  All ``group`` q-heads that share the
kv head ride along in the same cell — they reuse the streamed K/V block from
VMEM ``group`` times, which is the GQA arithmetic-intensity win (paper
Eq. 2's ICP/OCP reuse, transposed to the memory hierarchy).

Validity masking comes from the stored absolute positions (``pos`` array) —
this is what makes the ring buffer work without data movement: a slot is
attendable iff ``0 <= pos[slot] <= cur_pos`` (and within the sliding window
if one is configured).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF, cdiv


def _dec_kernel(
    q_ref, k_ref, v_ref, pos_ref, cur_ref, o_ref, m_ref, l_ref, acc_ref,
    *, window: Optional[int], block_c: int, n_c: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                # (group, dh)
    k = k_ref[0, 0].astype(jnp.float32)                # (bc, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = pos_ref[0]                                   # (1, bc)
    cur = cur_ref[0]                                   # (1, 1)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(q.shape[-1]))             # (group, bc)

    valid = jnp.logical_and(pos >= 0, pos <= cur)
    if window is not None:
        valid = jnp.logical_and(valid, pos > cur - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # (group, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    scale = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * scale + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * scale + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )

    @pl.when(ci == n_c - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(
    q, k, v, pos, cur_pos, *, window: Optional[int] = None,
    block_c: int = 1024, interpret: bool = False,
):
    """q: (B, Hkv, group, dh); k/v: (B, Hkv, C, dh); pos: (B, C);
    cur_pos: (B,) int32 → (B, Hkv, group, dh).

    ``pos`` and ``cur_pos`` ride in as (B, 1, C) and (B, 1, 1) so their
    blocks' last two dims are (1, block_c) and (1, 1) against array dims
    (1, C) and (1, 1) — legal for the TPU's tiling, where a (1, block_c)
    block over (B, C) is not.  VMEM per cell: K and V blocks
    double-buffered ``4 * block_c * dh * E`` (E = cache bytes per element)
    plus their f32 copies ``2 * block_c * dh * 4`` — 1 MiB + 1 MiB at
    block_c 1024, dh 128, bf16."""
    B, Hkv, group, dh = q.shape
    C = k.shape[2]
    block_c = min(block_c, C)
    n_c = cdiv(C, block_c)
    pad = n_c * block_c - C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)

    grid = (B, Hkv, n_c)
    kern = functools.partial(_dec_kernel, window=window, block_c=block_c, n_c=n_c)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, dh), lambda b, h, ci: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_c, dh), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, block_c, dh), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, block_c), lambda b, h, ci: (b, 0, ci)),
            pl.BlockSpec((1, 1, 1), lambda b, h, ci: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh), lambda b, h, ci: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, pos[:, None, :], cur_pos.reshape(B, 1, 1))
