"""Prefix-context flash-attention Pallas TPU kernel (suffix prefill).

Shared-prefix admission runs only the uncached tail of a prompt: suffix
queries attend to ``[cached prefix K/V ; fresh suffix K/V]``.  The XLA path
concatenates the two before its flash scan — a full extra copy of the
prefix context per layer.  This kernel keeps the two operands separate and
never materializes the concat: the innermost grid axis runs
``n_kp + n_ks`` steps, the first ``n_kp`` streaming prefix blocks, the rest
suffix blocks.  Each operand has its own BlockSpec whose index map *clamps*
into its own array during the other phase (consecutive equal block indices
make Pallas skip the re-fetch, so the idle operand costs one stale block in
VMEM, not bandwidth).

Masking: every prefix position precedes every suffix query row, so the
prefix phase needs only the padding mask (``col < Lp``); the suffix phase
applies the standard causal mask in suffix-local coordinates
(``col <= row + q_offset``), which is exactly rows ``[Lp:]`` of the
full-sequence causal attention — the cached==cold identity contract.
Online-softmax scratch (m, l, acc) is carried across both phases, as in
``kernels/flash_attention``.

A windowed layer (``window`` = W) also masks cols at or behind
``row - W`` (absolute positions, the prefix first): prefix blocks wholly
behind a query block's window are skipped, and their index map clamps to
the block where the window starts, so they are not fetched either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import NEG_INF, cdiv


def _pfx_kernel(
    q_ref, pk_ref, pv_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, lp: int, sk: int, q_offset: int, window,
    block_q: int, block_kp: int, block_ks: int, n_kp: int, n_k: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                # (bq, dh)

    def online_update(k, v, mask):
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / jnp.sqrt(jnp.float32(q.shape[-1]))          # (bq, bk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * scale + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * scale + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    # suffix-local coordinates; tile-level causal skip as in flash_attention
    q_lo = qi * block_q + q_offset
    k_lo = (ki - n_kp) * block_ks

    in_prefix = ki < n_kp
    if window is not None:      # skip prefix blocks behind the window
        in_prefix = jnp.logical_and(
            in_prefix, (ki + 1) * block_kp - 1 > lp + q_lo - window)

    @pl.when(in_prefix)
    def _prefix_phase():
        # every prefix col precedes every suffix row: padding mask only
        cols = ki * block_kp + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kp), 1)
        mask = cols < lp
        if window is not None:
            rows = lp + q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kp), 0)
            mask = jnp.logical_and(mask, cols > rows - window)
        online_update(pk_ref[0, 0].astype(jnp.float32),
                      pv_ref[0, 0].astype(jnp.float32), mask)

    @pl.when(jnp.logical_and(ki >= n_kp, k_lo <= q_lo + block_q - 1))
    def _suffix_phase():
        rows = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_ks), 0)
        cols = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_ks), 1)
        mask = jnp.logical_and(cols < sk, cols <= rows)
        if window is not None:
            mask = jnp.logical_and(mask, cols > rows - window)
        online_update(k_ref[0, 0].astype(jnp.float32),
                      v_ref[0, 0].astype(jnp.float32), mask)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def prefix_flash_attention_kernel(
    q, pk, pv, k, v, *, q_offset: int = 0, window=None,
    block_q: int = 512, block_k: int = 512, interpret: bool = False,
):
    """q: (B, H, Sq, dh); pk/pv: (B, Hkv, Lp, dh); k/v: (B, Hkv, Sk, dh)
    → (B, H, Sq, dh).  Suffix rows are causal with offset ``Lp + q_offset``
    over the virtual concat [prefix; suffix]; Sq must divide into block_q
    (the ops wrapper pads)."""
    B, H, Sq, dh = q.shape
    Hkv, Lp = pk.shape[1], pk.shape[2]
    Sk = k.shape[2]
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_kp = min(block_k, Lp)
    block_ks = min(block_k, Sk)
    n_q = cdiv(Sq, block_q)
    n_kp = cdiv(Lp, block_kp)
    n_ks = cdiv(Sk, block_ks)
    n_k = n_kp + n_ks
    assert Sq % block_q == 0, (Sq, block_q)
    pad_p = n_kp * block_kp - Lp
    if pad_p:
        pk = jnp.pad(pk, ((0, 0), (0, 0), (0, pad_p), (0, 0)))
        pv = jnp.pad(pv, ((0, 0), (0, 0), (0, pad_p), (0, 0)))
    pad_s = n_ks * block_ks - Sk
    if pad_s:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_s), (0, 0)))

    grid = (B, H, n_q, n_k)
    kern = functools.partial(
        _pfx_kernel, lp=Lp, sk=Sk, q_offset=q_offset, window=window,
        block_q=block_q,
        block_kp=block_kp, block_ks=block_ks, n_kp=n_kp, n_k=n_k,
    )
    # clamped index maps: during the other phase an operand re-presents its
    # previous block (same index -> no DMA), so phases don't double-fetch
    pfx_map = lambda b, h, qi, ki: (b, h // group,
                                    jnp.minimum(ki, n_kp - 1), 0)
    if window is not None:
        # the first prefix block inside query block qi's window
        def pfx_map(b, h, qi, ki):
            lo = (Lp + qi * block_q + q_offset - window + 1) // block_kp
            return (b, h // group,
                    jnp.clip(ki, jnp.clip(lo, 0, n_kp - 1), n_kp - 1), 0)
    sfx_map = lambda b, h, qi, ki: (b, h // group,
                                    jnp.clip(ki - n_kp, 0, n_ks - 1), 0)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kp, dh), pfx_map),
            pl.BlockSpec((1, 1, block_kp, dh), pfx_map),
            pl.BlockSpec((1, 1, block_ks, dh), sfx_map),
            pl.BlockSpec((1, 1, block_ks, dh), sfx_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # m
            pltpu.VMEM((block_q, 1), jnp.float32),     # l
            pltpu.VMEM((block_q, dh), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(q, pk, pv, k, v)
