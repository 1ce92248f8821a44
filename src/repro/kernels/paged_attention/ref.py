"""Pure-jnp oracle: the materialized-gather paged attention of the XLA path
(``models.attention._paged_attn_xla`` semantics, pool layout in/out)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import NEG_INF


def paged_decode_attention_ref(q, k_pool, v_pool, page_table, cur_pos,
                               window=None):
    """Same signature as ``ops.paged_decode_attention``: gather the slot's
    pages into a flat (B, max_pages*ps, Hkv, dh) view, mask to mapped pages
    and positions <= cur_pos (and > cur_pos - window), masked softmax."""
    B, H, dh = q.shape
    n_pages = k_pool.shape[0] - 1
    ps = k_pool.shape[1]
    Hkv = k_pool.shape[2]
    group = H // Hkv
    maxp = page_table.shape[1]
    L = maxp * ps

    gather = jnp.where(page_table >= 0, page_table, n_pages)
    kg = k_pool[gather].reshape(B, L, Hkv, dh)
    vg = v_pool[gather].reshape(B, L, Hkv, dh)
    pos = jnp.arange(L, dtype=jnp.int32)
    valid = (page_table >= 0)[:, pos // ps] & (pos[None, :] <= cur_pos[:, None])
    if window is not None:
        valid &= pos[None, :] > cur_pos[:, None] - window

    qg = (q.reshape(B, Hkv, group, dh) / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bgid,bkgd->bgik", qg, kg,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgik,bkgd->bgid", w.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, dh).astype(q.dtype)


def paged_verify_attention_ref(q, k_pool, v_pool, page_table, cur_pos,
                               window=None):
    """Multi-query twin of :func:`paged_decode_attention_ref`: query ``w``
    sits at absolute position ``cur_pos + w`` and attends mapped keys at
    positions ``<= cur_pos + w``.  Same signature/layout as
    ``ops.paged_verify_attention``."""
    B, W, H, dh = q.shape
    n_pages = k_pool.shape[0] - 1
    ps = k_pool.shape[1]
    Hkv = k_pool.shape[2]
    group = H // Hkv
    maxp = page_table.shape[1]
    L = maxp * ps

    gather = jnp.where(page_table >= 0, page_table, n_pages)
    kg = k_pool[gather].reshape(B, L, Hkv, dh)
    vg = v_pool[gather].reshape(B, L, Hkv, dh)
    pos = jnp.arange(L, dtype=jnp.int32)
    q_pos = cur_pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    valid = ((page_table >= 0)[:, pos // ps][:, None, :]
             & (pos[None, None, :] <= q_pos[:, :, None]))          # (B, W, L)
    if window is not None:
        valid &= pos[None, None, :] > q_pos[:, :, None] - window

    qg = (q.reshape(B, W, Hkv, group, dh)
          / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bwgid,bkgd->bwgik", qg, kg,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bwgik,bkgd->bwgid", w.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, W, H, dh).astype(q.dtype)
