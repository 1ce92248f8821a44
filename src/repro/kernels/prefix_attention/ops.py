"""jit'd public wrapper for prefix-context flash attention (model layout).

``prefix_flash_attention`` is what ``models.attention.self_attention``
dispatches to when ``prefix_kv`` is set and ``impl == "pallas"``: suffix
queries attend to the cached prefix K/V plus the fresh suffix K/V without
ever concatenating the two (the XLA path's per-layer concat copy).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import default_interpret
from .kernel import prefix_flash_attention_kernel


@functools.partial(
    jax.jit, static_argnames=("q_offset", "window", "block_q", "block_k",
                              "interpret"))
def prefix_flash_attention(
    q, pk, pv, k, v, *, q_offset: int = 0, window: Optional[int] = None,
    block_q: int = 512, block_k: int = 512,
    interpret: Optional[bool] = None,
):
    """q: (B, Sq, H, dh); pk/pv: (B, Lp, Hkv, dh); k/v: (B, Sk, Hkv, dh).
    Query row i is suffix position ``q_offset + i`` (chunked admission);
    it sees the full prefix and suffix cols ``<= q_offset + i``, or with
    ``window`` = W the positions of ``[prefix; suffix]`` within W of it.
    Returns (B, Sq, H, dh)."""
    interpret = default_interpret() if interpret is None else interpret
    B, Sq, H, dh = q.shape
    qt = jnp.swapaxes(q, 1, 2)              # (B, H, Sq, dh)
    pkt = jnp.swapaxes(pk, 1, 2)
    pvt = jnp.swapaxes(pv, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    bq = min(block_q, Sq)
    pad_q = (-Sq) % bq
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    out = prefix_flash_attention_kernel(
        qt, pkt, pvt, kt, vt, q_offset=q_offset, window=window,
        block_q=bq, block_k=block_k, interpret=interpret,
    )
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.swapaxes(out, 1, 2)
