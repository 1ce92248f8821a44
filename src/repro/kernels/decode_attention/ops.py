"""jit'd public wrapper for decode attention (model layout adapters)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common import default_interpret
from .kernel import decode_attention_kernel


@functools.partial(jax.jit, static_argnames=("window", "block_c", "interpret"))
def decode_attention(
    q, k, v, pos, cur_pos, *, window: Optional[int] = None,
    block_c: int = 1024, interpret: Optional[bool] = None,
):
    """q: (B, H, dh); k/v: (B, C, Hkv, dh); pos: (B, C); cur_pos: (B,).
    Returns (B, H, dh)."""
    interpret = default_interpret() if interpret is None else interpret
    B, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, dh)
    kt = jnp.swapaxes(k, 1, 2)            # (B, Hkv, C, dh)
    vt = jnp.swapaxes(v, 1, 2)
    out = decode_attention_kernel(
        qg, kt, vt, pos, cur_pos.astype(jnp.int32),
        window=window, block_c=block_c, interpret=interpret,
    )
    return out.reshape(B, H, dh)
