"""Primitive layers: explicit-pytree params, pure-functional apply.

No flax/haiku — params are nested dicts of jnp arrays so that
``jax.eval_shape(init_params, ...)`` yields allocation-free
ShapeDtypeStructs for the multi-pod dry-run, and sharding rules can be
written as path-pattern → PartitionSpec tables.

All linear layers are bias-free (every assigned arch is no-bias except the
Whisper stub, where we follow the same convention and note it in DESIGN.md).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Linear / embedding / norms
# ---------------------------------------------------------------------------


def init_dense(key, d_in: int, d_out: int, dtype="bfloat16", scale: float | None = None):
    scale = (1.0 / np.sqrt(d_in)) if scale is None else scale
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    return {"w": w.astype(_dtype(dtype))}


def dense(params, x):
    return x @ params["w"]


def init_embedding(key, vocab: int, d_model: int, dtype="bfloat16"):
    w = jax.random.normal(key, (vocab, d_model), dtype=jnp.float32) * 0.02
    return {"w": w.astype(_dtype(dtype))}


def embed(params, tokens):
    return jnp.take(params["w"], tokens, axis=0)


def init_rmsnorm(d: int, dtype="bfloat16"):
    return {"scale": jnp.ones((d,), dtype=_dtype(dtype))}


def rmsnorm(params, x, *, eps: float = 1e-6):
    """RMSNorm in f32 accumulation, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def gated_rmsnorm(params, x, gate, *, eps: float = 1e-6):
    """Mamba-2 output norm: RMSNorm(x * silu(gate))."""
    return rmsnorm(params, x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype), eps=eps)


# ---------------------------------------------------------------------------
# MLP: SwiGLU (fused gate+up, Llama family) or plain 2-matrix GELU
# (StarCoder2 / Whisper — keeps their assigned d_ff param counts faithful)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype="bfloat16", *, kind: str = "swiglu"):
    k1, k2 = jax.random.split(key)
    wi_out = 2 * d_ff if kind == "swiglu" else d_ff   # swiglu: [gate | up]
    return {
        "wi": init_dense(k1, d_model, wi_out, dtype)["w"],
        "wo": init_dense(k2, d_ff, d_model, dtype)["w"],
    }


def mlp(params, x, *, kind: str = "swiglu"):
    h = x @ params["wi"]
    if kind == "swiglu":
        gate, up = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(h)
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> np.ndarray:
    """Inverse frequencies for half the head dim (host constant)."""
    half = d_head // 2
    return 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))


def yarn_bounds(d_head: int, theta: float, yarn) -> Tuple[int, int]:
    """(low, high): the frequency indices between which YaRN ramps from
    extrapolation to interpolation — the dims whose wavelength over the
    original context completes ``beta_fast`` and ``beta_slow`` rotations."""
    def dim(rotations):
        return (d_head * math.log(yarn.original_max_position
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), d_head - 1)
    return low, high


def yarn_freqs(d_head: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """YaRN inverse frequencies for half the head dim, and the factor cos
    and sin are scaled by (host constants)."""
    extrap = rope_freqs(d_head, theta)
    interp = extrap / yarn.factor
    low, high = yarn_bounds(d_head, theta, yarn)
    ramp = np.clip((np.arange(d_head // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    e = 1.0 - ramp                        # 1: extrapolate, 0: interpolate
    mscale = yarn.attention_factor
    if mscale is None:
        mscale = 0.1 * math.log(yarn.factor) + 1.0
    return interp * (1.0 - e) + extrap * e, float(mscale)


def apply_rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S) int32.
    ``yarn`` (a ``configs.base.Yarn``) rescales the frequencies and the
    rotation's magnitude."""
    d_head = x.shape[-1]
    if yarn is None:
        freqs, mscale = rope_freqs(d_head, theta), None
    else:
        freqs, mscale = yarn_freqs(d_head, theta, yarn)
    inv = jnp.asarray(freqs, dtype=jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * inv          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                              # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if mscale is not None:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(d_head: int) -> Tuple[int, int, int]:
    """Qwen2-VL splits the rotary half-dim into (temporal, h, w) sections;
    128-dim heads use (16, 24, 24).  Generalized proportionally."""
    half = d_head // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return t, h, w


def apply_mrope(x, positions3, theta: float):
    """M-RoPE: positions3 is (3, ..., S) — (temporal, height, width) ids.
    Each rotary-frequency section uses its own position stream."""
    d_head = x.shape[-1]
    inv = jnp.asarray(rope_freqs(d_head, theta), dtype=jnp.float32)
    sec = mrope_sections(d_head)
    # section index per frequency: 0,0,...,1,1,...,2,2,...
    sec_id = jnp.asarray(
        np.concatenate([np.full(s, i) for i, s in enumerate(sec)]), dtype=jnp.int32
    )                                                              # (half,)
    # pos: (3, ..., S) -> select per-frequency stream -> (..., S, half)
    pos = jnp.moveaxis(positions3, 0, -1)                          # (..., S, 3)
    pos_f = jnp.take(pos.astype(jnp.float32), sec_id, axis=-1)     # (..., S, half)
    ang = pos_f * inv                                              # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, d_model: int) -> np.ndarray:
    """Whisper-style sinusoidal embeddings (host constant)."""
    half = d_model // 2
    freq = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    pos = np.arange(n_pos)[:, None] * freq[None, :]
    return np.concatenate([np.sin(pos), np.cos(pos)], axis=1).astype(np.float32)
