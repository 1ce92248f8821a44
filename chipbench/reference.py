"""Plain reference of a Qwen3 decoder, and the comparison that decides
``correct``.

A straightforward ``jax.numpy`` forward pass in float32 under
``jax.default_matmul_precision("highest")``: RMSNorm, per-head q/k RMSNorm,
rotary embedding (theta from the config, the half-split rotation of the
published model), grouped-query causal attention, SwiGLU, a tied or untied
LM head.  No cache, no batching, no kernels; nothing of the serving program
is imported.  Weights are made again from the seed (``chipbench/weights.py``)
one layer at a time, so the reference fits beside nothing else on the chip.

``precision="fp8"`` is the control: every matrix product (projections,
attention scores and values, LM head) takes its operands rounded to
float8_e4m3 with a scale per row or column, the step below the bfloat16
the configurations state.  It must fail the comparison.

The comparison: for each served token, the gap by which the reference's
logit of that token lies below the reference's best logit at that position.
The widest gap over a sample of served requests is held against the cell's
limit.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weights import Dims, embedding, final_norm, layer_weights, lm_head, \
    root_key

BUCKET = 512            # sequences are padded at the end to a multiple
Q_BLOCK = 512           # attention queries per block
V_BLOCK = 16384         # LM-head rows per block
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8_e4m3 with one scale per slice along ``axis``
    (the contraction axis)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(_F8).astype(jnp.float32) * s


def _mm(a, b, prec):
    """a (..., k) @ b (k, n)."""
    if prec == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return a @ b


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, heads, dh); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, prec):
    """Causal GQA.  q (S, H, dh); k, v (S, Hkv, dh) -> (S, H, dh)."""
    S, H, dh = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if prec == "fp8":
        k, v = _q8(k, -1), _q8(v, 0)
    col = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        if prec == "fp8":
            qb = _q8(qb, -1)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dh ** -0.5
        row = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(col[None, None, :] <= row[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if prec == "fp8":
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, H, dh)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(key, layer, x, dims: Dims, prec: str):
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, layer, dims))
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], dims.eps)
    q = _mm(h, w["wq"], prec).reshape(S, dims.n_heads, dims.d_head)
    k = _mm(h, w["wk"], prec).reshape(S, dims.n_kv, dims.d_head)
    v = _mm(h, w["wv"], prec).reshape(S, dims.n_kv, dims.d_head)
    q = _rope(_rms(q, w["q_norm"], dims.eps), pos, dims.theta)
    k = _rope(_rms(k, w["k_norm"], dims.eps), pos, dims.theta)
    a = _attention(q, k, v, prec).reshape(S, -1)
    x = x + _mm(a, w["wo"], prec)
    h = _rms(x, w["ln2"], dims.eps)
    f = jax.nn.silu(_mm(h, w["gate"], prec)) * _mm(h, w["up"], prec)
    return x + _mm(f, w["down"], prec)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(key, tokens, dims: Dims):
    return embedding(key, dims)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(key, x, rows, dims: Dims, prec: str):
    """Logits (len(rows), vocab) at positions ``rows`` of ``x``."""
    h = _rms(x[rows], final_norm(key, dims).astype(jnp.float32), dims.eps)
    w = lm_head(key, dims)
    n = -(-dims.vocab // V_BLOCK)
    w = jnp.pad(w, ((0, n * V_BLOCK - dims.vocab), (0, 0)))

    def block(i):
        wb = jax.lax.dynamic_slice_in_dim(w, i * V_BLOCK, V_BLOCK)
        return _mm(h, wb.astype(jnp.float32).T, prec)

    out = jax.lax.map(block, jnp.arange(n))            # (n, P, V_BLOCK)
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)[:, :dims.vocab]


def logits(dims: Dims, seed: int, tokens: Sequence[int], rows: Sequence[int],
           precision: str = "f32") -> np.ndarray:
    """Reference logits (len(rows), vocab), float32, of the sequence
    ``tokens`` at positions ``rows`` (each the logits that predict the token
    after that position)."""
    key = root_key(seed)
    toks = np.asarray(tokens, np.int32)
    S = -(-len(toks) // BUCKET) * BUCKET
    padded = np.zeros(S, np.int32)
    padded[:len(toks)] = toks               # causal: the tail changes nothing
    with jax.default_matmul_precision("highest"):
        x = _embed(key, jnp.asarray(padded), dims)
        for layer in range(dims.n_layers):
            x = _layer(key, jnp.int32(layer), x, dims, precision)
        out = _head(key, x, jnp.asarray(np.asarray(rows, np.int32)), dims,
                    precision)
    return np.asarray(jax.device_get(out), np.float32)


def served_rows(prompt: Sequence[int], out: Sequence[int]):
    """The sequence the reference reads for a request that served ``out``
    after ``prompt``, and the positions whose logits predict each served
    token."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(out[:-1], np.int32)])
    first = len(prompt) - 1
    return seq, np.arange(first, first + len(out))


def token_gaps(ref: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """Per position: reference best logit minus the reference logit of the
    token given there."""
    tokens = np.asarray(tokens)
    return ref.max(-1) - ref[np.arange(len(tokens)), tokens]


def served_gaps(dims: Dims, seed: int, requests) -> List[np.ndarray]:
    """Gap of every served token, per request; ``requests`` are
    ``(prompt, out)`` pairs."""
    out = []
    for prompt, toks in requests:
        seq, rows = served_rows(prompt, toks)
        out.append(token_gaps(logits(dims, seed, seq, rows), toks))
    return out


def control_gaps(dims: Dims, seed: int, requests) -> List[np.ndarray]:
    """The control: at each position of the same sequences, the gap (under
    the float32 reference) of the token the float8 computation puts
    first."""
    out = []
    for prompt, toks in requests:
        seq, rows = served_rows(prompt, toks)
        ref = logits(dims, seed, seq, rows)
        low = logits(dims, seed, seq, rows, precision="fp8")
        out.append(token_gaps(ref, low.argmax(-1)))
    return out
