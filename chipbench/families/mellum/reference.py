"""Plain reference of a Mellum2 decoder's expert share, and the comparison
that decides ``correct``.

A straightforward ``jax.numpy`` forward pass in float32 under
``jax.default_matmul_precision("highest")``, layer by layer, weights made
again from the seed (``weights.py``): RMSNorm; grouped-query attention,
causal, and on a ``sliding_attention`` layer limited to the last
``sliding_window`` positions (a key at distance >= the window is masked);
the rotary embedding of the layer's kind (default, or YaRN as Hugging Face
computes it: frequencies ramped between the ``beta_fast`` and ``beta_slow``
correction dims, cos and sin scaled by ``attention_factor``); the router in
float32 over all published experts, softmax, top-k, renormalised; the held
experts computed densely, each weighted by its routing weight (zero where
the token did not pick it); the untied LM head.  What the absent experts
would add is left out, as on the chip.  No cache, no batching, no kernels;
nothing of the serving program is imported.

``precision="fp8"`` is the control: every matrix product (projections,
router, experts, attention, LM head) takes its operands rounded to
float8_e4m3, as in the Qwen3 family.  The comparison is the Qwen3 family's
(``served_rows``, ``token_gaps``).

Besides the gaps, each compared request logs how many of its routing
decisions (position, layer, expert) change when the router's input is
rounded to bfloat16, the program's type: where the 8th and 9th experts lie
that close, the program may route otherwise than the reference.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from families.qwen3 import reference as q3
from families.qwen3.weights import root_key

from .weights import Dims, held_experts, layer_weights

BUCKET = q3.BUCKET
Q_BLOCK = q3.Q_BLOCK
ROW_BUCKET = 128        # LM-head rows per compiled shape


def rope(x, pos, theta, yarn):
    """x (S, heads, dh); the half-split rotation, YaRN-scaled when ``yarn``
    = (factor, original positions, beta_fast, beta_slow, attention
    factor)."""
    if yarn is None:
        return q3.rope(x, pos, theta)
    factor, orig, fast, slow, scale = yarn
    dh = x.shape[-1]
    half = dh // 2

    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                     dh - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    extrap = 1.0 / theta ** (i * 2 / dh)
    e = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = extrap / factor * (1.0 - e) + extrap * e
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = (jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, prec, window):
    """Causal GQA, keys within ``window`` of the query when set.
    q (S, H, dh); k, v (S, Hkv, dh) -> (S, H, dh)."""
    S, H, dh = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if prec == "fp8":
        k, v = q3._q8(k, -1), q3._q8(v, 0)
    col = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        if prec == "fp8":
            qb = q3._q8(qb, -1)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dh ** -0.5
        row = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[None, :, None]
        ok = col[None, None, :] <= row
        if window is not None:
            ok &= col[None, None, :] > row - window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        if prec == "fp8":
            p = q3._q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H, dh)


def route(h, router, k):
    """Top-``k`` experts of each row and their renormalised weights."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    return top_p / top_p.sum(-1, keepdims=True), top_i


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(key, layer, x, dims: Dims, prec: str, kind: tuple):
    """One layer of ``kind`` = (window, theta, yarn); also the number of
    routing decisions that the router's input rounded to bfloat16
    changes."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     layer_weights(key, layer, dims))
    S = x.shape[0]
    pos = jnp.arange(S)
    window, theta, yarn = kind
    h = q3.rms(x, w["ln1"], dims.eps)
    q = q3.mm(h, w["wq"], prec).reshape(S, dims.n_heads, dims.d_head)
    k = q3.mm(h, w["wk"], prec).reshape(S, dims.n_kv, dims.d_head)
    v = q3.mm(h, w["wv"], prec).reshape(S, dims.n_kv, dims.d_head)
    a = attention(rope(q, pos, theta, yarn), rope(k, pos, theta, yarn), v,
                  prec, window).reshape(S, -1)
    x = x + q3.mm(a, w["wo"], prec)

    h = q3.rms(x, w["ln2"], dims.eps)
    if prec == "fp8":
        top_p, top_i = route(q3._q8(h, -1), q3._q8(w["router"], 0),
                             dims.top_k)
    else:
        top_p, top_i = route(h, w["router"], dims.top_k)
    _, low_i = route(h.astype(jnp.bfloat16).astype(jnp.float32), w["router"],
                     dims.top_k)
    flips = dims.top_k - (top_i[:, :, None] == low_i[:, None, :]).sum((1, 2))

    experts = jax.tree.map(lambda a: a.astype(jnp.float32),
                           held_experts(key, layer, dims))

    def expert(acc, i):
        e = jax.tree.map(lambda a: a[i], experts)
        wt = jnp.where(top_i == dims.first_expert + i, top_p, 0.0).sum(-1)
        f = jax.nn.silu(q3.mm(h, e["gate"], prec)) * q3.mm(h, e["up"], prec)
        return acc + wt[:, None] * q3.mm(f, e["down"], prec), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(dims.held))
    return x + y, flips


def logits(dims: Dims, seed: int, tokens, rows, precision: str = "f32",
           n_real: int = None):
    """Reference logits (len(rows), vocab), float32, of the sequence
    ``tokens`` at positions ``rows``, and the routing decisions of its
    first ``n_real`` positions (all by default) that bfloat16 rounding of
    the router's input changes."""
    key = root_key(seed)
    toks = np.asarray(tokens, np.int32)
    n_real = len(toks) if n_real is None else n_real
    S = -(-len(toks) // BUCKET) * BUCKET
    padded = np.zeros(S, np.int32)
    padded[:len(toks)] = toks               # causal: the tail changes nothing
    flips = 0
    with jax.default_matmul_precision("highest"):
        x = q3.embed(key, jnp.asarray(padded), dims)
        for layer in range(dims.n_layers):
            kind = (dims.windows[layer],) + dims.ropes[layer]
            x, f = _layer(key, jnp.int32(layer), x, dims, precision, kind)
            flips += int(jnp.sum(f[:n_real]))
        # rows padded to a bucket (repeating the last), so that the head
        # compiles once per bucket and not once per served length
        rows = np.asarray(rows, np.int32)
        padded_rows = np.full(-(-len(rows) // ROW_BUCKET) * ROW_BUCKET,
                              rows[-1], np.int32)
        padded_rows[:len(rows)] = rows
        out = q3.head(key, x, jnp.asarray(padded_rows), dims, precision)
    return np.asarray(jax.device_get(out), np.float32)[:len(rows)], flips


def served_gaps(dims: Dims, seed: int, requests) -> List[np.ndarray]:
    """Gap of every served token, per request; ``requests`` are
    ``(prompt, out)`` pairs.  Logs the routing decisions at a near-tie."""
    out, flips = [], []
    for prompt, toks in requests:
        seq, rows = q3.served_rows(prompt, toks)
        ref, f = logits(dims, seed, seq, rows)
        out.append(q3.token_gaps(ref, toks))
        flips.append(f)
    n = sum(len(q3.served_rows(p, t)[0]) for p, t in requests)
    print(f"routing decisions changed by bfloat16 router input: "
          f"{sum(flips)} of {n * dims.n_layers * dims.top_k} "
          f"(per request: {' '.join(map(str, flips))})", flush=True)
    return out


def control_gaps(dims: Dims, seed: int, requests) -> List[np.ndarray]:
    """The control: at each position of the same sequences, the gap (under
    the float32 reference) of the token the float8 computation puts
    first."""
    out = []
    for prompt, toks in requests:
        seq, rows = q3.served_rows(prompt, toks)
        ref, _ = logits(dims, seed, seq, rows)
        low, _ = logits(dims, seed, seq, rows, precision="fp8")
        out.append(q3.token_gaps(ref, low.argmax(-1)))
    return out
