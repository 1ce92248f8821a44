"""Attention: GQA + qk_norm + RoPE/M-RoPE + sliding window + KV cache decode.

Three compute paths, selected by ``impl``:

* ``"xla"``     — chunked online-softmax attention in pure JAX (lax.scan over
  KV blocks).  This is the default for lowering/dry-run: peak memory is
  O(S·block) instead of O(S²), and the HLO stays small.  It is also the
  numerical oracle for the Pallas kernel.
* ``"pallas"``  — Pallas TPU kernels, validated in interpret mode: dense
  prefill (``repro.kernels.flash_attention``), dense decode
  (``repro.kernels.decode_attention``), paged decode
  (``repro.kernels.paged_attention`` — walks the page table inside the
  kernel), and prefix-context prefill (``repro.kernels.prefix_attention``
  — attends to cached-prefix + fresh-suffix K/V without the concat).
* ``"naive"``   — materialized-scores einsum, used only by tiny tests.

Which impl is legal for which mode is owned by :data:`ATTN_CAPABILITIES`
(checked at serving-config/batcher construction via
:func:`check_attn_impl`, so a bad combination fails at build time, not
three layers deep in a jit trace).

Decode (single new token against a KV cache) uses a separate path; the
sliding-window archs keep a **ring-buffer** cache of ``min(S, window)`` slots
(the O(window) memory claim that makes long_500k runnable for Mixtral).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnKind

from .layers import apply_mrope, apply_rope, init_dense, init_rmsnorm, rmsnorm

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Capability table: which attn impl is legal for which execution mode
# ---------------------------------------------------------------------------
#
# The single source of truth for impl × mode support.  Serving configs and
# the batcher validate against this at construction, replacing the
# NotImplementedErrors that used to fire three layers deep inside a traced
# decode step.  Modes:
#   train           — differentiable prefill (self_attention under grad)
#   dense           — prefill + dense-cache decode
#   paged           — paged-pool decode (block-granular KV virtualization)
#   prefix          — suffix prefill against cached prefix K/V
#   sliding_window  — any path on an arch with a windowed layer
#   verify          — multi-query draft verification (speculative decode);
#                     "pallas" rides the paged multi-query kernel in paged
#                     mode and falls back to the XLA multi-query path on
#                     dense caches

ATTN_CAPABILITIES = {
    "train": ("xla", "flash", "pallas", "naive"),
    "dense": ("xla", "pallas", "naive"),
    "paged": ("xla", "pallas"),
    "prefix": ("xla", "pallas", "naive"),
    "sliding_window": ("xla", "pallas", "naive", "flash"),
    "verify": ("xla", "pallas"),
}


def check_attn_impl(impl: str, mode: str) -> str:
    """Validate ``impl`` against :data:`ATTN_CAPABILITIES` for ``mode``.

    Returns ``impl`` unchanged on success so callers can validate inline;
    raises ``ValueError`` naming the mode and the supported impls otherwise.
    """
    try:
        supported = ATTN_CAPABILITIES[mode]
    except KeyError:
        raise ValueError(
            f"unknown attention mode {mode!r}; "
            f"expected one of {sorted(ATTN_CAPABILITIES)}") from None
    if impl not in supported:
        raise ValueError(
            f"attn_impl={impl!r} is not supported for mode {mode!r}; "
            f"supported: {supported}")
    return impl


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg, *, cross: bool = False):
    """cfg: ModelConfig.  ``cross=True`` builds encoder-decoder cross-attn
    (no qk_norm, kv over encoder states)."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_dense(kq, cfg.d_model, cfg.q_dim, cfg.dtype)["w"],
        "wk": init_dense(kk, cfg.d_model, cfg.kv_dim, cfg.dtype)["w"],
        "wv": init_dense(kv, cfg.d_model, cfg.kv_dim, cfg.dtype)["w"],
        "wo": init_dense(ko, cfg.q_dim, cfg.d_model, cfg.dtype)["w"],
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = init_rmsnorm(cfg.d_head, cfg.dtype)
        p["k_norm"] = init_rmsnorm(cfg.d_head, cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# Core attention math (shared by all impls)
# ---------------------------------------------------------------------------


def layer_kind(cfg, kind: Optional[AttnKind] = None) -> AttnKind:
    """The attention kind a call runs: ``kind`` as the layer's position in
    the period gives it, else the model-wide one (``cfg.sliding_window``,
    ``cfg.rope_theta``), which a model with a per-layer pattern lacks."""
    if kind is not None:
        return kind
    if cfg.attn_pattern:
        raise ValueError("a per-layer attention pattern needs each layer's "
                         "kind (transformer.LayerSpec.attn)")
    return cfg.attn_kind(0)


def _project_qkv(params, x, cfg, *, positions=None, rope: bool = True,
                 kind: Optional[AttnKind] = None):
    """x: (B, S, D) -> q (B,S,H,dh), k/v (B,S,Hkv,dh), rope applied (the
    theta and YaRN scaling of ``kind``)."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm and "q_norm" in params:
        q = rmsnorm(params["q_norm"], q, eps=cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, eps=cfg.norm_eps)
    kind = layer_kind(cfg, kind)
    theta = kind.rope_theta
    if rope and theta > 0:
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        if cfg.m_rope:
            if positions.ndim == 2:   # plain (B,S) ids (e.g. text-only decode):
                positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
            q = apply_mrope(q, positions, theta)
            k = apply_mrope(k, positions, theta)
        else:
            q = apply_rope(q, positions, theta, kind.yarn)
            k = apply_rope(k, positions, theta, kind.yarn)
    return q, k, v


def _expand_kv(k, n_heads: int):
    """(B,S,Hkv,dh) -> (B,S,H,dh) by repeating each kv head (GQA)."""
    B, S, Hkv, dh = k.shape
    group = n_heads // Hkv
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, Hkv, group, dh)).reshape(
        B, S, n_heads, dh
    )


def naive_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    q_offset: int = 0):
    """Materialized-scores reference.  q: (B,Sq,H,dh); k,v: (B,Sk,Hkv,dh)."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    k = _expand_kv(k, H)
    v = _expand_kv(v, H)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(
        jnp.float32(dh)
    )
    qi = jnp.arange(Sq)[:, None] + q_offset
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return out


def chunked_flash_attention(
    q, k, v, *, causal: bool, window: Optional[int] = None,
    q_offset: int = 0, block_k: int = 512,
):
    """Online-softmax attention, lax.scan over KV blocks (pure JAX "flash").

    Peak memory O(B·H·Sq·block_k) — this is what lets 32k-prefill cells lower
    without an O(S²) score buffer.  Also the oracle for the Pallas kernel.
    """
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    block_k = min(block_k, Sk)
    n_blocks = (Sk + block_k - 1) // block_k
    pad = n_blocks * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    group = H // Hkv
    # (B, nb, bk, Hkv, dh)
    kb = k.reshape(B, n_blocks, block_k, Hkv, dh)
    vb = v.reshape(B, n_blocks, block_k, Hkv, dh)
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(dh))
    qg = qf.reshape(B, Sq, Hkv, group, dh)

    qi = jnp.arange(Sq, dtype=jnp.int32) + q_offset          # (Sq,)

    def body(carry, xs):
        m, l, acc = carry                                     # (B,Sq,Hkv,g), ..., (B,Sq,Hkv,g,dh)
        kc, vc, blk = xs                                      # (B,bk,Hkv,dh) x2, scalar
        ki = blk * block_k + jnp.arange(block_k, dtype=jnp.int32)
        s = jnp.einsum("bqgid,bkgd->bqgik", qg, kc.astype(jnp.float32))
        valid = ki[None, :] < Sk
        mask = jnp.broadcast_to(valid, (Sq, block_k))
        if causal:
            mask = mask & (ki[None, :] <= qi[:, None])
        if window is not None:
            mask = mask & (ki[None, :] > qi[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + p.sum(axis=-1)
        acc_new = acc * scale[..., None] + jnp.einsum(
            "bqgik,bkgd->bqgid", p, vc.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, group), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, group), dtype=jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, group, dh), dtype=jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)                             # (nb, B, bk, Hkv, dh)
    vb_t = jnp.moveaxis(vb, 1, 0)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb_t, vb_t, jnp.arange(n_blocks, dtype=jnp.int32))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash attention with a custom VJP (blockwise-recompute backward)
# ---------------------------------------------------------------------------
#
# The plain chunked attention above is correct but TRAINS badly: jax.grad
# through the lax.scan saves each block's (B,Sq,Hkv,g,block_k) f32 residuals
# (probabilities/scores), resurrecting the O(Sq·Sk) memory/traffic that
# flash attention exists to avoid — measured as the dominant HLO-bytes term
# of every train/prefill cell in the baseline roofline (EXPERIMENTS.md
# §Perf).  The custom VJP saves only (q, k, v, out, LSE) and recomputes each
# block's probabilities in the backward pass — the FlashAttention backward —
# making train-time attention memory O(S·block) for real.


def _flash_fwd_lse(q, k, v, *, causal, window, q_offset, block_k):
    """Forward pass that also returns the log-sum-exp (B,Sq,Hkv,g)."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    block_k = min(block_k, Sk)
    n_blocks = (Sk + block_k - 1) // block_k
    pad = n_blocks * block_k - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    group = H // Hkv
    kb = jnp.moveaxis(k.reshape(B, n_blocks, block_k, Hkv, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, n_blocks, block_k, Hkv, dh), 1, 0)
    qg = (q.astype(jnp.float32) / jnp.sqrt(jnp.float32(dh))).reshape(B, Sq, Hkv, group, dh)
    qi = jnp.arange(Sq, dtype=jnp.int32) + q_offset

    def mask_for(blk):
        ki = blk * block_k + jnp.arange(block_k, dtype=jnp.int32)
        m = jnp.broadcast_to(ki[None, :] < Sk, (Sq, block_k))
        if causal:
            m = m & (ki[None, :] <= qi[:, None])
        if window is not None:
            m = m & (ki[None, :] > qi[:, None] - window)
        return m

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, blk = xs
        s = jnp.einsum("bqgid,bkgd->bqgik", qg, kc.astype(jnp.float32))
        s = jnp.where(mask_for(blk)[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + p.sum(axis=-1)
        acc_new = acc * scale[..., None] + jnp.einsum(
            "bqgik,bkgd->bqgid", p, vc.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, Hkv, group), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, group), dtype=jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, group, dh), dtype=jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(n_blocks, dtype=jnp.int32))
    )
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).reshape(B, Sq, H, dh).astype(q.dtype)
    lse = m + jnp.log(l_safe)                        # (B,Sq,Hkv,g)
    return out, lse


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def flash_attention_vjp(q, k, v, causal, window, q_offset, block_k):
    out, _ = _flash_fwd_lse(q, k, v, causal=causal, window=window,
                            q_offset=q_offset, block_k=block_k)
    return out


def _fa_vjp_fwd(q, k, v, causal, window, q_offset, block_k):
    out, lse = _flash_fwd_lse(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, block_k=block_k)
    return out, (q, k, v, out, lse)


def _fa_vjp_bwd(causal, window, q_offset, block_k, res, do):
    q, k, v, out, lse = res
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    Hkv = k.shape[2]
    group = H // Hkv
    bk = min(block_k, Sk)
    n_blocks = (Sk + bk - 1) // bk
    pad = n_blocks * bk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sm = 1.0 / jnp.sqrt(jnp.float32(dh))
    qg = q.astype(jnp.float32).reshape(B, Sq, Hkv, group, dh)
    dog = do.astype(jnp.float32).reshape(B, Sq, Hkv, group, dh)
    og = out.astype(jnp.float32).reshape(B, Sq, Hkv, group, dh)
    # D_i = rowsum(dO * O)  — the softmax-correction term
    D = jnp.sum(dog * og, axis=-1)                   # (B,Sq,Hkv,g)
    kb = jnp.moveaxis(k.reshape(B, n_blocks, bk, Hkv, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, n_blocks, bk, Hkv, dh), 1, 0)
    qi = jnp.arange(Sq, dtype=jnp.int32) + q_offset

    def body(dq_acc, xs):
        kc, vc, blk = xs                              # (B,bk,Hkv,dh)
        ki = blk * bk + jnp.arange(bk, dtype=jnp.int32)
        mask = jnp.broadcast_to(ki[None, :] < Sk, (Sq, bk))
        if causal:
            mask = mask & (ki[None, :] <= qi[:, None])
        if window is not None:
            mask = mask & (ki[None, :] > qi[:, None] - window)
        s = jnp.einsum("bqgid,bkgd->bqgik", qg * sm, kc.astype(jnp.float32))
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])               # recomputed probs
        dv = jnp.einsum("bqgik,bqgid->bkgd", p, dog)  # (B,bk,Hkv,dh)
        dp = jnp.einsum("bqgid,bkgd->bqgik", dog, vc.astype(jnp.float32))
        ds = p * (dp - D[..., None]) * sm
        dq_acc = dq_acc + jnp.einsum("bqgik,bkgd->bqgid", ds, kc.astype(jnp.float32))
        dk = jnp.einsum("bqgik,bqgid->bkgd", ds, qg)  # (B,bk,Hkv,dh)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((B, Sq, Hkv, group, dh), jnp.float32)
    dq, (dk_b, dv_b) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(n_blocks, dtype=jnp.int32))
    )
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(B, n_blocks * bk, Hkv, dh)[:, :Sk]
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(B, n_blocks * bk, Hkv, dh)[:, :Sk]
    return (
        dq.reshape(B, Sq, H, dh).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


flash_attention_vjp.defvjp(_fa_vjp_fwd, _fa_vjp_bwd)


def flash_attention_train(q, k, v, *, causal: bool = True,
                          window: Optional[int] = None, q_offset: int = 0,
                          block_k: int = 512):
    """Differentiable flash attention (blockwise-recompute backward)."""
    return flash_attention_vjp(q, k, v, causal, window, q_offset,
                               min(block_k, k.shape[1]))


# ---------------------------------------------------------------------------
# Full layers
# ---------------------------------------------------------------------------


def self_attention(
    params, x, cfg, *, positions=None, causal: bool = True,
    impl: str = "xla", q_offset: int = 0, block_k: int = 512,
    prefix_kv=None, kind: Optional[AttnKind] = None,
):
    """Training/prefill self-attention.  Returns (out, (k, v)) so prefill can
    seed the KV cache.

    ``prefix_kv=(pk, pv)`` prepends an already-computed K/V context of
    length ``Lp`` (shared-prefix admission: the cached prompt pages): the
    queries attend to ``[prefix; self]`` with the causal mask offset by
    ``Lp``, which is exactly rows ``[Lp:]`` of the full-sequence causal
    attention — so a suffix prefill over the same tokens/positions
    reproduces the cold prefill's suffix rows.  Callers must pass
    ``positions`` already offset by ``Lp``; the returned (k, v) cover only
    the fresh suffix.  On a windowed layer (``kind.window``) the suffix
    rows see the prefix positions inside their window only."""
    kind = layer_kind(cfg, kind)
    window = kind.window
    q, k, v = _project_qkv(params, x, cfg, positions=positions, kind=kind)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        Lp = pk.shape[1]
        if impl == "pallas":
            from repro.kernels.prefix_attention import ops as pfx_ops

            # prefix and suffix K/V stay separate operands — the kernel
            # streams both phases over one grid axis; no concat copy
            out = pfx_ops.prefix_flash_attention(
                q, pk.astype(k.dtype), pv.astype(v.dtype), k, v,
                q_offset=q_offset, window=window)
        else:
            k_att = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v_att = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            if impl == "naive":
                out = naive_attention(q, k_att, v_att, causal=causal,
                                      window=window, q_offset=q_offset + Lp)
            else:
                out = chunked_flash_attention(q, k_att, v_att, causal=causal,
                                              window=window,
                                              q_offset=q_offset + Lp,
                                              block_k=block_k)
        B, S, _, _ = q.shape
        y = out.reshape(B, S, cfg.q_dim) @ params["wo"]
        return y, (k, v)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops

        out = fa_ops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    elif impl == "naive":
        out = naive_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    elif impl == "flash":
        # custom-VJP path: O(S·block) memory THROUGH the backward pass
        out = flash_attention_train(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, block_k=block_k,
        )
    else:
        out = chunked_flash_attention(
            q, k, v, causal=causal, window=window,
            q_offset=q_offset, block_k=block_k,
        )
    B, S, _, _ = q.shape
    y = out.reshape(B, S, cfg.q_dim) @ params["wo"]
    return y, (k, v)


def cross_attention(params, x, enc_kv, cfg, *, impl: str = "xla"):
    """Decoder cross-attention over precomputed encoder (k, v)."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
    k, v = enc_kv
    if impl == "naive":
        out = naive_attention(q, k, v, causal=False)
    else:
        out = chunked_flash_attention(q, k, v, causal=False)
    return out.reshape(B, S, cfg.q_dim) @ params["wo"]


def encode_cross_kv(params, enc_out, cfg):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    B, S, _ = enc_out.shape
    k = (enc_out @ params["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = (enc_out @ params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    return k, v


# ---------------------------------------------------------------------------
# Decode path (one new token vs. KV cache)
# ---------------------------------------------------------------------------


class KVCacheView(NamedTuple):
    """One layer's cache: ring buffer when the arch has a sliding window.

    k, v:  (B, C, Hkv, dh) with C = min(max_len, window or max_len)
    pos:   (B, C) int32 — absolute position stored in each slot (-1 = empty)
    """

    k: jax.Array
    v: jax.Array
    pos: jax.Array


def decode_attention(
    params, x, cache: KVCacheView, cur_pos, cfg, *, impl: str = "xla",
    policy=None, kind: Optional[AttnKind] = None,
):
    """x: (B, 1, D); cur_pos: (B,) absolute position of the new token.

    Returns (out (B,1,D), updated cache).  The new token's K/V is written at
    slot ``cur_pos % C`` (ring buffer ≡ plain buffer when C == max_len).

    When the cache-length axis is model-sharded (kv heads don't divide the
    axis), the slot write goes through ``policy.kv_slot_update`` — a
    partial-manual shard_map masked write — instead of a scatter that GSPMD
    can only implement by resharding the whole cache.
    """
    B = x.shape[0]
    kind = layer_kind(cfg, kind)
    q, k_new, v_new = _project_qkv(
        params, x, cfg, positions=cur_pos[:, None], rope=True, kind=kind
    )                                                          # q: (B,1,H,dh)
    C = cache.k.shape[1]
    # RoPE computes in f32 — cast BEFORE the slot write, or `.at[].set`
    # promotes the whole cache to f32 and every decode step round-trips the
    # full stacked cache through converts (measured 2×279 GB/step/device on
    # command-r decode_32k — EXPERIMENTS.md §Perf iteration D3).
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)

    if policy is not None and getattr(policy, "kv_len_sharded", False):
        k, v, pos = policy.kv_slot_update(
            cache.k, cache.v, cache.pos, k_new[:, 0], v_new[:, 0], cur_pos
        )
    else:
        slot = (cur_pos % C).astype(jnp.int32)                 # (B,)
        bidx = jnp.arange(B)
        k = cache.k.at[bidx, slot].set(k_new[:, 0])
        v = cache.v.at[bidx, slot].set(v_new[:, 0])
        pos = cache.pos.at[bidx, slot].set(cur_pos.astype(jnp.int32))

    if impl == "pallas":
        from repro.kernels.decode_attention import ops as da_ops

        out = da_ops.decode_attention(
            q[:, 0], k, v, pos, cur_pos, window=kind.window
        )[:, None]
    else:
        out = _decode_attn_xla(q, k, v, pos, cur_pos, cfg, window=kind.window)
    y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
    return y, KVCacheView(k=k, v=v, pos=pos)


def _decode_attn_xla(q, k, v, pos, cur_pos, cfg, *, window=None):
    """q: (B,1,H,dh); k/v: (B,C,Hkv,dh); pos: (B,C); cur_pos: (B,);
    ``window``: the layer's sliding window, None = full.

    K/V stay in cache dtype; the contractions accumulate in f32 via
    ``preferred_element_type`` — materializing ``k.astype(f32)`` copies the
    whole cache every layer (measured ~26 GB/step/device on command-r
    decode_32k before this change, EXPERIMENTS.md §Perf)."""
    B, _, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = (q.reshape(B, Hkv, group, dh) / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bgid,bkgd->bgik", qg, k,
                   preferred_element_type=jnp.float32)             # (B,Hkv,g,C)
    valid = (pos >= 0) & (pos <= cur_pos[:, None])
    if window is not None:
        valid &= pos > (cur_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgik,bkgd->bgid", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, dh).astype(q.dtype)


def init_kv_cache(cfg, batch: int, max_len: int, *, dtype=None,
                  window: Optional[int] = None) -> KVCacheView:
    """Cache for ONE attention layer.  Ring-buffer length = min(max_len,
    window) for a windowed layer — the O(window) decode-memory property."""
    C = min(max_len, window) if window else max_len
    dt = jnp.dtype(dtype or cfg.dtype)
    return KVCacheView(
        k=jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), dtype=dt),
        v=jnp.zeros((batch, C, cfg.n_kv_heads, cfg.d_head), dtype=dt),
        pos=jnp.full((batch, C), -1, dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# Paged decode path (block-granular KV virtualization)
# ---------------------------------------------------------------------------


class PagedKVView(NamedTuple):
    """One layer's cache as a shared pool of fixed-size pages.

    k, v: (n_pages + 1, page_size, Hkv, dh) — one extra *trash* page at
    index ``n_pages`` that absorbs writes from slots with no mapping
    (inactive, page-fault denied).  Which pool page holds which slot's
    tokens lives outside the view, in the per-slot **page table**
    (B, max_pages) int32 where entry j maps the slot's logical page j
    (absolute positions [j*page_size, (j+1)*page_size)) to a physical
    page id, -1 = unmapped.

    No per-token ``pos`` array is needed: paged placement is
    position-indexed by construction — logical page j, offset o *is*
    absolute position j*page_size + o — so validity of a gathered key is
    ``page mapped and position <= cur_pos``.  (A slot only ever attends
    to positions it has itself written since acquiring the page, so
    stale contents of recycled pages can never leak across slots.)

    The decode and verify steps take the stacked view of every layer's
    pool, k, v: (n_layers, n_pages + 1, page_size, Hkv, dh), with a
    ``layer`` index: the pool rides whole through the layer scan and is
    written in place, never sliced per layer.  ``n_pages`` and
    ``page_size`` read the same from one layer's pool and from the stack.
    """

    k: jax.Array
    v: jax.Array

    @property
    def n_pages(self) -> int:
        return self.k.shape[-4] - 1

    @property
    def page_size(self) -> int:
        return self.k.shape[-3]


def init_paged_kv_cache(cfg, n_pages: int, page_size: int, *, dtype=None) -> PagedKVView:
    """Page pool for ONE attention layer (+1 trash page).  A windowed layer
    keeps a slot's whole context in pages too and masks what lies behind
    its window; the attention reads only the pages the window overlaps."""
    dt = jnp.dtype(dtype or cfg.dtype)
    return PagedKVView(
        k=jnp.zeros((n_pages + 1, page_size, cfg.n_kv_heads, cfg.d_head), dtype=dt),
        v=jnp.zeros((n_pages + 1, page_size, cfg.n_kv_heads, cfg.d_head), dtype=dt),
    )


def paged_decode_attention(params, x, cache: PagedKVView, cur_pos, page_table,
                           cfg, *, layer, impl: str = "xla", policy=None,
                           kind: Optional[AttnKind] = None):
    """Single-token decode against a paged pool.

    x: (B, 1, D); cur_pos: (B,) absolute position of the new token;
    page_table: (B, max_pages) int32 physical page per logical page;
    ``cache`` is the stacked view of every layer's pool and ``layer``
    (int32 scalar) the one this call writes and attends.

    The new token's K/V is written at (layer, page_table[b, cur_pos // ps],
    cur_pos % ps) — one scatter into the stack, which a carried, donated
    pool takes in place; unmapped slots write to the trash page.  The
    returned view is the whole stack.

    ``impl="xla"`` gathers the slot's pages into a
    (B, max_pages*ps, Hkv, dh) view before attending — the pool bytes
    twice (gather copy + attention read).  ``impl="pallas"``
    (``repro.kernels.paged_attention``) walks the page table inside the
    kernel instead: the table rides in as a scalar-prefetch operand and
    becomes the DMA schedule, so only the mapped pages' bytes move, once.
    The XLA path stays as the numerical oracle.

    A windowed layer (``kind.window`` = W) masks keys at positions
    ``<= cur_pos - W``; the kernel then walks only the ``ceil(W / ps) + 1``
    pages that can overlap ``[cur_pos - W + 1, cur_pos]``.

    The length-sharded ``kv_slot_update`` policy hook is
    dense-cache-only and is rejected loudly instead of silently falling
    back.
    """
    if policy is not None and getattr(policy, "kv_len_sharded", False):
        raise NotImplementedError(
            "paged decode does not support a length-sharded KV cache")
    B = x.shape[0]
    kind = layer_kind(cfg, kind)
    q, k_new, v_new = _project_qkv(
        params, x, cfg, positions=cur_pos[:, None], rope=True, kind=kind
    )
    P = cache.n_pages
    ps = cache.page_size
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)

    cur_pos = cur_pos.astype(jnp.int32)
    logical = cur_pos // ps                                    # (B,)
    pid = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    dest = jnp.where(pid >= 0, pid, P)                         # trash if unmapped
    off = cur_pos % ps
    k = cache.k.at[layer, dest, off].set(k_new[:, 0])
    v = cache.v.at[layer, dest, off].set(v_new[:, 0])

    if impl == "pallas":
        from repro.kernels.paged_attention import ops as pa_ops

        out = pa_ops.paged_decode_attention(
            q[:, 0], k, v, page_table, cur_pos, layer,
            window=kind.window)[:, None]
    else:
        gather = jnp.where(page_table >= 0, page_table, P)     # (B, maxp)
        kg = k[layer, gather]                                  # (B, maxp, ps, Hkv, dh)
        vg = v[layer, gather]
        maxp = page_table.shape[1]
        L = maxp * ps
        kg = kg.reshape(B, L, cfg.n_kv_heads, cfg.d_head)
        vg = vg.reshape(B, L, cfg.n_kv_heads, cfg.d_head)
        pos_l = jnp.arange(L, dtype=jnp.int32)                 # flat == absolute
        valid = (page_table >= 0)[:, pos_l // ps] & (
            pos_l[None, :] <= cur_pos[:, None])
        if kind.window is not None:
            valid &= pos_l[None, :] > cur_pos[:, None] - kind.window
        out = _paged_attn_xla(q, kg, vg, valid, cfg)
    y = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
    return y, PagedKVView(k=k, v=v)


def _paged_attn_xla(q, k, v, valid, cfg):
    """q: (B,1,H,dh); k/v: (B,L,Hkv,dh); valid: (B,L).  Same masked-softmax
    math as :func:`_decode_attn_xla`, validity precomputed from the page
    table instead of a per-slot ``pos`` array."""
    B, _, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = (q.reshape(B, Hkv, group, dh) / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bgid,bkgd->bgik", qg, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgik,bkgd->bgid", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Verify path (speculative decode: W candidate tokens against the cache)
# ---------------------------------------------------------------------------
#
# Draft-and-verify scores a whole window of W candidate tokens in one pass:
# the window's K/V is written into the cache FIRST (positions cur_pos +
# [0, W)), then every query attends with per-query validity ``key position
# <= query position`` — which realizes within-window causality for free.
# Rollback of rejected drafts is overwrite-before-attend: the accepted
# count is always >= 1 for a surviving slot, so the next window's write
# range covers every stale position, and the position-validity mask keeps
# stale entries unattendable in the meantime.  No data is ever un-written.


def verify_decode_attention(
    params, x, cache: KVCacheView, cur_pos, cfg, *, impl: str = "xla",
    policy=None, write_limit=None, kind: Optional[AttnKind] = None,
):
    """Multi-query decode attention for draft verification (dense cache).

    x: (B, W, D) hidden states of the W window tokens; cur_pos: (B,)
    absolute position of the window's first token.  Returns
    (out (B, W, D), updated cache): query j attends every cached position
    ``<= cur_pos + j``, including the window's own writes at positions
    ``< j`` (within-window causality via the position-validity mask).

    ``write_limit`` (B,) bounds how many of the window's K/V writes stick
    (entries ``w >= write_limit[b]`` keep the old cache contents).  The
    ring buffer wraps at C: without the bound, a window overrunning a
    slot's token budget near capacity would wrap and clobber the oldest
    *live* context.  Positions ``>= write_limit`` can never be committed,
    so their garbage attention output is never observed.

    ``impl="pallas"`` has no dense multi-query kernel — the XLA multi-query
    path is the documented fallback (the paged pool is where the kernel
    leg lives; see :func:`paged_verify_attention`).
    """
    if policy is not None and getattr(policy, "kv_len_sharded", False):
        raise NotImplementedError(
            "verify decode does not support a length-sharded KV cache")
    kind = layer_kind(cfg, kind)
    if kind.window:
        raise ValueError(
            "verify decode does not support windowed layers (the dense "
            "ring recycles positions the window still reads)")
    B, W, _ = x.shape
    wi = jnp.arange(W, dtype=jnp.int32)
    pos_w = cur_pos.astype(jnp.int32)[:, None] + wi[None, :]       # (B, W)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions=pos_w, rope=True,
                                   kind=kind)
    C = cache.k.shape[1]
    assert W <= C, (W, C)       # window slots stay distinct mod C
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)

    slot = (pos_w % C).astype(jnp.int32)                           # (B, W)
    bidx = jnp.arange(B)[:, None]
    if write_limit is not None:
        ok = wi[None, :] < write_limit[:, None]                    # (B, W)
        k_new = jnp.where(ok[..., None, None], k_new, cache.k[bidx, slot])
        v_new = jnp.where(ok[..., None, None], v_new, cache.v[bidx, slot])
        pos_vals = jnp.where(ok, pos_w, cache.pos[bidx, slot])
    else:
        pos_vals = pos_w
    k = cache.k.at[bidx, slot].set(k_new)
    v = cache.v.at[bidx, slot].set(v_new)
    pos = cache.pos.at[bidx, slot].set(pos_vals)

    # no dense multi-query kernel: "pallas" falls back to the XLA oracle
    out = _verify_attn_xla(q, k, v, pos, pos_w, cfg)
    y = out.reshape(B, W, cfg.q_dim) @ params["wo"]
    return y, KVCacheView(k=k, v=v, pos=pos)


def _verify_attn_xla(q, k, v, pos, q_pos, cfg):
    """q: (B,W,H,dh); k/v: (B,C,Hkv,dh); pos: (B,C); q_pos: (B,W).

    :func:`_decode_attn_xla` with a query-window axis: same contractions,
    same f32 accumulation, per-query validity ``pos <= q_pos[:, j]``."""
    B, W, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = (q.reshape(B, W, Hkv, group, dh)
          / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bwgid,bkgd->bwgik", qg, k,
                   preferred_element_type=jnp.float32)         # (B,W,Hkv,g,C)
    valid = (pos[:, None, :] >= 0) & (pos[:, None, :] <= q_pos[:, :, None])
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bwgik,bkgd->bwgid", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, W, H, dh).astype(q.dtype)


def paged_verify_attention(params, x, cache: PagedKVView, cur_pos,
                           page_table, cfg, *, layer, impl: str = "xla",
                           policy=None, kind: Optional[AttnKind] = None):
    """Multi-query decode attention for draft verification (paged pool).

    x: (B, W, D); cur_pos: (B,) first window position; page_table,
    ``cache`` (the stacked pools) and ``layer`` as in
    :func:`paged_decode_attention`.  The window's K/V is scattered at
    ``(layer, page_table[b, pos // ps], pos % ps)`` per token; positions whose
    logical page is unmapped or out of table range land on the trash page
    (allocation is the caller's job — the spec chunk scan faults every
    spanned page before the verify, all-or-nothing per slot).

    ``impl="pallas"`` walks the page table inside the multi-query kernel
    (``repro.kernels.paged_attention.paged_attention_kernel``);
    ``impl="xla"`` is the gather oracle.  A windowed layer masks as in
    :func:`paged_decode_attention`, per query position.
    """
    if policy is not None and getattr(policy, "kv_len_sharded", False):
        raise NotImplementedError(
            "paged decode does not support a length-sharded KV cache")
    kind = layer_kind(cfg, kind)
    B, W, _ = x.shape
    wi = jnp.arange(W, dtype=jnp.int32)
    pos_w = cur_pos.astype(jnp.int32)[:, None] + wi[None, :]       # (B, W)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions=pos_w, rope=True,
                                   kind=kind)
    P = cache.n_pages
    ps = cache.page_size
    maxp = page_table.shape[1]
    k_new = k_new.astype(cache.k.dtype)
    v_new = v_new.astype(cache.v.dtype)

    logical = pos_w // ps                                          # (B, W)
    pid = jnp.take_along_axis(page_table,
                              jnp.clip(logical, 0, maxp - 1), axis=1)
    dest = jnp.where((pid >= 0) & (logical < maxp), pid, P)        # trash
    off = pos_w % ps
    k = cache.k.at[layer, dest, off].set(k_new)
    v = cache.v.at[layer, dest, off].set(v_new)

    if impl == "pallas":
        from repro.kernels.paged_attention import ops as pa_ops

        out = pa_ops.paged_verify_attention(q, k, v, page_table, cur_pos,
                                            layer, window=kind.window)
    else:
        gather = jnp.where(page_table >= 0, page_table, P)         # (B, maxp)
        L = maxp * ps
        kg = k[layer, gather].reshape(B, L, cfg.n_kv_heads, cfg.d_head)
        vg = v[layer, gather].reshape(B, L, cfg.n_kv_heads, cfg.d_head)
        pos_l = jnp.arange(L, dtype=jnp.int32)                     # absolute
        valid = (page_table >= 0)[:, pos_l // ps][:, None, :] & (
            pos_l[None, None, :] <= pos_w[:, :, None])             # (B, W, L)
        if kind.window is not None:
            valid &= pos_l[None, None, :] > pos_w[:, :, None] - kind.window
        out = _paged_verify_attn_xla(q, kg, vg, valid, cfg)
    y = out.reshape(B, W, cfg.q_dim) @ params["wo"]
    return y, PagedKVView(k=k, v=v)


def _paged_verify_attn_xla(q, k, v, valid, cfg):
    """q: (B,W,H,dh); k/v: (B,L,Hkv,dh); valid: (B,W,L).  The multi-query
    twin of :func:`_paged_attn_xla` — the numerical oracle for the paged
    multi-query verify kernel."""
    B, W, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = (q.reshape(B, W, Hkv, group, dh)
          / jnp.sqrt(jnp.float32(dh))).astype(q.dtype)
    s = jnp.einsum("bwgid,bkgd->bwgik", qg, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bwgik,bkgd->bwgid", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, W, H, dh).astype(q.dtype)
