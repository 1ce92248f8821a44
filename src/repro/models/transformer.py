"""Model assembly: period-stacked blocks under lax.scan, all six families.

Layer heterogeneity (hybrid attn/ssm interleave, MoE-every-k) is handled by
grouping layers into **periods**: P = lcm(attn_every, moe.every).  One period
of P layers is traced once; lax.scan runs it n_layers/P times over stacked
params.  This keeps the HLO O(P) instead of O(n_layers) — essential for
compiling 64–80-layer configs at 512 devices on the dry-run host — and makes
remat policy application uniform (checkpoint around the period body).

A layer position's attention kind (its sliding window and RoPE,
``ModelConfig.attn_kind``) is part of its :class:`LayerSpec`, so a model
whose attention kinds repeat (three windowed layers, then a full one) has
the pattern's length in its period.

Params are nested dicts; caches are pytrees aligned with the period
structure so prefill can emit them as scan ys and decode can consume/update
them as scan xs/ys.

The serving entry points (:func:`prefill`, :func:`prefix_prefill`,
:func:`decode_step`, :func:`verify_step`) run MoE layers through the
no-drop expert share (``moe.moe_share``); with ``with_stats=True`` they
also return its counts summed over layers, ``(3,)`` int32 by
``moe.EXPERT_STATS`` (zeros for a model without experts).  Device-trace
scopes: ``attn_window`` / ``attn_full`` round each layer's attention,
``moe`` round its experts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnKind

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .attention import KVCacheView
from .layers import embed, init_embedding, init_mlp, init_rmsnorm, mlp, rmsnorm
from .ssm import SSMState


# ---------------------------------------------------------------------------
# Period structure
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str           # "attn" | "ssm"
    mlp: Optional[str]   # "mlp" | "moe" | None (ssm family has no separate MLP)
    attn: Optional[AttnKind] = None   # an "attn" mixer's window and RoPE

    @property
    def scope(self) -> str:
        """The device-trace scope of this layer's attention."""
        return "attn_window" if self.attn and self.attn.window \
            else "attn_full"


def period_len(cfg) -> int:
    p = 1
    if cfg.family == "hybrid":
        p = cfg.attn_every
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every)
    if cfg.attn_pattern:
        p = math.lcm(p, len(cfg.attn_pattern))
    assert cfg.n_layers % p == 0, (cfg.n_layers, p)
    return p


def period_structure(cfg) -> List[LayerSpec]:
    """Layer specs for positions 0..P-1 of one period."""
    P = period_len(cfg)
    specs = []
    for i in range(P):
        mixer = "attn" if cfg.is_attn_layer(i) else "ssm"
        if cfg.family == "ssm" or (cfg.family == "hybrid" and mixer == "ssm" and not cfg.is_moe_layer(i)):
            m = "moe" if cfg.is_moe_layer(i) else None
        else:
            m = "moe" if cfg.is_moe_layer(i) else "mlp"
        if cfg.family == "ssm":
            m = None   # pure mamba blocks carry their own gating/MLP
        specs.append(LayerSpec(mixer=mixer, mlp=m,
                               attn=cfg.attn_kind(i) if mixer == "attn"
                               else None))
    return specs


def windowed_layers(cfg) -> bool:
    """Some attention layer of ``cfg`` reads through a sliding window."""
    return any(s.attn is not None and s.attn.window
               for s in period_structure(cfg))


def _no_stats():
    return jnp.zeros((3,), jnp.int32)


def _expert_stacks(cfg, blocks):
    """Split each period position's stacked expert weights (``wi``,
    ``wo``) off the block params that a layer scan slices, for the serving
    paths.  The grouped-matmul kernel takes its weights as a whole operand,
    so a layer's experts sliced out of the stack in the scan would be
    copied out every layer; it is handed the stack and the layer's index
    instead.  Returns (the blocks for the scan, the stacks by position —
    None where there are none — and the layer indices to scan, None for a
    model without experts)."""
    if cfg.moe is None:
        return blocks, [None] * len(blocks), None
    rest, stacks = [], []
    for bp in blocks:
        stack = None
        if "moe" in bp:
            moe = dict(bp["moe"])
            stack = {"wi": moe.pop("wi"), "wo": moe.pop("wo")}
            bp = dict(bp, moe=moe)
        rest.append(bp)
        stacks.append(stack)
    return rest, stacks, jnp.arange(n_blocks(cfg), dtype=jnp.int32)


def _serve_moe(lp, stack, h, cfg, *, mask=None, layer=None):
    """One layer's expert share on a serving path, its experts read from
    ``stack`` by ``layer`` when given (:func:`_expert_stacks`)."""
    params = lp["moe"] if stack is None else dict(lp["moe"], **stack)
    with jax.named_scope("moe"):
        return moe_mod.moe_share(params, h, cfg, mask=mask,
                                 layer=None if stack is None else layer)


def n_blocks(cfg) -> int:
    return cfg.n_layers // period_len(cfg)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_one_layer(key, cfg, spec: LayerSpec, *, cross: bool = False):
    keys = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": init_rmsnorm(cfg.d_model, cfg.dtype)}
    if spec.mixer == "attn":
        p["attn"] = attn_mod.init_attention(keys[0], cfg)
    else:
        p["ssm"] = ssm_mod.init_ssm(keys[0], cfg)
    if cross:
        p["ln_x"] = init_rmsnorm(cfg.d_model, cfg.dtype)
        p["cross"] = attn_mod.init_attention(keys[1], cfg, cross=True)
    if spec.mlp is not None:
        p["ln2"] = init_rmsnorm(cfg.d_model, cfg.dtype)
        if spec.mlp == "moe":
            p["moe"] = moe_mod.init_moe(keys[2], cfg)
        else:
            p["mlp"] = init_mlp(keys[2], cfg.d_model, cfg.d_ff, cfg.dtype,
                                kind=cfg.mlp_kind)
    return p


def _stack(trees: List[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_params(cfg, key) -> Dict[str, Any]:
    """Full parameter pytree.  Works under jax.eval_shape for the dry-run."""
    specs = period_structure(cfg)
    nb = n_blocks(cfg)
    k_embed, k_blocks, k_head, k_enc = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embedding(k_embed, cfg.vocab_padded, cfg.d_model, cfg.dtype),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    cross = cfg.family == "audio"
    blocks = []
    for p, spec in enumerate(specs):
        per_block = [
            _init_one_layer(jax.random.fold_in(k_blocks, b * len(specs) + p), cfg, spec, cross=cross)
            for b in range(nb)
        ]
        blocks.append(_stack(per_block))
    params["blocks"] = blocks
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(k_head, cfg.vocab_padded, cfg.d_model, cfg.dtype)
    if cfg.family == "audio":
        enc_spec = LayerSpec(mixer="attn", mlp="mlp")
        enc_blocks = [
            _init_one_layer(jax.random.fold_in(k_enc, b), cfg, enc_spec)
            for b in range(cfg.n_enc_layers)
        ]
        params["encoder"] = {
            "blocks": [_stack(enc_blocks)],
            "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Shared block application
# ---------------------------------------------------------------------------


def _sinusoid(positions, d_model: int):
    """On-the-fly sinusoidal embedding (no host table in the HLO)."""
    half = d_model // 2
    freq = jnp.exp(
        -jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1)
    )
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _shard(x, policy, name: str):
    return policy(x, name) if policy is not None else x


def _embed(params, tokens, policy):
    """Vocab-parallel lookup when the policy provides one (distributed runs);
    plain take otherwise."""
    if policy is not None and hasattr(policy, "embed"):
        return policy.embed(params["embed"]["w"], tokens)
    return embed(params["embed"], tokens)


class FwdOut(NamedTuple):
    hidden: jax.Array
    aux: jax.Array              # MoE load-balance loss (0 for non-MoE)


def _apply_layer_train(
    lp, spec: LayerSpec, x, cfg, *, positions, impl, policy, enc_kv=None,
    causal: bool = True, prefix_kv=None, serve: bool = False, mask=None,
    experts=None, layer=None,
):
    """One layer, full-sequence (train/prefill shape).  Returns
    (x, aux, kv_or_None, ssm_state_or_None).  ``prefix_kv`` threads a cached
    K/V context into the attention (shared-prefix suffix prefill).

    ``serve`` runs an MoE layer as the serving program does (the no-drop
    expert share, its stats counting the tokens of ``mask``), and ``aux``
    is then those stats, (3,) int32, instead of the load-balance loss;
    ``experts``/``layer`` as :func:`_serve_moe` takes them."""
    aux = _no_stats() if serve else jnp.float32(0.0)
    kv = None
    sstate = None
    h = rmsnorm(lp["ln1"], x, eps=cfg.norm_eps)
    if spec.mixer == "attn":
        with jax.named_scope(spec.scope):
            y, kv = attn_mod.self_attention(
                lp["attn"], h, cfg, positions=positions, causal=causal,
                impl=impl, prefix_kv=prefix_kv, kind=spec.attn,
            )
    else:
        y, sstate = ssm_mod.ssm_forward(lp["ssm"], h, cfg, impl=impl, return_state=True)
    x = x + _shard(_shard(y, policy, "attn_out"), policy, "residual")
    if enc_kv is not None and "cross" in lp:
        hx = rmsnorm(lp["ln_x"], x, eps=cfg.norm_eps)
        x = x + attn_mod.cross_attention(lp["cross"], hx, enc_kv, cfg, impl=impl)
    if spec.mlp is not None:
        h2 = rmsnorm(lp["ln2"], x, eps=cfg.norm_eps)
        if spec.mlp == "moe" and serve:
            y2, aux = _serve_moe(lp, experts, h2, cfg, mask=mask,
                                 layer=layer)
        elif spec.mlp == "moe":
            with jax.named_scope("moe"):
                y2, aux = moe_mod.moe_apply(lp["moe"], h2, cfg, policy=policy)
        else:
            y2 = mlp(lp["mlp"], h2, kind=cfg.mlp_kind)
        x = x + _shard(_shard(y2, policy, "mlp_out"), policy, "residual")
    return x, aux, kv, sstate


def _apply_layer_decode(
    lp, spec: LayerSpec, x, cfg, *, cur_pos, kv_cache, ssm_state, cross_kv,
    impl, policy, page_table=None, layer=None, mask=None, experts=None,
):
    """One layer, single-token decode.  Returns (x, new_kv, new_ssm,
    stats): ``stats`` the expert share's counts over the slots of
    ``mask`` (zeros without experts).  With ``page_table``, ``kv_cache``
    is the stacked paged pool and ``layer`` this layer's index into it."""
    h = rmsnorm(lp["ln1"], x, eps=cfg.norm_eps)
    new_kv, new_ssm = kv_cache, ssm_state
    stats = _no_stats()
    if spec.mixer == "attn" and page_table is not None:
        with jax.named_scope(spec.scope):
            y, new_kv = attn_mod.paged_decode_attention(
                lp["attn"], h, kv_cache, cur_pos, page_table, cfg,
                layer=layer, impl=impl, policy=policy, kind=spec.attn,
            )
    elif spec.mixer == "attn":
        with jax.named_scope(spec.scope):
            y, new_kv = attn_mod.decode_attention(
                lp["attn"], h, kv_cache, cur_pos, cfg, impl=impl,
                policy=policy, kind=spec.attn,
            )
    else:
        y, new_ssm = ssm_mod.ssm_decode(lp["ssm"], h, ssm_state, cfg)
    x = x + _shard(y, policy, "attn_out")
    if cross_kv is not None and "cross" in lp:
        hx = rmsnorm(lp["ln_x"], x, eps=cfg.norm_eps)
        x = x + attn_mod.cross_attention(lp["cross"], hx, cross_kv, cfg, impl=impl)
    if spec.mlp is not None:
        h2 = rmsnorm(lp["ln2"], x, eps=cfg.norm_eps)
        if spec.mlp == "moe":
            y2, stats = _serve_moe(lp, experts, h2, cfg, mask=mask,
                                   layer=layer)
        else:
            y2 = mlp(lp["mlp"], h2, kind=cfg.mlp_kind)
        x = x + _shard(y2, policy, "mlp_out")
    return x, new_kv, new_ssm, stats


def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    pol = {
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[remat]
    return jax.checkpoint(fn, policy=pol)


# ---------------------------------------------------------------------------
# Forward (train) — also the encoder stack driver
# ---------------------------------------------------------------------------


def forward(
    params, tokens, cfg, *, positions=None, extra_embeds=None, enc_out=None,
    impl: str = "xla", policy=None, remat: str = "none", causal: bool = True,
) -> FwdOut:
    """Full-sequence forward to final hidden states.

    tokens:       (B, S_txt) int32
    extra_embeds: (B, S_vis, d) precomputed patch/frame embeddings prepended
                  to the token embeddings (VLM stub frontend).
    enc_out:      (B, S_enc, d) encoder output (audio family).
    """
    specs = period_structure(cfg)
    x = _embed(params, tokens, policy)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if cfg.family == "audio":
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
        x = x + _sinusoid(pos, cfg.d_model).astype(x.dtype)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    x = _shard(x, policy, "hidden")

    enc_kvs = None
    if enc_out is not None:
        # Precompute per-position cross K/V once (stacked over blocks).
        enc_kvs = []
        for p, spec in enumerate(specs):
            lp = params["blocks"][p]
            enc_kvs.append(
                jax.vmap(
                    lambda lpb: attn_mod.encode_cross_kv(lpb["cross"], enc_out, cfg)
                )(lp)
            )

    def body(carry, xs_in):
        x, aux = carry
        if enc_kvs is None:
            (block_params,) = xs_in
            ekv = [None] * len(specs)
        else:
            block_params, ekv = xs_in
        for p, spec in enumerate(specs):
            x, aux_p, _, _ = _apply_layer_train(
                block_params[p], spec, x, cfg, positions=positions, impl=impl,
                policy=policy, enc_kv=ekv[p], causal=causal,
            )
            aux = aux + aux_p
        return (x, aux), None

    body_w = _remat_wrap(body, remat)
    xs = (params["blocks"],) if enc_kvs is None else (params["blocks"], enc_kvs)
    (x, aux), _ = jax.lax.scan(body_w, (x, jnp.float32(0.0)), xs)
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    return FwdOut(hidden=x, aux=aux)


def encoder_forward(params, frames, cfg, *, impl="xla", policy=None, remat="none"):
    """Audio encoder over stub frame embeddings (B, S_enc, d)."""
    enc = params["encoder"]
    B, S, _ = frames.shape
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    x = frames + _sinusoid(pos, cfg.d_model).astype(frames.dtype)
    spec = LayerSpec(mixer="attn", mlp="mlp")

    def body(x, block_params):
        y, _, _, _ = _apply_layer_train(
            block_params, spec, x, cfg, positions=pos, impl=impl,
            policy=policy, causal=False,
        )
        return y, None

    x, _ = jax.lax.scan(_remat_wrap(body, remat), x, enc["blocks"][0])
    return rmsnorm(enc["final_norm"], x, eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# LM head + chunked loss
# ---------------------------------------------------------------------------


def unembed_weight(params, cfg):
    w = params["embed"]["w"] if cfg.tie_embeddings else params["lm_head"]["w"]
    return w   # (Vp, d): logits = h @ w.T


def logits_fn(params, hidden, cfg):
    return hidden @ unembed_weight(params, cfg).T


def lm_loss(
    params, hidden, labels, cfg, *, chunk: int = 1024, policy=None,
) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over (B, S) labels; ignore label < 0.  Chunked over the
    sequence axis with lax.map so the full (B,S,V) logits tensor is never
    materialized.  Returns (sum_loss, count)."""
    w = unembed_weight(params, cfg)            # (Vp, d)
    B, S, d = hidden.shape
    ck = min(chunk, S)
    n = (S + ck - 1) // ck
    pad = n * ck - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    hc = jnp.moveaxis(hidden.reshape(B, n, ck, d), 1, 0)    # (n, B, ck, d)
    lc = jnp.moveaxis(labels.reshape(B, n, ck), 1, 0)

    def one(args):
        h, l = args
        logits = (h @ w.T).astype(jnp.float32)              # (B, ck, Vp)
        logits = _shard(logits, policy, "logits")
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(l, 0)[..., None], axis=-1
        )[..., 0]
        valid = l >= 0
        return (
            jnp.where(valid, lz - gold, 0.0).sum(),
            valid.sum(),
        )

    sums, counts = jax.lax.map(one, (hc, lc))
    return sums.sum(), counts.sum()


# ---------------------------------------------------------------------------
# Prefill and decode
# ---------------------------------------------------------------------------


class Caches(NamedTuple):
    """Decode-time state, aligned with the period structure.

    kv:    {str(p): KVCacheView stacked over blocks}   (attn positions)
    ssm:   {str(p): SSMState stacked over blocks}      (ssm positions)
    cross: {str(p): (k, v) stacked over blocks} | None (audio)
    """

    kv: Dict[str, KVCacheView]
    ssm: Dict[str, SSMState]
    cross: Optional[Dict[str, Tuple[jax.Array, jax.Array]]] = None


def init_caches(cfg, batch: int, max_len: int) -> Caches:
    specs = period_structure(cfg)
    nb = n_blocks(cfg)
    kv, ssm = {}, {}
    for p, spec in enumerate(specs):
        if spec.mixer == "attn":
            one = attn_mod.init_kv_cache(cfg, batch, max_len,
                                         window=spec.attn.window)
            kv[str(p)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (nb,) + a.shape).copy(), one
            )
        else:
            one = ssm_mod.init_ssm_state(cfg, batch)
            ssm[str(p)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (nb,) + a.shape).copy(), one
            )
    cross = None
    if cfg.family == "audio":
        # cross-attention K/V over the encoder output (seeded by prefill)
        cross = {
            str(p): (
                jnp.zeros((nb, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head),
                          dtype=jnp.dtype(cfg.dtype)),
                jnp.zeros((nb, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head),
                          dtype=jnp.dtype(cfg.dtype)),
            )
            for p in range(len(specs))
        }
    return Caches(kv=kv, ssm=ssm, cross=cross)


def init_paged_caches(cfg, batch: int, n_pages: int, page_size: int) -> Caches:
    """Decode caches with the attention layers backed by one shared page
    pool (:class:`~repro.models.attention.PagedKVView`) instead of per-slot
    dense buffers.  SSM and cross-attention state stay dense per slot —
    they are fixed-size per sequence, so there is nothing to page.  The
    per-slot page *table* lives with the slot bookkeeping
    (``serving.engine.PageState``), not in the cache tree."""
    specs = period_structure(cfg)
    nb = n_blocks(cfg)
    kv, ssm = {}, {}
    for p, spec in enumerate(specs):
        if spec.mixer == "attn":
            one = attn_mod.init_paged_kv_cache(cfg, n_pages, page_size)
            kv[str(p)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (nb,) + a.shape).copy(), one
            )
        else:
            one = ssm_mod.init_ssm_state(cfg, batch)
            ssm[str(p)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (nb,) + a.shape).copy(), one
            )
    cross = None
    if cfg.family == "audio":
        cross = {
            str(p): (
                jnp.zeros((nb, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head),
                          dtype=jnp.dtype(cfg.dtype)),
                jnp.zeros((nb, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.d_head),
                          dtype=jnp.dtype(cfg.dtype)),
            )
            for p in range(len(specs))
        }
    return Caches(kv=kv, ssm=ssm, cross=cross)


def prefill(
    params, tokens, cfg, *, max_len: int, positions=None, extra_embeds=None,
    enc_out=None, impl: str = "xla", policy=None, remat: str = "none",
    with_stats: bool = False, mask=None, rings: bool = True,
):
    """Run the full prompt, returning (last-token logits, seeded Caches),
    and with ``with_stats`` the expert share's counts over the rows of
    ``mask`` (B,) as a third output.

    The KV buffers are sized ``min(max_len, window)`` per layer; prompt K/V
    are scattered in ring-buffer order (see serving.kv_cache.seed_cache).
    ``rings=False`` keeps every position of every layer instead (paged
    admission packs them into pages; a windowed layer masks, it does not
    recycle).
    """
    from repro.serving.kv_cache import seed_kv_cache, seed_ssm_state

    specs = period_structure(cfg)
    x = _embed(params, tokens, policy)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if cfg.family == "audio":
        pos0 = jnp.arange(S, dtype=jnp.int32)[None, :]
        x = x + _sinusoid(pos0, cfg.d_model).astype(x.dtype)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    x = _shard(x, policy, "hidden")

    enc_kvs = None
    if enc_out is not None:
        enc_kvs = []
        for p, spec in enumerate(specs):
            lp = params["blocks"][p]
            enc_kvs.append(
                jax.vmap(
                    lambda lpb: attn_mod.encode_cross_kv(lpb["cross"], enc_out, cfg)
                )(lp)
            )

    track = with_stats and cfg.moe is not None

    blocks, stacks, layer_ids = _expert_stacks(cfg, params["blocks"])

    def body(carry, xs_in):
        x, st = carry if track else (carry, None)
        block_params, layer, ekv = xs_in
        ekv = ekv or [None] * len(specs)
        outs = {}
        for p, spec in enumerate(specs):
            x, aux, kv, sstate = _apply_layer_train(
                block_params[p], spec, x, cfg, positions=positions, impl=impl,
                policy=policy, enc_kv=ekv[p], causal=True, serve=True,
                mask=mask, experts=stacks[p], layer=layer,
            )
            outs[str(p)] = kv if spec.mixer == "attn" else sstate
            if track:
                st = st + aux
        return ((x, st) if track else x), outs

    body_w = _remat_wrap(body, remat)
    carry, ys = jax.lax.scan(body_w, (x, _no_stats()) if track else x,
                             (blocks, layer_ids, enc_kvs))
    x, st = carry if track else (carry, _no_stats())
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    last = x[:, -1:, :]
    logits = logits_fn(params, last, cfg)[:, 0]

    kv, ssm = {}, {}
    for p, spec in enumerate(specs):
        if spec.mixer == "attn":
            k, v = ys[str(p)]
            kv[str(p)] = seed_kv_cache(cfg, k, v, max_len=max_len,
                                       seq_positions=positions,
                                       window=spec.attn.window if rings
                                       else None)
        else:
            ssm[str(p)] = seed_ssm_state(ys[str(p)])
    cross = None
    if enc_kvs is not None:
        cross = {str(p): enc_kvs[p] for p in range(len(specs))}
    out = (logits, Caches(kv=kv, ssm=ssm, cross=cross))
    return out + (st,) if with_stats else out


def prefix_prefill(
    params, tokens, prefix_kv, cfg, *, prefix_len: int, impl: str = "xla",
    policy=None, with_stats: bool = False, mask=None,
):
    """Suffix prefill against a cached prompt prefix (shared-prefix
    admission): run only the uncached tail of the prompt, attending to the
    per-layer prefix K/V gathered from the paged pool.

    tokens:     (B, S_suffix) int32 — the prompt tail, absolute positions
                ``prefix_len + [0, S_suffix)``.
    prefix_kv:  {str(p): (k, v)} with k/v (nb, B, prefix_len, Hkv, dh) —
                the cached pages' contents, one entry per period position.

    Returns (last-token logits (B, Vp), {str(p): (k, v)}) where the output
    K/V cover only the suffix, in absolute-position order (ready for page
    packing); with ``with_stats``, the expert share's counts over the rows
    of ``mask`` (B,) as a third output.  A windowed layer's suffix rows see
    the prefix positions inside their window, a full layer's all of them.  Because causal attention makes the suffix rows independent
    of whether the prefix was recomputed or read back, this reproduces the
    cold ``prefill``'s suffix exactly (bit-for-bit when the cache dtype is
    the compute dtype — the page store's dtype cast is the only lossy step).

    Pure-attention archs only: an SSM layer's post-prompt state depends on
    every prompt token (nothing positional to cache), and audio/VLM prompts
    carry non-token context that shifts positions.
    """
    specs = period_structure(cfg)
    if any(s.mixer != "attn" for s in specs):
        raise ValueError(
            "prefix_prefill requires a pure-attention arch (SSM state is "
            "not positional — there is no per-page prefix to reuse)")
    if cfg.family in ("audio", "vlm"):
        raise ValueError(
            f"prefix_prefill does not support the {cfg.family} family")
    x = _embed(params, tokens, policy)
    B, S, _ = x.shape
    positions = prefix_len + jnp.arange(S, dtype=jnp.int32)[None, :]
    x = _shard(x, policy, "hidden")

    track = with_stats and cfg.moe is not None

    blocks, stacks, layer_ids = _expert_stacks(cfg, params["blocks"])

    def body(carry, xs_in):
        x, st = carry if track else (carry, None)
        block_params, pkv, layer = xs_in
        outs = {}
        for p, spec in enumerate(specs):
            x, aux, kv, _ = _apply_layer_train(
                block_params[p], spec, x, cfg, positions=positions, impl=impl,
                policy=policy, causal=True, prefix_kv=pkv[str(p)],
                serve=True, mask=mask, experts=stacks[p], layer=layer,
            )
            outs[str(p)] = kv
            if track:
                st = st + aux
        return ((x, st) if track else x), outs

    carry, ys = jax.lax.scan(body, (x, _no_stats()) if track else x,
                             (blocks, prefix_kv, layer_ids))
    x, st = carry if track else (carry, _no_stats())
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = logits_fn(params, x[:, -1:, :], cfg)[:, 0]
    return (logits, ys, st) if with_stats else (logits, ys)


def decode_step(
    params, tokens, caches: Caches, cur_pos, cfg, *, impl: str = "xla",
    policy=None, page_table=None, with_stats: bool = False, mask=None,
):
    """One decode step.  tokens: (B,) int32; cur_pos: (B,) absolute position.
    Returns (logits (B, Vp), updated Caches), and with ``with_stats`` the
    expert share's counts over the slots of ``mask`` (B,).

    With ``page_table`` (B, max_pages) the attention caches are treated as
    paged pools (:func:`init_paged_caches`); the table is read-only here —
    page allocation happens in the caller (chunk scan body or admission).
    The stacked pools then ride in the layer scan's carry and each layer
    writes its token into them by layer index, so the (donated) pool is
    updated in place: as ``xs``/``ys`` each layer's pool would be sliced
    out, written back and copied.  Dense rings and SSM state are per slot
    and small, and stay in ``xs``/``ys``.
    """
    specs = period_structure(cfg)
    x = _embed(params, tokens, policy)[:, None, :]     # (B, 1, d)
    if cfg.family == "audio":
        x = x + _sinusoid(cur_pos[:, None], cfg.d_model).astype(x.dtype)
    x = _shard(x, policy, "hidden_decode")

    cross = caches.cross or {}

    track = with_stats and cfg.moe is not None
    blocks, stacks, layer_ids = _expert_stacks(cfg, params["blocks"])

    def layers(x, block_params, kv_in, ssm_in, cross_in, layer=None):
        kv_out, ssm_out = {}, {}
        st = _no_stats()
        for p, spec in enumerate(specs):
            x, nkv, nssm, s_p = _apply_layer_decode(
                block_params[p], spec, x, cfg, cur_pos=cur_pos,
                kv_cache=kv_in.get(str(p)), ssm_state=ssm_in.get(str(p)),
                cross_kv=cross_in.get(str(p)), impl=impl, policy=policy,
                page_table=page_table, layer=layer, mask=mask,
                experts=stacks[p],
            )
            st = st + s_p
            if spec.mixer == "attn":
                kv_out[str(p)] = nkv
            else:
                ssm_out[str(p)] = nssm
        return x, kv_out, ssm_out, st

    st = _no_stats()
    if page_table is None:
        def body(carry, xs_in):
            x, st = carry if track else (carry, None)
            x, kv_out, ssm_out, s_b = layers(x, *xs_in)
            return ((x, st + s_b) if track else x), (kv_out, ssm_out)

        carry, (kv_new, ssm_new) = jax.lax.scan(
            body, (x, st) if track else x,
            (blocks, caches.kv, caches.ssm, cross, layer_ids))
        x, st = carry if track else (carry, st)
    else:
        def body(carry, xs_in):
            x, pools = carry[:2]
            block_params, layer, ssm_in, cross_in = xs_in
            x, pools, ssm_out, s_b = layers(x, block_params, pools, ssm_in,
                                            cross_in, layer)
            if track:
                return (x, pools, carry[2] + s_b), ssm_out
            return (x, pools), ssm_out

        xs = (blocks, jnp.arange(n_blocks(cfg), dtype=jnp.int32),
              caches.ssm, cross)
        carry, ssm_new = jax.lax.scan(
            body, (x, caches.kv, st) if track else (x, caches.kv), xs)
        x, kv_new = carry[:2]
        if track:
            st = carry[2]
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = logits_fn(params, x, cfg)[:, 0]
    out = (logits, Caches(kv=kv_new, ssm=ssm_new, cross=caches.cross))
    return out + (st,) if with_stats else out


def verify_step(
    params, tokens, caches: Caches, cur_pos, cfg, *, impl: str = "xla",
    policy=None, page_table=None, write_limit=None,
):
    """Score a window of W candidate tokens in one pass (draft-and-verify).

    tokens: (B, W) int32 — the slot's last committed token followed by
    W-1 drafted candidates, at absolute positions ``cur_pos + [0, W)``.
    Returns (logits (B, W, Vp), updated Caches): ``logits[:, j]`` is the
    model's next-token distribution *after* ``tokens[:, j]``, exactly what
    ``decode_step`` would produce having decoded the window prefix — the
    accepted-prefix outputs are identical to sequential greedy decode
    because causal attention makes each query row depend only on positions
    ``<= cur_pos + j`` (the verify attention writes the window's K/V
    before attending, so within-window causality falls out of the
    position-validity mask).

    With ``page_table`` the caches are paged pools and every window
    position must have its logical page mapped by the caller (unmapped
    positions write to the trash page); without it, ``write_limit`` (B,)
    caps how many window writes stick in the dense ring (see
    :func:`repro.models.attention.verify_decode_attention`).

    Pure-attention archs without windowed layers only: SSM state advances
    sequentially and cannot be rolled back for free, a dense ring recycles
    positions a window still reads, and audio/vlm prompts carry non-token
    context.  MoE layers are fine — decode routing is per-token.
    """
    specs = period_structure(cfg)
    if any(s.mixer != "attn" for s in specs):
        raise ValueError(
            "verify_step requires a pure-attention arch (SSM state cannot "
            "be rolled back to the accepted prefix)")
    if cfg.family in ("audio", "vlm"):
        raise ValueError(
            f"verify_step does not support the {cfg.family} family")
    if windowed_layers(cfg):
        raise ValueError(
            "verify_step does not support windowed attention layers "
            "(speculative decode is for models whose layers all attend in "
            "full)")
    x = _embed(params, tokens, policy)                  # (B, W, d)
    x = _shard(x, policy, "hidden_decode")
    blocks, stacks, layer_ids = _expert_stacks(cfg, params["blocks"])

    def layers(x, block_params, kv_in, layer=None):
        kv_out = {}
        for p, spec in enumerate(specs):
            lp = block_params[p]
            h = rmsnorm(lp["ln1"], x, eps=cfg.norm_eps)
            with jax.named_scope(spec.scope):
                if page_table is not None:
                    y, nkv = attn_mod.paged_verify_attention(
                        lp["attn"], h, kv_in[str(p)], cur_pos, page_table,
                        cfg, layer=layer, impl=impl, policy=policy,
                        kind=spec.attn,
                    )
                else:
                    y, nkv = attn_mod.verify_decode_attention(
                        lp["attn"], h, kv_in[str(p)], cur_pos, cfg,
                        impl=impl, policy=policy, write_limit=write_limit,
                        kind=spec.attn,
                    )
            kv_out[str(p)] = nkv
            x = x + _shard(y, policy, "attn_out")
            if spec.mlp is not None:
                h2 = rmsnorm(lp["ln2"], x, eps=cfg.norm_eps)
                if spec.mlp == "moe":
                    y2, _ = _serve_moe(lp, stacks[p], h2, cfg, layer=layer)
                else:
                    y2 = mlp(lp["mlp"], h2, kind=cfg.mlp_kind)
                x = x + _shard(y2, policy, "mlp_out")
        return x, kv_out

    if page_table is None:
        x, kv_new = jax.lax.scan(lambda x, xs_in: layers(x, *xs_in), x,
                                 (blocks, caches.kv, layer_ids))
    else:
        # the stacked pools in the carry, written in place (decode_step)
        def body(carry, xs_in):
            x, pools = carry
            block_params, layer = xs_in
            return layers(x, block_params, pools, layer), None

        (x, kv_new), _ = jax.lax.scan(
            body, (x, caches.kv),
            (blocks, jnp.arange(n_blocks(cfg), dtype=jnp.int32)))
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = logits_fn(params, x, cfg)                  # (B, W, Vp)
    return logits, Caches(kv=kv_new, ssm=caches.ssm, cross=caches.cross)
