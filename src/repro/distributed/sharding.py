"""Sharding rules: parameter specs, activation policy, batch specs.

Mesh axes (see launch/mesh.py):
  single-pod:  ("data", "model")          = (16, 16)
  multi-pod:   ("pod", "data", "model")   = (2, 16, 16)

Policy (the paper's per-layer {width | output-channel} tiling choice, as a
sharding selector — DESIGN.md §3):

* **Params**: tensor-parallel over "model" on the width dimension
  (heads·d_head, d_ff, experts, vocab), FSDP over "data" on the other
  dimension.  Params are REPLICATED over "pod" (pure DP across pods; the
  cross-pod gradient all-reduce is the compressible collective).
* **Activations**: batch over ("pod", "data"); TP dims over "model".
* **Fallbacks** (recorded per-arch): a dim that doesn't divide the axis size
  is left unsharded — e.g. starcoder2's 36 heads on a 16-way model axis make
  per-head attention TP impossible, so its attention runs sequence-sharded
  (the "width tiling" arm of the paper's chooser) while its FFN stays
  output-channel-sharded.

Everything here is *structural* — specs are built by walking the same period
structure as ``models.transformer.init_params``, so the two pytrees match by
construction (asserted in tests/test_sharding.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.transformer import LayerSpec, period_structure


# ---------------------------------------------------------------------------
# Axis helpers
# ---------------------------------------------------------------------------


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_shard(mesh: Mesh, batch: int):
    """Largest prefix of the data axes that divides ``batch`` (None if the
    batch can't be sharded at all, e.g. global_batch=1 long-context)."""
    axes = []
    prod = 1
    for a in data_axes(mesh):
        if batch % (prod * mesh_axis_size(mesh, a)) == 0:
            axes.append(a)
            prod *= mesh_axis_size(mesh, a)
        else:
            break
    return tuple(axes) if axes else None


class _Div:
    """Divisibility-gated axis chooser for one mesh.

    ``fsdp=False`` disables the "data"-axis param sharding: the serving
    layout.  FSDP weights are fatal for decode — every token re-gathers the
    full parameter set (measured ~0.77 TB/step/device on command-r
    decode_32k, EXPERIMENTS.md §Perf); TP-only weights read locally."""

    def __init__(self, mesh: Mesh, *, fsdp: bool = True, moe_ep: bool = True):
        self.mesh = mesh
        self.model = mesh_axis_size(mesh, "model")
        self.data = mesh_axis_size(mesh, "data")
        self.fsdp = fsdp
        self.moe_ep = moe_ep

    def m(self, dim: int):
        return "model" if dim % self.model == 0 else None

    def d(self, dim: int):
        if not self.fsdp:
            return None
        return "data" if dim % self.data == 0 else None


# ---------------------------------------------------------------------------
# Parameter specs (mirrors models/*.init_* structures)
# ---------------------------------------------------------------------------


def _spec_attn(cfg, dv: _Div, *, cross: bool = False) -> Dict[str, Any]:
    p = {
        "wq": P(dv.d(cfg.d_model), dv.m(cfg.q_dim)),
        "wk": P(dv.d(cfg.d_model), dv.m(cfg.kv_dim)),
        "wv": P(dv.d(cfg.d_model), dv.m(cfg.kv_dim)),
        "wo": P(dv.m(cfg.q_dim), dv.d(cfg.d_model)),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = {"scale": P(None)}
        p["k_norm"] = {"scale": P(None)}
    return p


def _spec_mlp(cfg, dv: _Div, d_ff: int, *, kind: str = None) -> Dict[str, Any]:
    kind = cfg.mlp_kind if kind is None else kind
    wi_out = 2 * d_ff if kind == "swiglu" else d_ff
    return {
        "wi": P(dv.d(cfg.d_model), dv.m(wi_out)),
        "wo": P(dv.m(d_ff), dv.d(cfg.d_model)),
    }


def _spec_moe(cfg, dv: _Div) -> Dict[str, Any]:
    m = cfg.moe
    p: Dict[str, Any] = {"router": P(dv.d(cfg.d_model), None)}
    if m.n_experts % dv.model == 0 and dv.moe_ep:
        # expert parallelism: experts over "model".  NOTE: under GSPMD the
        # dense dispatch (scatter into model-sharded buckets) reshards the
        # capacity buffers every layer — measured 8.6 TB/step/device of
        # all-reduce on jamba train_4k; expert-TP below avoids it entirely
        # (EXPERIMENTS.md §Perf cell 2), so moe_ep=False is the optimized
        # default for training cells.
        p["wi"] = P("model", dv.d(cfg.d_model), None)
        p["wo"] = P("model", None, dv.d(cfg.d_model))
    else:
        # TP within each expert: buckets stay local to each device's tokens
        # (zero dispatch collectives), each expert's width is model-sharded;
        # per-device FLOPs identical to EP.
        p["wi"] = P(None, dv.d(cfg.d_model), dv.m(2 * m.expert_d_ff))
        p["wo"] = P(None, dv.m(m.expert_d_ff), dv.d(cfg.d_model))
    if m.n_shared_experts:
        p["shared"] = _spec_mlp(cfg, dv, m.n_shared_experts * (m.shared_d_ff or m.expert_d_ff), kind="swiglu")
    return p


def _spec_ssm(cfg, dv: _Div) -> Dict[str, Any]:
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    nh = s.n_ssm_heads(cfg.d_model)
    gn = s.n_groups * s.d_state
    return {
        "wz": P(dv.d(cfg.d_model), dv.m(d_in)),
        "wx": P(dv.d(cfg.d_model), dv.m(d_in)),
        "wb": P(dv.d(cfg.d_model), dv.m(gn)),
        "wc": P(dv.d(cfg.d_model), dv.m(gn)),
        "wdt": P(dv.d(cfg.d_model), dv.m(nh)),
        "conv_w": P(None, None),
        "A_log": P(None),
        "D": P(None),
        "dt_bias": P(None),
        "norm": {"scale": P(None)},
        "out_proj": P(dv.m(d_in), dv.d(cfg.d_model)),
    }


def _spec_layer(cfg, dv: _Div, spec: LayerSpec, *, cross: bool) -> Dict[str, Any]:
    p: Dict[str, Any] = {"ln1": {"scale": P(None)}}
    if spec.mixer == "attn":
        p["attn"] = _spec_attn(cfg, dv)
    else:
        p["ssm"] = _spec_ssm(cfg, dv)
    if cross:
        p["ln_x"] = {"scale": P(None)}
        p["cross"] = _spec_attn(cfg, dv, cross=True)
    if spec.mlp is not None:
        p["ln2"] = {"scale": P(None)}
        if spec.mlp == "moe":
            p["moe"] = _spec_moe(cfg, dv)
        else:
            p["mlp"] = _spec_mlp(cfg, dv, cfg.d_ff)
    return p


def _add_leading(tree, axis=None):
    """Stacked-block params get an unsharded leading (block) axis."""
    return jax.tree.map(
        lambda s: P(axis, *s), tree, is_leaf=lambda x: isinstance(x, P)
    )


def param_specs(cfg, mesh: Mesh, *, fsdp: bool = True, moe_ep: bool = True) -> Dict[str, Any]:
    """PartitionSpec pytree structurally matching models.init_params(cfg).

    Embedding tables are vocab-sharded over "model" with the feature dim
    REPLICATED (not FSDP): the lookup runs as a vocab-parallel masked gather
    + psum (Megatron-style, see ``make_policy``), and the tied LM head then
    produces vocab-sharded logits with zero resharding.  A d-sharded table
    would force XLA's "involuntary full rematerialization" of the gather —
    a 6.3 GB table replication per chip at command-r scale."""
    dv = _Div(mesh, fsdp=fsdp, moe_ep=moe_ep)
    specs = period_structure(cfg)
    cross = cfg.family == "audio"
    embed_spec = P(dv.m(cfg.vocab_padded), None)
    out: Dict[str, Any] = {
        "embed": {"w": embed_spec},
        "final_norm": {"scale": P(None)},
        "blocks": [
            _add_leading(_spec_layer(cfg, dv, s, cross=cross)) for s in specs
        ],
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = {"w": embed_spec}
    if cfg.family == "audio":
        enc_spec = LayerSpec(mixer="attn", mlp="mlp")
        out["encoder"] = {
            "blocks": [_add_leading(_spec_layer(cfg, dv, enc_spec, cross=False))],
            "final_norm": {"scale": P(None)},
        }
    return out


def param_shardings(cfg, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(cfg, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Activation policy (with_sharding_constraint hooks inside the model)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ActivationPolicy:
    """Callable passed as ``policy=`` into model forward functions.

    Also carries the vocab-parallel embedding lookup (``embed``): a masked
    local gather + psum over the "model" axis under partial-manual shard_map
    — Megatron's vocab-parallel embedding, avoiding XLA's gather-over-
    sharded-dim replication fallback.
    """

    mesh: Mesh
    batch_axes: Optional[Tuple[str, ...]]
    rules: Dict[str, P]
    vocab_parallel: bool = False
    # decode KV cache has its LENGTH axis sharded over "model" (set when the
    # arch's kv heads don't divide the model axis — see cache_specs); the
    # slot write must then use kv_slot_update.
    kv_len_sharded: bool = False

    def __call__(self, x, name: str):
        spec = self.rules.get(name)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def kv_slot_update(self, k_cache, v_cache, pos_cache, k_new, v_new, cur_pos):
        """Ring-buffer slot write for a LENGTH-sharded KV cache.

        A plain ``cache.at[b, slot].set(...)`` scatter across the
        model-sharded cache-length axis makes GSPMD reshard the whole cache
        ("involuntary full rematerialization" — measured as ~770 GB/step of
        HBM traffic on command-r decode_32k, EXPERIMENTS.md §Perf).  Under
        partial-manual shard_map each shard masks the write to its own slot
        range: zero collective, zero copy.

        k_cache/v_cache: (B, C, Hkv, dh) sharded (?, "model", None, None);
        pos_cache: (B, C); k_new/v_new: (B, Hkv, dh); cur_pos: (B,).
        """
        C = k_cache.shape[1]

        def upd(kc, vc, pc, kn, vn, cur):
            c_loc = kc.shape[1]
            lo = jax.lax.axis_index("model") * c_loc
            slot = (cur % C).astype(jnp.int32) - lo
            ok = (slot >= 0) & (slot < c_loc)
            safe = jnp.clip(slot, 0, c_loc - 1)
            b = jnp.arange(kc.shape[0])
            kc = kc.at[b, safe].set(
                jnp.where(ok[:, None, None], kn, kc[b, safe])
            )
            vc = vc.at[b, safe].set(
                jnp.where(ok[:, None, None], vn, vc[b, safe])
            )
            pc = pc.at[b, safe].set(
                jnp.where(ok, cur.astype(jnp.int32), pc[b, safe])
            )
            return kc, vc, pc

        return jax.shard_map(
            upd, mesh=self.mesh,
            in_specs=(
                P(None, "model"), P(None, "model"), P(None, "model"),
                P(), P(), P(),
            ),
            out_specs=(P(None, "model"), P(None, "model"), P(None, "model")),
            axis_names={"model"}, check_vma=False,
        )(k_cache, v_cache, pos_cache, k_new, v_new, cur_pos)

    def embed(self, table, ids):
        """table: (Vp, d) vocab-sharded over "model"; ids: int32 (...)."""
        if not self.vocab_parallel:
            return jnp.take(table, ids, axis=0)

        def lookup(tbl, ids_):
            vloc = tbl.shape[0]
            lo = jax.lax.axis_index("model") * vloc
            local = ids_ - lo
            ok = (local >= 0) & (local < vloc)
            safe = jnp.clip(local, 0, vloc - 1)
            out = jnp.take(tbl, safe, axis=0)
            out = jnp.where(ok[..., None], out, 0)
            # psum in f32: exactly one shard contributes per row, so this is
            # value-exact; it also sidesteps an XLA-CPU AllReducePromotion
            # crash on bf16 all-reduces emitted inside partial-manual
            # shard_map (CloneAllReduce check-fails on the cloned region).
            return jax.lax.psum(out.astype(jnp.float32), "model").astype(tbl.dtype)

        return jax.shard_map(
            lookup, mesh=self.mesh,
            in_specs=(P("model", None), P()),
            out_specs=P(),
            axis_names={"model"}, check_vma=False,
        )(table, ids)


def make_policy(cfg, mesh: Mesh, *, batch: int, moe_ep: bool = True) -> ActivationPolicy:
    ba = batch_shard(mesh, batch)
    dv = _Div(mesh)
    rules = {
        "hidden": P(ba, None, None),
        "residual": P(ba, None, None),
        "hidden_decode": P(ba, None, None),
        "logits": P(ba, None, dv.m(cfg.vocab_padded)),
    }
    if (cfg.moe is not None and moe_ep and dv.model > 1
            and cfg.moe.n_experts % dv.model == 0):
        # keep the MoE capacity buffers expert-sharded over "model": without
        # this GSPMD all-reduces the full (B,E,cap,2·dff) tensor every layer
        rules["moe_ecap"] = P(ba, "model", None, None)
    return ActivationPolicy(
        mesh=mesh, batch_axes=ba, rules=rules,
        # manual (shard_map) paths only make sense on a non-trivial axis —
        # a size-1 "model" axis trips XLA's manual-subgroup RET_CHECK
        vocab_parallel=dv.m(cfg.vocab_padded) is not None and dv.model > 1,
        kv_len_sharded=(
            cfg.family != "ssm" and cfg.n_kv_heads % dv.model != 0 and dv.model > 1
        ),
    )


# ---------------------------------------------------------------------------
# Batch / cache specs (inputs and outputs of the step functions)
# ---------------------------------------------------------------------------


def train_batch_specs(cfg, mesh: Mesh, *, batch: int) -> Dict[str, P]:
    ba = batch_shard(mesh, batch)
    specs = {"tokens": P(ba, None), "labels": P(ba, None)}
    if cfg.family == "vlm":
        specs["extra_embeds"] = P(ba, None, None)            # (B, Sv, d)
        specs["positions"] = P(None, ba, None)               # (3, B, S)
    if cfg.family == "audio":
        specs["frames"] = P(ba, None, None)                  # (B, S_enc, d)
    return specs


def cache_specs(cfg, mesh: Mesh, *, batch: int):
    """Spec pytree structurally matching ``models.transformer.Caches``.

    KV sharding policy: shard kv-heads over "model" when divisible; otherwise
    shard the cache-length axis over "model" (flash-decoding style partial
    softmax, handled by GSPMD's sharded-softmax rewrite).  Batch over the
    data axes when divisible (decode_32k), else unsharded (long_500k B=1,
    where the length axis carries all the parallelism).
    """
    from repro.models.attention import KVCacheView
    from repro.models.ssm import SSMState
    from repro.models.transformer import Caches

    dv = _Div(mesh)
    ba = batch_shard(mesh, batch)
    specs = period_structure(cfg)
    kv: Dict[str, Any] = {}
    ssm: Dict[str, Any] = {}
    kv_heads_ok = cfg.n_kv_heads % dv.model == 0
    for p, sp in enumerate(specs):
        if sp.mixer == "attn":
            if kv_heads_ok:
                kvspec = P(None, ba, None, "model", None)
                pspec = P(None, ba, None)
            else:
                kvspec = P(None, ba, "model", None, None)
                pspec = P(None, ba, "model")
            kv[str(p)] = KVCacheView(k=kvspec, v=kvspec, pos=pspec)
        else:
            s = cfg.ssm
            nh = s.n_ssm_heads(cfg.d_model)
            ssm[str(p)] = SSMState(
                conv=P(None, ba, None, None),   # (K-1)-row window: tiny, replicate channels
                ssm=P(None, ba, dv.m(nh), None, None),
            )
    cross = None
    if cfg.family == "audio":
        cross = {
            str(p): (P(None, ba, None, None, None), P(None, ba, None, None, None))
            for p in range(len(specs))
        }
    return Caches(kv=kv, ssm=ssm, cross=cross)


# ---------------------------------------------------------------------------
# Tensor-parallel serving (full-manual shard_map over a flat ("tp",) mesh)
# ---------------------------------------------------------------------------
#
# The serving fast path shards ONE tenant's decode over the devices of its
# hypervisor lease: attention heads and MLP hidden features are split over a
# 1D "tp" axis, slot bookkeeping / page tables / draft state stay replicated,
# and each layer costs exactly two psums (attention output + MLP output).
# Unlike the train-side partial-manual policy above, these helpers run the
# model *entirely* inside shard_map (manual over every mesh axis), so the
# collectives are exactly the policy's psums and nothing is left to the SPMD
# partitioner's propagation.  The trick that keeps the model code
# untouched: every program is traced with a *shard-local* cfg
# (n_heads/n_kv_heads/d_ff divided by tp, d_head unchanged), so per-shard
# shapes are just a smaller model, and the TPShardPolicy turns the two
# residual hooks ("attn_out"/"mlp_out") into psums.


class TPShardPolicy:
    """Activation policy for fully-manual tensor-parallel decode.

    Sums the row-sharded attention/MLP output projections over the "tp"
    axis; identity for every other rule name.  Deliberately has NO ``embed``
    attribute (the table is replicated, each shard does the plain take) and
    ``kv_len_sharded`` False (KV is sharded over *heads*, never length).
    """

    kv_len_sharded = False

    def __init__(self, axis: str = "tp") -> None:
        self.axis = axis

    def __call__(self, x, name: str):
        if name not in ("attn_out", "mlp_out"):
            return x
        if x.dtype == jnp.float32:
            return jax.lax.psum(x, self.axis)
        # psum in f32: XLA-CPU check-fails cloning bf16 all-reduces emitted
        # inside shard_map (AllReducePromotion), same issue as .embed above
        return jax.lax.psum(x.astype(jnp.float32), self.axis).astype(x.dtype)


#: Shared instance for the default "tp" axis.  The program registry keys on
#: policy *identity*, so every batcher (and every re-mesh) must shard
#: through the same object for same-shape programs to cache-hit; the policy
#: is stateless, so sharing it is free.
TP_POLICY = TPShardPolicy()


def tp_supported(cfg) -> Optional[str]:
    """None when ``cfg`` can tensor-shard on the serving path, else the
    reason it cannot (pure-attention dense-MLP text archs only — SSM state,
    MoE dispatch, and cross-attention caches have no head axis to split)."""
    if cfg.family in ("audio", "vlm"):
        return f"family {cfg.family!r} has cross-attention/encoder state"
    specs = period_structure(cfg)
    if any(s.mixer != "attn" for s in specs):
        return "SSM/hybrid archs have no head axis in their recurrent state"
    if any(s.mlp == "moe" for s in specs):
        return "MoE expert dispatch is not tensor-shardable on this path"
    return None


def check_tp(cfg, tp: int) -> None:
    """Validate that ``cfg`` divides into ``tp`` shards; raises ValueError."""
    why = tp_supported(cfg)
    if why is not None:
        raise ValueError(f"tp={tp} unsupported for {cfg.name}: {why}")
    for dim, val in (("n_heads", cfg.n_heads), ("n_kv_heads", cfg.n_kv_heads),
                     ("d_ff", cfg.d_ff)):
        if val % tp:
            raise ValueError(
                f"tp={tp} must divide {dim}={val} for {cfg.name}")


def tp_local_cfg(cfg, tp: int):
    """The shard-local model: heads and hidden width divided by tp.  d_head
    is an explicit field (set in __post_init__), so it survives the replace;
    vocab / rope / norms are untouched (embeddings stay replicated)."""
    if tp <= 1:
        return cfg
    check_tp(cfg, tp)
    return dataclasses.replace(
        cfg,
        n_heads=cfg.n_heads // tp,
        n_kv_heads=cfg.n_kv_heads // tp,
        d_ff=cfg.d_ff // tp,
    )


def make_tp_mesh(tp: int, devices=None) -> Mesh:
    """Flat 1D ("tp",) mesh over ``devices`` (default: the first ``tp``
    process devices) — the per-tenant sub-mesh a hypervisor lease maps to."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < tp:
        raise ValueError(f"need {tp} devices for tp={tp}, have {len(devices)}")
    return Mesh(np.asarray(devices[:tp]), ("tp",))


def _swiglu_tp_perm(d_ff: int, tp: int):
    """Column permutation putting swiglu's packed [gate | up] wi into
    per-shard-contiguous [gate_i | up_i] blocks, so a plain contiguous
    chunking over the last axis hands shard i exactly its gate/up columns
    (and the silu(gate_i)*up_i features line up with wo's row shard i)."""
    import numpy as np

    f = d_ff // tp
    idx = []
    for i in range(tp):
        idx.extend(range(i * f, (i + 1) * f))
        idx.extend(range(d_ff + i * f, d_ff + (i + 1) * f))
    return np.asarray(idx, dtype=np.int64)


def permute_params_for_tp(params, cfg, tp: int):
    """Host-side relayout making every sharded matrix *contiguously*
    chunkable over its tp axis.  Only swiglu's packed wi needs moving;
    attention projections are head-contiguous already (contiguous head
    chunks preserve the GQA group ratio because tp divides both head
    counts).  Returns a new pytree; leaves come back as host numpy."""
    import numpy as np

    host = jax.device_get(params)
    if tp <= 1 or cfg.mlp_kind != "swiglu":
        return host
    perm = _swiglu_tp_perm(cfg.d_ff, tp)
    out = dict(host)
    out["blocks"] = [dict(layer) for layer in host["blocks"]]
    for layer in out["blocks"]:
        if "mlp" in layer:
            m = dict(layer["mlp"])
            m["wi"] = np.ascontiguousarray(np.asarray(m["wi"])[..., perm])
            layer["mlp"] = m
    return out


def tp_param_specs(cfg) -> Dict[str, Any]:
    """PartitionSpec pytree over the "tp" axis, structurally matching
    ``init_params`` for the pure-attention archs ``check_tp`` admits.
    Attention q/k/v are column-sharded (head-contiguous), output projections
    row-sharded; embeddings / lm_head / every norm scale replicated.  All
    leaves carry the leading stacked-blocks axis (hence the extra None)."""
    attn: Dict[str, Any] = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
    }
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": P()}
        attn["k_norm"] = {"scale": P()}
    layer = {
        "ln1": {"scale": P()},
        "attn": attn,
        "ln2": {"scale": P()},
        "mlp": {"wi": P(None, None, "tp"), "wo": P(None, "tp", None)},
    }
    out: Dict[str, Any] = {
        "embed": {"w": P()},
        "final_norm": {"scale": P()},
        "blocks": [layer for _ in period_structure(cfg)],
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = {"w": P()}
    return out


def tp_cache_specs(cfg, *, paged: bool):
    """Spec pytree matching serving's ``Caches``: K/V sharded over the head
    axis (axis 3 of both the dense ring and the page pool), positions
    replicated."""
    from repro.models.attention import KVCacheView, PagedKVView
    from repro.models.transformer import Caches

    kvspec = P(None, None, None, "tp", None)
    kv: Dict[str, Any] = {}
    for p, sp in enumerate(period_structure(cfg)):
        if sp.mixer != "attn":        # unreachable under check_tp; defensive
            raise ValueError("tp caches require a pure-attention arch")
        if paged:
            kv[str(p)] = PagedKVView(k=kvspec, v=kvspec)
        else:
            kv[str(p)] = KVCacheView(k=kvspec, v=kvspec, pos=P())
    return Caches(kv=kv, ssm={}, cross=None)


def tp_shardings(mesh: Mesh, spec_tree):
    """NamedShardings for a spec pytree (PartitionSpec leaves)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def tp_put_replicated(mesh: Mesh, tree):
    """device_put every leaf of ``tree`` replicated over the tp mesh (slot
    bookkeeping, page tables, draft state, PRNG keys)."""
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda a: jax.device_put(a, sh), tree)
