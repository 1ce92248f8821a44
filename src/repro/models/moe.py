"""Mixture-of-Experts: top-k router, a capacity-bounded training dispatch
and a no-drop serving path over this chip's share of the experts.

Two compute paths:

* **grouped-dispatch** (training) — per batch-row sort-based dispatch
  with a capacity bound, Switch-Transformer style but WITHOUT the O(T·E·C)
  one-hot dispatch tensor: tokens are argsorted by expert id, given a
  position-in-expert via a running offset, scattered into an (E, C, d) buffer,
  pushed through a stacked-expert einsum, and combined by a scatter-add.
  FLOPs ≈ top_k · T · 3 · d · d_ff · capacity_factor — HLO-honest for the
  roofline.  The sort is vmapped over the batch row, so with batch sharded on
  the "data" axis the sort is *local* (no cross-device sort network).

* **expert share** (serving: prefill, admission, decode) — the layer
  holds experts ``[first_expert, first_expert + n_held)`` of ``n_experts``
  (expert parallelism: the other shares live on other chips).  The router
  keeps all ``n_experts`` outputs; every assignment to a held expert is
  computed, with no capacity, so a served answer does not depend on how
  the batch was packed.  Assignments are sorted by expert and pushed
  through two grouped matrix products (:func:`grouped_matmul`: megablox's
  Pallas kernel) over the held experts only; the result is this
  share's part of the layer's output.

Shared experts (DeepSeek-MoE) are mathematically a single always-on MLP of
width n_shared·d_ff and are implemented as such (see test_moe_shared_equiv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .layers import init_dense, init_mlp, mlp


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(key, cfg):
    """cfg: ModelConfig with cfg.moe set."""
    m = cfg.moe
    kr, ke1, ke2, ks = jax.random.split(key, 4)
    d, dff, E, H = cfg.d_model, m.expert_d_ff, m.n_experts, m.n_held
    p = {
        "router": init_dense(kr, d, E, "float32")["w"],   # router math in f32
        "wi": (jax.random.normal(ke1, (H, d, 2 * dff), dtype=jnp.float32)
               * (1.0 / jnp.sqrt(d))).astype(cfg.dtype),
        "wo": (jax.random.normal(ke2, (H, dff, d), dtype=jnp.float32)
               * (1.0 / jnp.sqrt(dff))).astype(cfg.dtype),
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(ks, d, m.n_shared_experts * (m.shared_d_ff or dff), cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def router_probs(params, x, cfg):
    """(T, d) -> top-k (probs (T,k) normalized, expert ids (T,k), full probs)."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm
    return top_p, top_i, probs


def load_balance_loss(probs, top_i, n_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e (1.0 = perfectly balanced)."""
    counts = jnp.zeros((n_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    P = probs.mean(axis=0)
    return n_experts * jnp.sum(f * P)


# ---------------------------------------------------------------------------
# Grouped (sort-based) dispatch — train / prefill
# ---------------------------------------------------------------------------


def _route_row(x, top_p, top_i, E: int, capacity: int):
    """One batch row.  x: (T, d); top_p/top_i: (T, k).

    Returns (buf (E, capacity, d), slot (T·k,), t_sorted, w_sorted, keep)."""
    T, d = x.shape
    k = top_i.shape[1]
    Tk = T * k
    expert = top_i.reshape(Tk)                      # assignment expert ids
    token = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    weight = top_p.reshape(Tk)

    order = jnp.argsort(expert, stable=True)        # group by expert
    e_sorted = expert[order]
    t_sorted = token[order]
    w_sorted = weight[order]

    counts = jnp.zeros((E,), jnp.int32).at[expert].add(1)
    starts = jnp.cumsum(counts) - counts            # first sorted index of e
    pos = jnp.arange(Tk, dtype=jnp.int32) - starts[e_sorted]   # pos within expert
    keep = pos < capacity                           # overflow tokens dropped
    slot = jnp.where(keep, e_sorted * capacity + pos, E * capacity)  # OOB sink

    buf = jnp.zeros((E * capacity + 1, d), dtype=x.dtype)
    buf = buf.at[slot].set(x[t_sorted])             # dropped -> sink row
    return buf[:-1].reshape(E, capacity, d), slot, t_sorted, w_sorted, keep


def _combine_row(y, slot, t_sorted, w_sorted, keep, T: int):
    """y: (E, capacity, d) expert outputs -> (T, d) combined tokens."""
    E, capacity, d = y.shape
    y_flat = jnp.concatenate([y.reshape(E * capacity, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
    contrib = y_flat[slot] * w_sorted[:, None].astype(y.dtype)
    return jnp.zeros((T, d), dtype=y.dtype).at[t_sorted].add(
        jnp.where(keep[:, None], contrib, 0)
    )


def moe_grouped(params, x, cfg, *, capacity: Optional[int] = None, policy=None):
    """x: (B, S, d).  Per-row dispatch (sort local to each batch row); the
    expert matmuls run in batch form so the expert axis of the capacity
    buffers can be sharding-constrained over "model" (EP).  Without the
    constraint GSPMD materializes + all-reduces the full (B, E, cap, 2·dff)
    buffer every layer — measured 8×290 GB/step/device on jamba train_4k
    (EXPERIMENTS.md §Perf cell 2, iteration J4)."""
    m = cfg.moe
    B, S, d = x.shape
    if capacity is None:
        capacity = max(1, int(S * m.top_k / m.n_experts * m.capacity_factor))
        capacity = min(capacity, S * m.top_k)
    x2 = x.reshape(B, S, d)
    top_p, top_i, probs = router_probs(params, x2.reshape(B * S, d), cfg)
    top_p = top_p.reshape(B, S, m.top_k)
    top_i = top_i.reshape(B, S, m.top_k)

    bufs, slot, t_sorted, w_sorted, keep = jax.vmap(
        lambda xr, pr, ir: _route_row(xr, pr, ir, m.n_experts, capacity)
    )(x2, top_p, top_i)

    def shard(t):
        return policy(t, "moe_ecap") if policy is not None else t

    bufs = shard(bufs)                              # (B, E, cap, d) E-sharded
    h = jnp.einsum("becd,edf->becf", bufs, params["wi"])
    h = shard(h)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    y = jnp.einsum("becf,efd->becd", h, params["wo"])
    y = shard(y)

    routed = jax.vmap(
        lambda yr, sl, ts, ws, kp: _combine_row(yr, sl, ts, ws, kp, S)
    )(y, slot, t_sorted, w_sorted, keep)
    out = routed
    if "shared" in params:
        out = out + mlp(params["shared"], x)
    aux = load_balance_loss(probs, top_i.reshape(-1, m.top_k), m.n_experts)
    return out, aux


# ---------------------------------------------------------------------------
# Expert share: the serving path (no capacity, held experts only)
# ---------------------------------------------------------------------------

#: what ``moe_share``'s stats count, in order: assignments routed to held
#: experts, all assignments, held experts that received at least one
EXPERT_STATS = ("held_assignments", "assignments", "held_experts_hit")
#: most assignments one grouped product takes: a longer input (a cold
#: admission) runs in token chunks, which bounds its temporaries
MAX_ROWS = 16384
#: largest k and n tile of the grouped kernel: a 1152 x 896 bfloat16 weight
#: tile is 2 MB, 4 MB double-buffered, inside the scoped VMEM
GMM_TILE = 1152


def _tile(x: int, cap: int = GMM_TILE) -> int:
    """The largest multiple of 128 that divides ``x`` and is at most
    ``cap`` (``x`` itself when it fits)."""
    if x <= cap:
        return x
    for t in range(cap - cap % 128, 127, -128):
        if x % t == 0:
            return t
    return 128


def _gmm(lhs, rhs, sizes, *, interpret: bool):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m = lhs.shape[0]
    lhs = jnp.pad(lhs, ((0, (-m) % 128), (0, 0)))
    out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
              tiling=(128, _tile(lhs.shape[1]), _tile(rhs.shape[2])),
              interpret=interpret)
    return out[:m]


def grouped_matmul(lhs, rhs, sizes):
    """``lhs`` (m, k), rows sorted by group; ``rhs`` (G, k, n); ``sizes``
    (G,): the rows of group g times ``rhs[g]``.  Rows past ``sizes.sum()``
    are left undefined.

    Megablox's grouped matmul (a Pallas kernel, ``gmm``): its grid visits
    only the groups that have rows, and reads each one's weights from
    ``rhs`` where they lie, so ``rhs`` may be the stack of every layer's
    experts with the other layers' groups empty.  (A layer's experts
    sliced out of the stack would be copied for the kernel every layer.)
    Off the TPU the kernel runs in interpret mode, as the repo's other
    kernels do; the mode follows the platform the program is lowered for,
    so a program compiled for a described TPU from a CPU process gets the
    kernel itself."""
    return jax.lax.platform_dependent(
        lhs, rhs, sizes, tpu=functools.partial(_gmm, interpret=False),
        default=functools.partial(_gmm, interpret=True))


def moe_share(params, x, cfg, *, mask=None, layer=None):
    """This layer's share of the experts, every assignment computed.

    x: (B, S, d); ``mask`` (B,) or (B, S) bool marks the tokens the stats
    count (all when None; the output covers every token regardless).
    ``params["wi"]`` / ``["wo"]`` are this layer's held experts (H, ...),
    or, with ``layer`` (an int32 index), the stack of every layer's
    (nb, H, ...), read in place.  Returns (out (B, S, d), stats (3,) int32
    by :data:`EXPERT_STATS`).

    The router's softmax and top-k run in float32 over all ``n_experts``;
    assignments to experts outside ``[first_expert, first_expert +
    n_held)`` contribute nothing here (another chip holds them).  The held
    assignments are sorted by expert, gathered, and run through the two
    grouped products; rows past the held ones are zeroed, not weighted,
    so whatever the grouped kernel leaves there cannot leak.  Weighted
    contributions are summed per token in float32."""
    m = cfg.moe
    B, S, d = x.shape
    T, k, H = B * S, m.top_k, m.n_held
    wi, wo = params["wi"], params["wo"]
    base = 0
    if layer is not None:
        nb = wi.shape[0]
        wi = wi.reshape(nb * H, *wi.shape[2:])
        wo = wo.reshape(nb * H, *wo.shape[2:])
        base = layer * H
    counted = jnp.ones((T,), bool) if mask is None else \
        jnp.broadcast_to(mask.reshape(B, -1), (B, S)).reshape(T)

    def share(xc, cc):
        n = xc.shape[0]
        top_p, top_i, _ = router_probs(params, xc, cfg)
        local = top_i.reshape(n * k) - m.first_expert
        held = (local >= 0) & (local < H)
        group = jnp.where(held, local, H)              # H: not held here
        order = jnp.argsort(group, stable=True)        # held first, by expert
        token = (jnp.arange(n * k, dtype=jnp.int32) // k)[order]
        weight = top_p.reshape(n * k)[order]
        keep = held[order]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((wi.shape[0],), jnp.int32),
            jnp.zeros((H + 1,), jnp.int32).at[group].add(1)[:H], (base,))

        h = grouped_matmul(xc[token], wi, sizes)
        gate, up = jnp.split(h, 2, axis=-1)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        y = grouped_matmul(h, wo, sizes)
        y = jnp.where(keep[:, None], y.astype(jnp.float32) * weight[:, None],
                      0.0)
        out = jnp.zeros((n, d), jnp.float32).at[token].add(y)

        counts = jnp.repeat(cc, k)
        load = jnp.zeros((H + 1,), jnp.int32).at[
            jnp.where(counts, group, H)].add(1)[:H]
        return out.astype(x.dtype), load, counts.sum(dtype=jnp.int32)

    x2 = x.reshape(T, d)
    chunk = max(1, MAX_ROWS // k)
    if T <= chunk:
        out, load, total = share(x2, counted)
    else:                                  # bounded temporaries, in order
        n_c = -(-T // chunk)
        pad = n_c * chunk - T
        xs = jnp.pad(x2, ((0, pad), (0, 0))).reshape(n_c, chunk, d)
        cs = jnp.pad(counted, (0, pad)).reshape(n_c, chunk)
        out, load, total = jax.lax.map(lambda a: share(*a), (xs, cs))
        out = out.reshape(n_c * chunk, d)[:T]
        load, total = load.sum(0), total.sum()
    out = out.reshape(B, S, d)
    if "shared" in params:
        out = out + mlp(params["shared"], x)
    stats = jnp.stack([load.sum(), total, (load > 0).sum(dtype=jnp.int32)])
    return out, stats


def moe_apply(params, x, cfg, *, policy=None):
    """Training entry point: capacity-bounded grouped dispatch."""
    if not cfg.moe.whole:
        raise ValueError("training needs every expert (MoEConfig.held = 0)")
    return moe_grouped(params, x, cfg, policy=policy)
