"""Inference engine: prefill/serve/decode-chunk factories and host generate.

``prefill_step`` and ``serve_step`` are the two programs the dry-run lowers
for the inference cells (prefill_32k → prefill_step; decode_32k / long_500k
→ serve_step).  Both are pure functions of (params, inputs, caches) so the
tenancy layer can AOT-compile them per (arch × shape × lease size) — the
TPU-side "instruction frame package".

The serving hot path is **chunked and donated**:

* :func:`make_decode_chunk` fuses ``n_steps`` decode iterations into one
  ``lax.scan`` program with on-device slot bookkeeping (:class:`SlotState`:
  active mask, per-slot positions, EOS/max-token detection inside the scan),
  so a batcher issues one device dispatch and one host sync per chunk
  instead of per token.
* Callers jit these programs with ``donate_argnums`` on the cache/state
  arguments so XLA updates the ring-buffer KV in place; without donation
  every token would copy the entire cache tree (the dominant decode-bytes
  term).  A donated input buffer is dead after the call — owners must adopt
  the returned tree (see ``ContinuousBatcher``).
* :func:`make_admit_step` fuses prefill + per-slot scatter admission into
  one donated program (see ``serving.batcher`` for the slot protocol).
* The vocab-padding mask is built **once** per (vocab, padded) pair
  (:meth:`ServeConfig.logit_mask`) and applied as a fused additive mask,
  instead of rebuilding a full-logits ``.at[..., vocab:].set(-inf)`` copy on
  every step.

Invariant: a slot that deactivates mid-chunk (EOS or token budget) keeps
decoding with its position frozen — it overwrites its *own* ring slot with
dead values, which is safe because admission re-seeds the slot's cache from
prefill before it is reused.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import (
    decode_step, encoder_forward, prefill, prefix_prefill, verify_step,
)
from repro.models.attention import check_attn_impl
from repro.models.transformer import Caches

from .kv_cache import pages_for


@functools.lru_cache(maxsize=32)
def _logit_mask(vocab: int, vocab_padded: int):
    """Additive mask (Vp,) — 0 on the real vocab, -inf on padding.  Built
    once and closed over by the step functions (a hoisted jit constant),
    replacing the per-step full-logits ``.set(-inf)`` copy."""
    if vocab_padded <= vocab:
        return None
    m = np.zeros((vocab_padded,), np.float32)
    m[vocab:] = -np.inf
    return jnp.asarray(m)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    attn_impl: str = "xla"       # see models.attention.ATTN_CAPABILITIES
    greedy: bool = True
    temperature: float = 1.0
    chunk: int = 8               # max decode steps fused per device dispatch

    def __post_init__(self):
        # fail at config construction, not three layers into a jit trace;
        # mode-specific checks (paged/prefix/sliding_window) happen where
        # the mode is known — ContinuousBatcher.__init__
        check_attn_impl(self.attn_impl, "dense")

    def logit_mask(self, cfg):
        return _logit_mask(cfg.vocab, cfg.vocab_padded)


def chunk_bucket(n: int) -> int:
    """Largest power of two ≤ n — the fixed set of chunk/prefill shapes the
    jit cache may hold (log2 many programs, no per-request recompiles)."""
    return 1 << (max(n, 1).bit_length() - 1)


def select_token(logits, mask, scfg: ServeConfig, key):
    """Greedy or sampled next-token selection under the vocab-padding mask."""
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    if scfg.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits.astype(jnp.float32) / scfg.temperature, axis=-1
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Single-step programs (AOT surface for cells.py / tenancy)
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, scfg: ServeConfig, *, policy=None):
    """prefill_step(params, batch) -> (last-token logits, Caches).

    batch: {"tokens": (B, S)} + family extras (extra_embeds/positions/frames).
    """

    def prefill_step(params, batch):
        kw: Dict[str, Any] = dict(impl=scfg.attn_impl, policy=policy)
        if cfg.family == "vlm":
            kw["extra_embeds"] = batch["extra_embeds"]
            kw["positions"] = batch["positions"]
        if cfg.family == "audio":
            kw["enc_out"] = encoder_forward(
                params, batch["frames"], cfg, impl=scfg.attn_impl, policy=policy
            )
        return prefill(params, batch["tokens"], cfg, max_len=scfg.max_len, **kw)

    return prefill_step


def make_serve_step(cfg, scfg: ServeConfig, *, policy=None):
    """serve_step(params, tokens (B,), caches, cur_pos (B,), key) ->
    (next_tokens (B,), logits, caches)."""
    mask = scfg.logit_mask(cfg)

    def serve_step(params, tokens, caches: Caches, cur_pos, key):
        logits, caches = decode_step(
            params, tokens, caches, cur_pos, cfg, impl=scfg.attn_impl,
            policy=policy,
        )
        if mask is not None:
            logits = logits + mask.astype(logits.dtype)
        nxt = select_token(logits, None, scfg, key)
        return nxt, logits, caches

    return serve_step


# ---------------------------------------------------------------------------
# Chunked decode with on-device slot bookkeeping
# ---------------------------------------------------------------------------


class SlotState(NamedTuple):
    """Per-slot decode bookkeeping, resident on device between dispatches.

    tokens:     (B,) int32 — last emitted token (next decode input)
    cur_pos:    (B,) int32 — absolute position the next token writes to
    active:     (B,) bool  — slot is mid-generation
    remaining:  (B,) int32 — decode tokens left until the slot's max budget
    eos:        (B,) int32 — per-slot EOS id, -1 = none
    """

    tokens: jax.Array
    cur_pos: jax.Array
    active: jax.Array
    remaining: jax.Array
    eos: jax.Array


def init_slot_state(batch: int) -> SlotState:
    return SlotState(
        tokens=jnp.zeros((batch,), jnp.int32),
        cur_pos=jnp.zeros((batch,), jnp.int32),
        active=jnp.zeros((batch,), bool),
        remaining=jnp.zeros((batch,), jnp.int32),
        eos=jnp.full((batch,), -1, jnp.int32),
    )


def make_decode_chunk(cfg, scfg: ServeConfig, n_steps: int, *, policy=None):
    """decode_chunk(params, caches, state, key) ->
    (caches, state, tokens (T, B), emitted (T, B), poisoned (B,)).

    One ``lax.scan`` over ``n_steps`` decode iterations.  EOS and
    token-budget detection happen inside the scan: a slot that finishes
    deactivates immediately, its position freezes, and later iterations
    emit nothing for it (``emitted`` is the validity mask).

    ``poisoned`` is the fault sentinel: a slot whose logits come back
    non-finite (NaN/inf — a corrupted cache page, a bad reduction) is
    deactivated *before* its token is selected or emitted, so a poisoned
    value never enters any output stream — the blast radius is the slot.
    The host requeues the flagged request (see ``ContinuousBatcher``).
    Jit this with ``donate_argnums=(1, 2)`` so the cache tree is updated
    in place.
    """
    mask = scfg.logit_mask(cfg)

    def decode_chunk(params, caches: Caches, state: SlotState, key):
        B = state.tokens.shape[0]

        def body(carry, _):
            caches, st, key, poisoned = carry
            key, sub = jax.random.split(key)
            logits, caches = decode_step(
                params, st.tokens, caches, st.cur_pos, cfg,
                impl=scfg.attn_impl, policy=policy,
            )
            bad = st.active & ~jnp.isfinite(logits).all(axis=-1)
            active = st.active & ~bad
            nxt = select_token(logits, mask, scfg, sub)
            nxt = jnp.where(active, nxt, st.tokens)
            emitted = active
            remaining = st.remaining - active.astype(jnp.int32)
            done = active & ((nxt == st.eos) | (remaining <= 0))
            st = SlotState(
                tokens=nxt,
                cur_pos=st.cur_pos + active.astype(jnp.int32),
                active=active & ~done,
                remaining=remaining,
                eos=st.eos,
            )
            return (caches, st, key, poisoned | bad), (nxt, emitted)

        poisoned0 = jnp.zeros((B,), bool)
        (caches, state, _, poisoned), (toks, emitted) = jax.lax.scan(
            body, (caches, state, key, poisoned0), None, length=n_steps
        )
        return caches, state, toks, emitted, poisoned

    return decode_chunk


class ProgramRegistry:
    """Process-wide executable LRU: one compile per (program kind × arch cfg
    × serve shape × trace-relevant shape ints) — the AOT "instruction frame
    package" discipline of the paper's static compilation stage.

    Every serving program (decode chunks, admits, speculative variants, the
    page-push helper) registers through :meth:`get` with the **same key
    scheme**: ``(kind, cfg, scfg-with-chunk-normalized, shapes, id(policy))``
    — no per-program hand-rolled key tuples.  ``scfg.chunk`` is normalized
    out because the traced program never reads it (the chunk length rides in
    ``shapes``), so batchers that differ only in their max chunk share
    executables.  Policy objects are compared by identity and pinned by the
    cached value so their id cannot be recycled while cached.  Bounded LRU:
    a long-running server that churns policies/shapes cannot grow without
    limit.

    A new batcher for the same tenant shape reuses the compiled program
    instead of re-jitting; :data:`PROGRAMS` is the module singleton every
    ``*_program`` wrapper routes through.

    Tensor-sharded programs additionally key on the **mesh fingerprint**
    (axis names × shape × concrete device ids): two tenants whose leases
    differ in TP width *or* device set must never collide — same-shape
    programs over different devices are different executables.  Per-key
    ``hits`` counters expose registry effectiveness (a re-meshed batcher
    re-keying onto an existing mesh should hit, never rebuild).
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = int(maxsize)
        self._cache: "OrderedDict[Tuple, Tuple[Any, Any]]" = OrderedDict()
        self.hits: Dict[Tuple, int] = {}

    @staticmethod
    def mesh_key(mesh) -> Optional[Tuple]:
        """Hashable fingerprint of a mesh (None passes through)."""
        if mesh is None:
            return None
        return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
                tuple(int(d.id) for d in mesh.devices.flat))

    @staticmethod
    def make_key(kind: str, cfg, scfg: Optional[ServeConfig],
                 shapes: Tuple, policy, mesh=None) -> Tuple:
        key_scfg = (None if scfg is None
                    else dataclasses.replace(scfg, chunk=0))
        return (kind, cfg, key_scfg, tuple(shapes), id(policy),
                ProgramRegistry.mesh_key(mesh))

    def get(self, kind: str, cfg, scfg: Optional[ServeConfig],
            shapes: Tuple, policy, build, *, mesh=None):
        """Return the cached executable for the key, building (and pinning
        ``policy``) on miss."""
        return self.get_raw(
            self.make_key(kind, cfg, scfg, shapes, policy, mesh),
            policy, build)

    def get_raw(self, key: Tuple, policy, build):
        hit = self._cache.get(key)
        if hit is None:
            self._cache[key] = hit = (build(), policy)
            self.hits.setdefault(key, 0)
            if len(self._cache) > self.maxsize:
                evicted, _ = self._cache.popitem(last=False)
                self.hits.pop(evicted, None)
        else:
            self.hits[key] += 1
            self._cache.move_to_end(key)
        return hit[0]

    def __len__(self) -> int:
        return len(self._cache)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._cache

    def clear(self) -> None:
        self._cache.clear()
        self.hits.clear()


PROGRAMS = ProgramRegistry()


def _tp_program(kind: str, cfg, scfg, shapes: Tuple, policy, mesh,
                build_local, *, paged: bool, n_in: int, cache_in: int,
                n_out: int, cache_out: int, donate: Tuple[int, ...]):
    """Register + build one tensor-sharded serving program.

    ``build_local(local_cfg)`` returns the un-jitted program traced at the
    shard-local model (heads/d_ff divided by tp) — the *same* make_* the
    single-device path uses.  It is wrapped in a fully-manual shard_map over
    the tenant's flat ("tp",) mesh: params follow ``tp_param_specs``, the
    KV tree ``tp_cache_specs`` (head axis split), and every other argument
    and output — slot state, page tables, draft state, token batches, PRNG
    keys — is replicated (identical on every shard: replicated inputs plus
    the policy's per-layer psums keep all non-head-sharded values
    bit-identical, which is what makes the replicated out_specs sound under
    check_vma=False).  One jit, same donation pattern as the single-device
    twin, so the ≤1 dispatch / ≤1 sync per chunk contract is unchanged.
    """
    from jax.sharding import PartitionSpec
    from repro.distributed.sharding import (
        tp_cache_specs, tp_local_cfg, tp_param_specs)

    lcfg = tp_local_cfg(cfg, int(mesh.shape["tp"]))

    def build():
        cspec = tp_cache_specs(cfg, paged=paged)
        in_specs = [PartitionSpec()] * n_in
        in_specs[0] = tp_param_specs(cfg)
        in_specs[cache_in] = cspec
        out_specs = [PartitionSpec()] * n_out
        out_specs[cache_out] = cspec
        fn = jax.shard_map(
            build_local(lcfg), mesh=mesh,
            in_specs=tuple(in_specs), out_specs=tuple(out_specs),
            axis_names={"tp"}, check_vma=False,
        )
        return jax.jit(fn, donate_argnums=donate)

    return PROGRAMS.get(kind, cfg, scfg, shapes, policy, build, mesh=mesh)


def decode_chunk_program(cfg, scfg: ServeConfig, n_steps: int, *, policy=None,
                         mesh=None):
    """Jitted :func:`make_decode_chunk` with the cache/state donated.  With
    ``mesh`` (a flat ("tp",) mesh) the chunk runs tensor-sharded and
    ``policy`` must be the batcher's ``TPShardPolicy``."""
    if mesh is not None:
        return _tp_program(
            "chunk", cfg, scfg, (int(n_steps),), policy, mesh,
            lambda lcfg: make_decode_chunk(lcfg, scfg, n_steps,
                                           policy=policy),
            paged=False, n_in=4, cache_in=1, n_out=5, cache_out=0,
            donate=(1, 2))
    return PROGRAMS.get(
        "chunk", cfg, scfg, (int(n_steps),), policy,
        lambda: jax.jit(make_decode_chunk(cfg, scfg, n_steps, policy=policy),
                        donate_argnums=(1, 2)),
    )


def admit_program(cfg, scfg: ServeConfig, *, policy=None, mesh=None):
    """Jitted :func:`make_admit_step` with the cache/state donated."""
    if mesh is not None:
        return _tp_program(
            "admit", cfg, scfg, (), policy, mesh,
            lambda lcfg: make_admit_step(lcfg, scfg, policy=policy),
            paged=False, n_in=8, cache_in=2, n_out=3, cache_out=1,
            donate=(2, 3))
    return PROGRAMS.get(
        "admit", cfg, scfg, (), policy,
        lambda: jax.jit(make_admit_step(cfg, scfg, policy=policy),
                        donate_argnums=(2, 3)),
    )


def make_admit_step(cfg, scfg: ServeConfig, *, policy=None):
    """admit_step(params, batch, caches, state, slots, pos0, budget, eos) ->
    (first_tokens (n,), caches, state).

    Right-sized admission: ``batch["tokens"]`` is (n, S) for the *bucketed*
    number of joining requests — prefill runs over n rows, not the full slot
    count — and the fresh caches are merged into the resident tree with
    per-slot scatters (``.at[:, slots].set``) instead of a full-tree
    ``jnp.where``.  Jit with ``donate_argnums=(2, 3)``.

    Duplicate entries in ``slots`` are allowed only when they carry
    identical rows (the batcher pads a partial bucket by repeating row 0),
    making the duplicate-index scatter deterministic.
    """
    mask = scfg.logit_mask(cfg)
    prefill_step = make_prefill_step(cfg, scfg, policy=policy)

    def admit_step(params, batch, caches: Caches, state: SlotState,
                   slots, pos0, budget, eos):
        logits, fresh = prefill_step(params, batch)
        # admission is greedy: the prompt's continuation token
        if mask is not None:
            logits = logits + mask.astype(logits.dtype)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def merge(old, new):
            return old.at[:, slots].set(new.astype(old.dtype))

        kv = jax.tree.map(merge, caches.kv, fresh.kv)
        ssm = jax.tree.map(merge, caches.ssm, fresh.ssm)
        cross = caches.cross
        if cross is not None and fresh.cross is not None:
            cross = jax.tree.map(merge, cross, fresh.cross)
        # the admission token already counts toward the budget; a slot with
        # nothing left (or an immediate EOS) never activates
        remaining = budget - 1
        state = SlotState(
            tokens=state.tokens.at[slots].set(nxt),
            cur_pos=state.cur_pos.at[slots].set(pos0),
            active=state.active.at[slots].set(
                (remaining > 0) & (nxt != eos)
            ),
            remaining=state.remaining.at[slots].set(remaining),
            eos=state.eos.at[slots].set(eos),
        )
        return nxt, Caches(kv=kv, ssm=ssm, cross=cross), state

    return admit_step


# ---------------------------------------------------------------------------
# Paged KV: on-device page tables, free-list and page-fault allocation
# ---------------------------------------------------------------------------


class PageState(NamedTuple):
    """Device-resident page-pool bookkeeping, donated alongside the caches.

    table:    (B, max_pages) int32 — physical page backing each slot's
              logical page (absolute positions [j*ps, (j+1)*ps)); -1 =
              unmapped.  A physical page is mapped by at most one
              (slot, logical) entry — the no-double-mapping invariant.
    free:     (n_pages + 1,) int32 — stack of free page ids; entries
              [0, free_top) are valid, the last element is scratch for
              masked-out pushes (mirrors the trash page of the pool).
    free_top: () int32 — stack pointer; allocated pages = n_pages - free_top.
    quota:    () int32 — lease cap on allocated pages (the hypervisor's
              ``kv_pages`` dimension); a fault beyond it is denied even if
              the pool has free pages.
    pinned:   (B,) int32 — leading logical pages of each slot's row that are
              owned by the **prefix cache** (shared, read-only): a finishing
              slot never pushes them back onto the free stack — the host's
              refcount ledger decides when a shared page becomes free.
              Decode never writes them either, by construction: the write
              position's logical page is ``cur_pos // page_size >= pinned``.
    """

    table: jax.Array
    free: jax.Array
    free_top: jax.Array
    quota: jax.Array
    pinned: jax.Array

    @property
    def n_pages(self) -> int:
        return self.free.shape[0] - 1


def init_page_state(batch: int, n_pages: int, max_pages: int,
                    *, quota: Optional[int] = None) -> PageState:
    return PageState(
        table=jnp.full((batch, max_pages), -1, jnp.int32),
        free=jnp.concatenate([jnp.arange(n_pages, dtype=jnp.int32),
                              jnp.full((1,), -1, jnp.int32)]),
        free_top=jnp.int32(n_pages),
        quota=jnp.int32(n_pages if quota is None else min(quota, n_pages)),
        pinned=jnp.zeros((batch,), jnp.int32),
    )


def _free_finished_pages(pages_table, free, free_top, finished, pinned):
    """Push every *private* page mapped by a ``finished`` slot back onto the
    free stack (cumsum-ranked scatter; masked-out entries land on the
    scratch element) and clear those table rows.  The slot's first
    ``pinned`` logical pages are cache-owned (shared) and are NOT pushed —
    the host releases their refcounts at sync time.  Returns
    (table, free, free_top, pinned)."""
    scratch = free.shape[0] - 1
    maxp = pages_table.shape[1]
    private = jnp.arange(maxp, dtype=jnp.int32)[None, :] >= pinned[:, None]
    pmask = finished[:, None] & (pages_table >= 0) & private
    flat = pmask.reshape(-1)
    prank = jnp.cumsum(flat.astype(jnp.int32)) - 1
    idx = jnp.where(flat, free_top + prank, scratch)
    free = free.at[idx].set(pages_table.reshape(-1))
    free_top = free_top + flat.sum(dtype=jnp.int32)
    table = jnp.where(finished[:, None], -1, pages_table)
    pinned = jnp.where(finished, 0, pinned)
    return table, free, free_top, pinned


def make_paged_decode_chunk(cfg, scfg: ServeConfig, n_steps: int,
                            page_size: int, *, policy=None):
    """decode_chunk(params, caches, state, pages, key) ->
    (caches, state, pages, tokens (T, B), emitted (T, B), poisoned (B,),
    ctr (4,) int32, or (7,) for a model with experts).

    ``ctr`` is the chunk's device-counter vector — pages popped off the
    free stack, pages pushed back by in-scan frees, slot-steps denied a
    grant, and (speculative twin only; 0 here) draft tokens accepted —
    accumulated across the scan so the host-side telemetry sees in-chunk
    paging activity without an extra sync (it rides back in the same
    fetch as the tokens).  A model with experts appends the expert
    share's counts (``moe.EXPERT_STATS``) of the active slots, summed over
    steps and layers.

    The paged twin of :func:`make_decode_chunk`: same ``lax.scan`` with the
    same EOS/budget bookkeeping, plus **page faults handled inside the
    chunk boundary** — a slot whose write position crosses into an
    unmapped logical page pops a page from the device free stack before
    the decode step (so the batcher still pays ≤1 dispatch and ≤1 host
    sync per chunk).  Grants are prefix-ordered by slot index (both the
    stack bound and the quota bound are monotone in the cumsum rank, so a
    denied slot implies every later needer is denied too — pops stay
    contiguous at the top of the stack).  A denied slot (pool dry or
    quota hit) deactivates immediately without emitting — the host sees
    ``active`` drop without EOS/budget and requeues the request.  Pages
    of slots that finish (EOS, budget, denial, or the ``poisoned``
    NaN/inf sentinel — see :func:`make_decode_chunk`) are pushed back
    onto the stack in the same step, so capacity frees mid-chunk.  Jit
    with ``donate_argnums=(1, 2, 3)``.
    """
    mask = scfg.logit_mask(cfg)
    ps = int(page_size)
    experts = cfg.moe is not None

    def decode_chunk(params, caches: Caches, state: SlotState,
                     pages: PageState, key):
        n_pages = pages.free.shape[0] - 1
        B = state.tokens.shape[0]
        bidx = jnp.arange(B)

        def body(carry, _):
            caches, st, pg, key, poisoned, ctr = carry
            key, sub = jax.random.split(key)
            # -- page fault: map the write position's logical page --------
            logical = (st.cur_pos // ps).astype(jnp.int32)
            cur_pid = jnp.take_along_axis(pg.table, logical[:, None], axis=1)[:, 0]
            need = st.active & (cur_pid < 0)
            rank = jnp.cumsum(need.astype(jnp.int32)) - 1
            allocated = n_pages - pg.free_top
            got = need & (rank < pg.free_top) & (allocated + rank < pg.quota)
            pid = pg.free[jnp.clip(pg.free_top - 1 - rank, 0, n_pages)]
            table = pg.table.at[bidx, logical].set(
                jnp.where(got, pid, cur_pid))
            popped = got.sum(dtype=jnp.int32)
            free_top = pg.free_top - popped
            oom = need & ~got
            active = st.active & ~oom
            # -- decode against the (updated) page table ------------------
            out = decode_step(
                params, st.tokens, caches, st.cur_pos, cfg,
                impl=scfg.attn_impl, policy=policy, page_table=table,
                with_stats=experts, mask=active,
            )
            logits, caches = out[:2]
            bad = active & ~jnp.isfinite(logits).all(axis=-1)
            active = active & ~bad
            nxt = select_token(logits, mask, scfg, sub)
            nxt = jnp.where(active, nxt, st.tokens)
            emitted = active
            remaining = st.remaining - active.astype(jnp.int32)
            done = active & ((nxt == st.eos) | (remaining <= 0))
            # -- recycle pages of finished slots --------------------------
            ft_pop = free_top
            table, free, free_top, pinned = _free_finished_pages(
                table, pg.free, ft_pop, done | oom | bad, pg.pinned)
            ctr = ctr + jnp.stack(
                [popped, free_top - ft_pop, oom.sum(dtype=jnp.int32),
                 jnp.int32(0)] + ([*out[2]] if experts else []))
            st = SlotState(
                tokens=nxt,
                cur_pos=st.cur_pos + active.astype(jnp.int32),
                active=active & ~done,
                remaining=remaining,
                eos=st.eos,
            )
            pg = PageState(table=table, free=free, free_top=free_top,
                           quota=pg.quota, pinned=pinned)
            return (caches, st, pg, key, poisoned | bad, ctr), (nxt, emitted)

        poisoned0 = jnp.zeros((B,), bool)
        ctr0 = jnp.zeros((7 if experts else 4,), jnp.int32)
        (caches, state, pages, _, poisoned, ctr), (toks, emitted) = \
            jax.lax.scan(
                body, (caches, state, pages, key, poisoned0, ctr0), None,
                length=n_steps
            )
        return caches, state, pages, toks, emitted, poisoned, ctr

    return decode_chunk


def _grant_admission_pages(pages: PageState, ask, np_: int):
    """Prefix-feasible page grants for one admission batch: every asking
    row needs ``np_`` pages.  ``cum`` is monotone, so stack/quota denials
    only ever cut a suffix — pops stay contiguous at the stack top.
    Shared by the cold and cached admit programs (one discipline, edited
    once).  Returns (ok, grant, pid (n, np_), dest, free_top)."""
    n_pages = pages.free.shape[0] - 1
    cum = jnp.cumsum(ask.astype(jnp.int32)) * np_
    allocated = n_pages - pages.free_top
    ok = (cum <= pages.free_top) & (allocated + cum <= pages.quota)
    grant = ask & ok
    ranks = ((jnp.cumsum(grant.astype(jnp.int32)) - 1)[:, None] * np_
             + jnp.arange(np_, dtype=jnp.int32)[None, :])          # (n, np_)
    pid = pages.free[jnp.clip(pages.free_top - 1 - ranks, 0, n_pages)]
    dest = jnp.where(grant[:, None], pid, n_pages)                 # trash
    free_top = pages.free_top - grant.sum(dtype=jnp.int32) * np_
    return ok, grant, pid, dest, free_top


def _scatter_fresh_kv(caches_kv, fresh_kv, dest, *, S: int, np_: int,
                      ps: int, n: int):
    """Scatter freshly-prefilled K/V (per layer: (nb, n, S, Hkv, dh)) into
    the popped pool pages at ``dest`` ((n, np_); trash for denied rows).
    ``fresh_kv`` maps layer key -> (k, v)."""
    pad = np_ * ps - S

    def to_pages(a):
        # (nb, n, S, ...) -> (nb, n * np_, ps, ...)
        if pad:
            width = ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3)
            a = jnp.pad(a, width)
        return a.reshape(a.shape[0], n * np_, ps, *a.shape[3:])

    def scatter(old, new):
        return old.at[:, dest.reshape(-1)].set(to_pages(new).astype(old.dtype))

    return {
        p: type(view)(k=scatter(view.k, fresh_kv[p][0]),
                      v=scatter(view.v, fresh_kv[p][1]))
        for p, view in caches_kv.items()
    }


def make_paged_admit_step(cfg, scfg: ServeConfig, *, policy=None):
    """admit_step(params, batch, caches, state, pages, slots, pos0, budget,
    eos, real, pin) -> (first_tokens (n,), caches, state, pages, rows), and
    for a model with experts a sixth output: the expert share's counts
    (``moe.EXPERT_STATS``, (3,) int32) over the ``real`` rows.

    Paged admission: right-sized bucketed prefill exactly like
    :func:`make_admit_step`, but the fresh K/V is scattered into
    **freshly-popped pool pages** instead of per-slot dense rows, and the
    joining slots' page-table rows are rewritten.  ``real`` (n,) bool marks
    genuine rows — bucket padding duplicates row 0 and must neither pop
    pages nor write conflicting values (every duplicate scatter carries row
    0's values, keeping the duplicate-index writes deterministic).  A row
    that never activates (immediate EOS / zero budget / allocation denied)
    gets no pages and a cleared table row.  ``pin`` (n,) int32 is the
    prefix-cache pin plan: how many of the row's leading logical pages the
    host will insert into the shared prefix cache after the sync (0 when
    prefix caching is off) — recorded in ``PageState.pinned`` so the chunk
    scan never recycles them.  ``rows`` returns the written page-table rows
    so the host learns the physical ids it is about to share.  Jit with
    ``donate_argnums=(2, 3, 4)``.
    """
    mask = scfg.logit_mask(cfg)
    experts = cfg.moe is not None

    def admit_step(params, batch, caches: Caches, state: SlotState,
                   pages: PageState, slots, pos0, budget, eos, real, pin):
        ps = None
        for view in caches.kv.values():
            ps = view.k.shape[2]
            break
        assert ps is not None, "paged admission needs at least one attn layer"
        kw: Dict[str, Any] = dict(impl=scfg.attn_impl, policy=policy)
        S = batch["tokens"].shape[1]
        if cfg.family == "vlm":
            kw["extra_embeds"] = batch["extra_embeds"]
            kw["positions"] = batch["positions"]
            S += batch["extra_embeds"].shape[1]
        if cfg.family == "audio":
            kw["enc_out"] = encoder_forward(
                params, batch["frames"], cfg, impl=scfg.attn_impl, policy=policy
            )
        # seed a dense cache sized exactly to the prompt: identity placement,
        # so fresh K/V rows are in absolute-position order for page packing
        out = prefill(params, batch["tokens"], cfg, max_len=S,
                      with_stats=experts, mask=real, rings=False, **kw)
        logits, fresh = out[:2]
        if mask is not None:
            logits = logits + mask.astype(logits.dtype)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        n = nxt.shape[0]
        np_ = pages_for(S, ps)
        maxp = pages.table.shape[1]
        remaining = budget - 1
        wants = (remaining > 0) & (nxt != eos)
        ask = real & wants
        ok, grant, pid, dest, free_top = _grant_admission_pages(
            pages, ask, np_)

        # page-table rows: granted rows map their np_ pages, everything else
        # clears; padding rows carry row 0's values (duplicate-scatter rule)
        row = jnp.full((n, maxp), -1, jnp.int32).at[:, :np_].set(
            jnp.where(grant[:, None], pid, -1))
        row = jnp.where(real[:, None], row, row[0:1])
        table = pages.table.at[slots].set(row)

        kv = _scatter_fresh_kv(
            caches.kv, {p: (fresh.kv[p].k, fresh.kv[p].v) for p in caches.kv},
            dest, S=S, np_=np_, ps=ps, n=n)

        def merge(old, new):
            return old.at[:, slots].set(new.astype(old.dtype))

        ssm = jax.tree.map(merge, caches.ssm, fresh.ssm)
        cross = caches.cross
        if cross is not None and fresh.cross is not None:
            cross = jax.tree.map(merge, cross, fresh.cross)

        activates = wants & (ok | (np_ == 0))
        act_vals = jnp.where(real, activates, activates[0])
        # pin plan only sticks for rows that really mapped their pages;
        # padding rows carry row 0's value (duplicate-scatter rule)
        pin_vals = jnp.where(grant, jnp.clip(pin, 0, np_), 0)
        pin_vals = jnp.where(real, pin_vals, pin_vals[0])
        state = SlotState(
            tokens=state.tokens.at[slots].set(nxt),
            cur_pos=state.cur_pos.at[slots].set(pos0),
            active=state.active.at[slots].set(act_vals),
            remaining=state.remaining.at[slots].set(remaining),
            eos=state.eos.at[slots].set(eos),
        )
        pages = PageState(table=table, free=pages.free, free_top=free_top,
                          quota=pages.quota,
                          pinned=pages.pinned.at[slots].set(pin_vals))
        return (nxt, Caches(kv=kv, ssm=ssm, cross=cross), state, pages,
                row) + out[2:]

    return admit_step


def paged_decode_chunk_program(cfg, scfg: ServeConfig, n_steps: int,
                               page_size: int, *, policy=None, mesh=None):
    """Jitted :func:`make_paged_decode_chunk`, caches/state/pages donated.
    Sharded under ``mesh``: the page pool's head axis splits, the page-fault
    machinery (tables, free stack, grants) is replicated — every shard pops
    the same pages, writes its own heads into them."""
    if mesh is not None:
        return _tp_program(
            "paged_chunk", cfg, scfg, (int(n_steps), int(page_size)),
            policy, mesh,
            lambda lcfg: make_paged_decode_chunk(lcfg, scfg, n_steps,
                                                 page_size, policy=policy),
            paged=True, n_in=5, cache_in=1, n_out=7, cache_out=0,
            donate=(1, 2, 3))
    return PROGRAMS.get(
        "paged_chunk", cfg, scfg, (int(n_steps), int(page_size)), policy,
        lambda: jax.jit(
            make_paged_decode_chunk(cfg, scfg, n_steps, page_size,
                                    policy=policy),
            donate_argnums=(1, 2, 3)),
    )


def paged_admit_program(cfg, scfg: ServeConfig, *, policy=None, mesh=None):
    """Jitted :func:`make_paged_admit_step`, caches/state/pages donated."""
    if mesh is not None:
        return _tp_program(
            "paged_admit", cfg, scfg, (), policy, mesh,
            lambda lcfg: make_paged_admit_step(lcfg, scfg, policy=policy),
            paged=True, n_in=11, cache_in=2,
            n_out=5 + (cfg.moe is not None), cache_out=1,
            donate=(2, 3, 4))
    return PROGRAMS.get(
        "paged_admit", cfg, scfg, (), policy,
        lambda: jax.jit(make_paged_admit_step(cfg, scfg, policy=policy),
                        donate_argnums=(2, 3, 4)),
    )


def make_cached_admit_step(cfg, scfg: ServeConfig, n_prefix_pages: int,
                           *, policy=None):
    """admit_step(params, batch, caches, state, pages, slots, pos0, budget,
    eos, real, prefix_pids, pin) -> (first_tokens, caches, state, pages,
    rows) — shared-prefix admission; a model with experts adds its counts
    as :func:`make_paged_admit_step` does.

    The cached twin of :func:`make_paged_admit_step` for rows whose prompt's
    first ``n_prefix_pages`` logical pages are already resident in the
    prefix cache: ``batch["tokens"]`` carries only the **uncached suffix**
    (``prompt_len - n_prefix_pages * page_size`` tokens), the cached pages'
    K/V is gathered from the pool and attended to as a prefix context
    (:func:`repro.models.prefix_prefill`), and only the suffix pages are
    popped from the free stack.  ``prefix_pids`` (n, n_prefix_pages) are the
    cached physical page ids, mapped **read-only** into the joining slot's
    table row — the copy-on-write discipline: the divergent tail (at
    minimum the page holding the last prompt token — the prefix is capped
    at ``(prompt_len - 1) // page_size`` pages, so a *fully* cached prompt
    still prefills its last page privately) always writes private pages,
    shared pages are never written.  ``pin`` (n,) counts the row's leading
    cache-owned logical pages (hits + the host's planned inserts), recorded
    in ``PageState.pinned``.  Bucketing/padding rules are identical to the
    cold program.  Jit with ``donate_argnums=(2, 3, 4)``.
    """
    mask = scfg.logit_mask(cfg)
    experts = cfg.moe is not None
    kp = int(n_prefix_pages)
    assert kp >= 1, "use the cold paged admit program for zero cached pages"

    def admit_step(params, batch, caches: Caches, state: SlotState,
                   pages: PageState, slots, pos0, budget, eos, real,
                   prefix_pids, pin):
        ps = None
        for view in caches.kv.values():
            ps = view.k.shape[2]
            break
        assert ps is not None, "cached admission needs at least one attn layer"
        Lp = kp * ps
        n, S = batch["tokens"].shape                       # S = suffix length

        # cached prefix context: pool pages -> (nb, n, Lp, Hkv, dh) per layer
        def gather(a):
            g = a[:, prefix_pids]                          # (nb,n,kp,ps,H,dh)
            return g.reshape(g.shape[0], n, Lp, *g.shape[4:])

        prefix_kv = {p: (gather(view.k), gather(view.v))
                     for p, view in caches.kv.items()}
        out = prefix_prefill(
            params, batch["tokens"], prefix_kv, cfg, prefix_len=Lp,
            impl=scfg.attn_impl, policy=policy, with_stats=experts, mask=real,
        )
        logits, ys = out[:2]
        if mask is not None:
            logits = logits + mask.astype(logits.dtype)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        np_ = pages_for(S, ps)                             # private pages
        maxp = pages.table.shape[1]
        remaining = budget - 1
        wants = (remaining > 0) & (nxt != eos)
        ask = real & wants
        ok, grant, pid, dest, free_top = _grant_admission_pages(
            pages, ask, np_)

        # table row: [shared prefix (read-only) | fresh suffix | -1 ...]
        row = jnp.full((n, maxp), -1, jnp.int32)
        row = row.at[:, :kp].set(jnp.where(grant[:, None], prefix_pids, -1))
        row = row.at[:, kp:kp + np_].set(jnp.where(grant[:, None], pid, -1))
        row = jnp.where(real[:, None], row, row[0:1])
        table = pages.table.at[slots].set(row)

        kv = _scatter_fresh_kv(caches.kv, ys, dest, S=S, np_=np_, ps=ps, n=n)

        activates = wants & ok
        act_vals = jnp.where(real, activates, activates[0])
        pin_vals = jnp.where(grant, jnp.clip(pin, kp, kp + np_), 0)
        pin_vals = jnp.where(real, pin_vals, pin_vals[0])
        state = SlotState(
            tokens=state.tokens.at[slots].set(nxt),
            cur_pos=state.cur_pos.at[slots].set(pos0),
            active=state.active.at[slots].set(act_vals),
            remaining=state.remaining.at[slots].set(remaining),
            eos=state.eos.at[slots].set(eos),
        )
        pages = PageState(table=table, free=pages.free, free_top=free_top,
                          quota=pages.quota,
                          pinned=pages.pinned.at[slots].set(pin_vals))
        return (nxt, Caches(kv=kv, ssm=caches.ssm, cross=caches.cross),
                state, pages, row) + out[2:]

    return admit_step


def cached_admit_program(cfg, scfg: ServeConfig, n_prefix_pages: int,
                         *, policy=None, mesh=None):
    """Jitted :func:`make_cached_admit_step`, caches/state/pages donated.
    One executable per (arch × serve shape × prefix-page count) — the
    prefix-page counts are bounded by ``prompt_len / page_size``, so the
    program cache stays small."""
    if mesh is not None:
        return _tp_program(
            "cached_admit", cfg, scfg, (int(n_prefix_pages),), policy, mesh,
            lambda lcfg: make_cached_admit_step(lcfg, scfg, n_prefix_pages,
                                                policy=policy),
            paged=True, n_in=12, cache_in=2,
            n_out=5 + (cfg.moe is not None), cache_out=1,
            donate=(2, 3, 4))
    return PROGRAMS.get(
        "cached_admit", cfg, scfg, (int(n_prefix_pages),), policy,
        lambda: jax.jit(
            make_cached_admit_step(cfg, scfg, n_prefix_pages, policy=policy),
            donate_argnums=(2, 3, 4)),
    )


def make_page_push():
    """push(pages, pids (K,)) -> pages — return evicted prefix-cache pages
    (host decision: refcount hit 0 and the LRU chose them) to the device
    free stack.  ``pids`` entries < 0 are padding.  Jit with
    ``donate_argnums=(0,)``."""

    def push(pages: PageState, pids):
        scratch = pages.free.shape[0] - 1
        valid = pids >= 0
        rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
        idx = jnp.where(valid, pages.free_top + rank, scratch)
        free = pages.free.at[idx].set(pids)
        return pages._replace(
            free=free, free_top=pages.free_top + valid.sum(dtype=jnp.int32))

    return push


def page_push_program():
    """Jitted :func:`make_page_push` (page state donated); one cached
    executable, re-traced per pid-vector shape by jit itself."""
    return PROGRAMS.get(
        "page_push", None, None, (), None,
        lambda: jax.jit(make_page_push(), donate_argnums=(0,)),
    )


# ---------------------------------------------------------------------------
# Speculative decode: n-gram drafting + batched verify inside the chunk scan
# ---------------------------------------------------------------------------
#
# The chunked scan's unit of work changes from one token to one **window**:
# a drafter proposes ``W - 1`` continuation tokens per slot from an
# on-device n-gram history, a single multi-query ``verify_step`` scores the
# committed token plus all drafts in one pass (W query positions instead of
# 1), and accept/rollback bookkeeping commits the longest draft prefix the
# greedy model agrees with, plus one bonus token.  Greedy acceptance is
# exact: position ``w`` of the verify logits is conditioned on exactly the
# tokens sequential greedy decode would have seen iff drafts ``1..w`` all
# matched — so the committed tokens are **token-identical to non-speculative
# greedy decode by construction**, and the win is purely dispatch/bandwidth
# (one cache sweep serves W positions).
#
# Rollback is *overwrite-before-attend*, not state surgery: rejected
# positions' KV writes are left in place (dense: masked beyond the budget so
# the ring never wraps onto live context; paged: stale offsets in mapped
# pages), ``cur_pos`` rewinds by simply not advancing past the commit point,
# and the next window rewrites every stale position before any query can
# attend to it (the window always spans at least as far as the previous
# window's overshoot).  Likewise "page-table rewind" for rejected tokens:
# pages mapped for the overshoot are *retained* as prefetched capacity —
# they are exactly the pages the next window needs — and are recycled by
# ``_free_finished_pages`` the moment the slot finishes.


class DraftState(NamedTuple):
    """On-device n-gram drafter history, donated alongside the caches.

    hist: (B, N) int32 — last ``N`` committed tokens per slot, newest at
          index ``N - 1``, front-padded with -1 (never a valid token, so
          padding cannot match).
    n:    (B,) int32 — count of valid entries (≤ N).
    """

    hist: jax.Array
    n: jax.Array


def init_draft_state(batch: int, hist_len: int) -> DraftState:
    return DraftState(
        hist=jnp.full((batch, hist_len), -1, jnp.int32),
        n=jnp.zeros((batch,), jnp.int32),
    )


def _propose_drafts(draft: DraftState, last, n_draft: int, ngram: int):
    """(B, n_draft) draft tokens: find the most recent earlier occurrence of
    the trailing ``ngram`` committed tokens and propose its continuation;
    slots with no match fall back to repeating the last token (free to
    verify — the window runs at fixed width W regardless)."""
    hist, n = draft.hist, draft.n
    B, N = hist.shape
    idx = jnp.arange(N, dtype=jnp.int32)
    m = jnp.ones((B, N), bool)
    for g in range(ngram):
        # shifted[:, i] = hist[:, i - g] (−1 beyond the front): candidate
        # n-gram *ending* at i matches the trailing n-gram ending at N-1
        shifted = (hist if g == 0 else
                   jnp.pad(hist, ((0, 0), (g, 0)),
                           constant_values=-1)[:, :N])
        m = m & (shifted == hist[:, N - 1 - g][:, None])
    # candidate must end strictly before the trailing n-gram and span only
    # valid history: i - ngram + 1 >= N - n
    m = m & (idx[None, :] < N - 1) & (idx[None, :] >= (N - n + ngram - 1)[:, None])
    match_idx = jnp.max(jnp.where(m, idx[None, :], -1), axis=1)       # (B,)
    found = (match_idx >= 0) & (n >= ngram + 1)
    cont = jnp.clip(
        match_idx[:, None] + 1 + jnp.arange(n_draft, dtype=jnp.int32)[None, :],
        0, N - 1)
    proposed = jnp.take_along_axis(hist, cont, axis=1)
    fallback = jnp.broadcast_to(last[:, None], (B, n_draft))
    return jnp.where(found[:, None], proposed, fallback).astype(jnp.int32)


def _advance_draft(draft: DraftState, toks, c):
    """Shift ``c[b]`` committed tokens (``toks[b, :c[b]]``) into each slot's
    history.  Gather indices never reach past position ``N - 1 + c[b]`` of
    the concatenation, so uncommitted window tokens are never read."""
    hist, n = draft.hist, draft.n
    N = hist.shape[1]
    ext = jnp.concatenate([hist, toks.astype(jnp.int32)], axis=1)
    idx = jnp.arange(N, dtype=jnp.int32)[None, :] + c[:, None]
    return DraftState(
        hist=jnp.take_along_axis(ext, idx, axis=1),
        n=jnp.minimum(n + c, N).astype(jnp.int32),
    )


def _spec_accept(q_toks, g, st: SlotState, active):
    """The acceptance algebra shared by the dense and paged spec chunks.

    ``g[b, w]`` is the greedy token given the prefix through ``q_toks[b, w]``
    — valid as a sequential-greedy output iff drafts ``1..w`` all matched,
    which is exactly what the cumulative-product acceptance scan checks, so
    garbage positions (wrong-context logits after the first mismatch) can
    never be committed.  Returns (c, nxt, done, emitted):

      c       (B,) int32 — committed tokens this window: the accepted draft
              prefix + 1 bonus token, cut at the first EOS and at the
              remaining budget; ≥ 1 for active slots (the bonus token is
              unconditional, mirroring one non-speculative step).
      nxt     (B,) int32 — last committed token (next window's root).
      done    (B,) bool  — EOS committed or budget exhausted.
      emitted (B, W) bool — prefix mask ``w < c`` over the window outputs.
    """
    W = g.shape[1]
    wi = jnp.arange(W, dtype=jnp.int32)
    acc = (q_toks[:, 1:] == g[:, :-1]).astype(jnp.int32)       # (B, W-1)
    e = 1 + jnp.cumprod(acc, axis=1).sum(axis=1)               # (B,) in [1,W]
    is_eos = (st.eos[:, None] >= 0) & (g == st.eos[:, None])
    fe = jnp.where(is_eos.any(axis=1),
                   jnp.argmax(is_eos, axis=1), W)              # first EOS
    # EOS beyond the accepted prefix (fe >= e) is a garbage-position token
    # and is correctly ignored: c = min(e, ...) cuts before it
    c = jnp.minimum(jnp.minimum(e, fe + 1), st.remaining)
    c = jnp.where(active, c, 0)
    hit_eos = fe < c                 # ⟺ the EOS is the last committed token
    done = active & (hit_eos | (st.remaining - c <= 0))
    nxt = jnp.take_along_axis(g, jnp.clip(c - 1, 0, W - 1)[:, None],
                              axis=1)[:, 0]
    nxt = jnp.where(active, nxt, st.tokens)
    emitted = active[:, None] & (wi[None, :] < c[:, None])
    return c, nxt, done, emitted


def make_spec_decode_chunk(cfg, scfg: ServeConfig, n_windows: int,
                           window: int, ngram: int, *, policy=None):
    """spec_chunk(params, caches, state, draft, key) ->
    (caches, state, draft, tokens (Tw, B, W), emitted (Tw, B, W), poisoned).

    The speculative twin of :func:`make_decode_chunk`: ``n_windows``
    draft-and-verify windows of width ``window`` per dispatch.  Greedy only
    — acceptance compares argmax tokens, which is meaningless under
    sampling.  ``emitted`` is a per-window *prefix* mask (the committed
    tokens are ``tokens[t, b, :c]``); the poison sentinel discards the whole
    window for a slot whose committable logits come back non-finite.  The
    dense ring writes are masked at the remaining budget (``write_limit``)
    so overshoot writes can never wrap the ring onto live context.  Jit
    with ``donate_argnums=(1, 2, 3)``.
    """
    assert scfg.greedy, "speculative decode requires greedy selection"
    mask = scfg.logit_mask(cfg)
    W = int(window)

    def spec_chunk(params, caches: Caches, state: SlotState,
                   draft: DraftState, key):
        del key  # greedy: kept for signature parity with the sampled chunk
        B = state.tokens.shape[0]
        wi = jnp.arange(W, dtype=jnp.int32)

        def body(carry, _):
            caches, st, dr, poisoned = carry
            drafts = _propose_drafts(dr, st.tokens, W - 1, ngram)
            q_toks = jnp.concatenate([st.tokens[:, None], drafts], axis=1)
            logits, caches = verify_step(
                params, q_toks, caches, st.cur_pos, cfg,
                impl=scfg.attn_impl, policy=policy,
                write_limit=st.remaining,
            )
            if mask is not None:
                logits = logits + mask.astype(logits.dtype)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (B, W)
            # poison only on committable positions: beyond the remaining
            # budget the ring write was masked and the query may read stale
            # slots — garbage there is expected and can never be emitted
            finite = jnp.isfinite(logits).all(axis=-1)          # (B, W)
            committable = wi[None, :] < st.remaining[:, None]
            bad = st.active & ~(finite | ~committable).all(axis=1)
            active = st.active & ~bad
            c, nxt, done, emitted = _spec_accept(q_toks, g, st, active)
            dr = _advance_draft(dr, g, c)
            st = SlotState(
                tokens=nxt,
                cur_pos=st.cur_pos + c,
                active=active & ~done,
                remaining=st.remaining - c,
                eos=st.eos,
            )
            return (caches, st, dr, poisoned | bad), (g, emitted)

        poisoned0 = jnp.zeros((B,), bool)
        (caches, state, draft, poisoned), (toks, emitted) = jax.lax.scan(
            body, (caches, state, draft, poisoned0), None, length=n_windows
        )
        return caches, state, draft, toks, emitted, poisoned

    return spec_chunk


def make_paged_spec_decode_chunk(cfg, scfg: ServeConfig, n_windows: int,
                                 window: int, ngram: int, page_size: int,
                                 *, policy=None):
    """spec_chunk(params, caches, state, pages, draft, key) ->
    (caches, state, pages, draft, tokens (Tw, B, W), emitted, poisoned,
    ctr (4,) int32).

    ``ctr`` = (pages popped, pages pushed, fault-denied slots, draft
    tokens accepted), accumulated in-scan — the same device-counter
    vector :func:`make_paged_decode_chunk` returns, with the speculative
    accept count in the last slot so telemetry sees per-window acceptance
    without an extra sync.

    Paged speculative chunk: the page fault inside the scan maps **every
    logical page the window's committable span touches** (up to
    ``(W - 2) // page_size + 2`` pages), all-or-nothing per slot — a slot
    that cannot map its full span is denied and requeued like a single-page
    OOM, so a half-mapped window can never commit tokens whose KV landed in
    the trash page.  Grants stay prefix-feasible: the per-slot page need is
    cumsum-ranked, both the stack bound and the quota bound are monotone in
    that rank, so denials cut a suffix and pops stay contiguous at the top
    of the stack.  Overshoot pages are retained (they are the next window's
    pages) and recycled by :func:`_free_finished_pages` when the slot
    finishes.  Jit with ``donate_argnums=(1, 2, 3, 4)``.
    """
    assert scfg.greedy, "speculative decode requires greedy selection"
    mask = scfg.logit_mask(cfg)
    W = int(window)
    ps = int(page_size)
    # max logical pages [cur, cur + W - 1] can span: the first page may be
    # entered mid-page, every later one is full
    max_span = (W - 2) // ps + 2

    def spec_chunk(params, caches: Caches, state: SlotState,
                   pages: PageState, draft: DraftState, key):
        del key  # greedy: kept for signature parity with the sampled chunk
        n_pages = pages.free.shape[0] - 1
        B = state.tokens.shape[0]
        maxp = pages.table.shape[1]
        bidx = jnp.arange(B)
        wi = jnp.arange(W, dtype=jnp.int32)

        def body(carry, _):
            caches, st, pg, dr, poisoned, ctr = carry
            # -- multi-page fault over the window's committable span -------
            weff = jnp.minimum(W, st.remaining)      # positions that can
            l0 = (st.cur_pos // ps).astype(jnp.int32)  # ever be committed
            l1 = ((st.cur_pos + jnp.maximum(weff, 1) - 1) // ps).astype(
                jnp.int32)
            span = l0[:, None] + jnp.arange(max_span, dtype=jnp.int32)[None, :]
            in_span = span <= l1[:, None]
            col = jnp.clip(span, 0, maxp - 1)
            cur = jnp.take_along_axis(pg.table, col, axis=1)  # (B, max_span)
            need = st.active[:, None] & in_span & (cur < 0)
            need_cnt = need.sum(axis=1)
            base = jnp.cumsum(need_cnt) - need_cnt
            allocated = n_pages - pg.free_top
            fits = ((base + need_cnt <= pg.free_top)
                    & (allocated + base + need_cnt <= pg.quota))
            got = (need_cnt > 0) & fits
            oom = st.active & (need_cnt > 0) & ~fits
            rank_in = jnp.cumsum(need.astype(jnp.int32), axis=1) - need
            flat_rank = base[:, None] + rank_in
            pop = need & got[:, None]
            pid = pg.free[jnp.clip(pg.free_top - 1 - flat_rank, 0, n_pages)]
            table = pg.table
            for s in range(max_span):
                table = table.at[bidx, col[:, s]].set(
                    jnp.where(pop[:, s], pid[:, s], cur[:, s]))
            popped = pop.sum(dtype=jnp.int32)
            free_top = pg.free_top - popped
            active = st.active & ~oom
            # -- draft + batched verify against the (updated) table --------
            drafts = _propose_drafts(dr, st.tokens, W - 1, ngram)
            q_toks = jnp.concatenate([st.tokens[:, None], drafts], axis=1)
            logits, caches = verify_step(
                params, q_toks, caches, st.cur_pos, cfg,
                impl=scfg.attn_impl, policy=policy, page_table=table,
            )
            if mask is not None:
                logits = logits + mask.astype(logits.dtype)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # overshoot positions past the budget may write to / read from
            # unmapped (trash-redirected) pages — only committable positions
            # can poison
            finite = jnp.isfinite(logits).all(axis=-1)
            committable = wi[None, :] < st.remaining[:, None]
            bad = active & ~(finite | ~committable).all(axis=1)
            active = active & ~bad
            c, nxt, done, emitted = _spec_accept(q_toks, g, st, active)
            dr = _advance_draft(dr, g, c)
            # -- recycle pages of finished / denied / poisoned slots -------
            ft_pop = free_top
            table, free, free_top, pinned = _free_finished_pages(
                table, pg.free, ft_pop, done | oom | bad, pg.pinned)
            ctr = ctr + jnp.stack(
                [popped, free_top - ft_pop, oom.sum(dtype=jnp.int32),
                 jnp.maximum(c - 1, 0).sum(dtype=jnp.int32)])
            st = SlotState(
                tokens=nxt,
                cur_pos=st.cur_pos + c,
                active=active & ~done,
                remaining=st.remaining - c,
                eos=st.eos,
            )
            pg = PageState(table=table, free=free, free_top=free_top,
                           quota=pg.quota, pinned=pinned)
            return (caches, st, pg, dr, poisoned | bad, ctr), (g, emitted)

        poisoned0 = jnp.zeros((B,), bool)
        ctr0 = jnp.zeros((4,), jnp.int32)
        (caches, state, pages, draft, poisoned, ctr), (toks, emitted) = (
            jax.lax.scan(body,
                         (caches, state, pages, draft, poisoned0, ctr0),
                         None, length=n_windows))
        return caches, state, pages, draft, toks, emitted, poisoned, ctr

    return spec_chunk


def spec_decode_chunk_program(cfg, scfg: ServeConfig, n_windows: int,
                              window: int, ngram: int, *, policy=None,
                              mesh=None):
    """Jitted :func:`make_spec_decode_chunk`, caches/state/draft donated.
    Sharded under ``mesh``: the n-gram draft history is replicated (drafting
    and accept/rollback are identical per shard), only the verify pass's
    KV/head math splits."""
    if mesh is not None:
        return _tp_program(
            "spec_chunk", cfg, scfg,
            (int(n_windows), int(window), int(ngram)), policy, mesh,
            lambda lcfg: make_spec_decode_chunk(lcfg, scfg, n_windows,
                                                window, ngram,
                                                policy=policy),
            paged=False, n_in=5, cache_in=1, n_out=6, cache_out=0,
            donate=(1, 2, 3))
    return PROGRAMS.get(
        "spec_chunk", cfg, scfg, (int(n_windows), int(window), int(ngram)),
        policy,
        lambda: jax.jit(
            make_spec_decode_chunk(cfg, scfg, n_windows, window, ngram,
                                   policy=policy),
            donate_argnums=(1, 2, 3)),
    )


def paged_spec_decode_chunk_program(cfg, scfg: ServeConfig, n_windows: int,
                                    window: int, ngram: int, page_size: int,
                                    *, policy=None, mesh=None):
    """Jitted :func:`make_paged_spec_decode_chunk`, caches/state/pages/draft
    donated."""
    if mesh is not None:
        return _tp_program(
            "paged_spec_chunk", cfg, scfg,
            (int(n_windows), int(window), int(ngram), int(page_size)),
            policy, mesh,
            lambda lcfg: make_paged_spec_decode_chunk(
                lcfg, scfg, n_windows, window, ngram, page_size,
                policy=policy),
            paged=True, n_in=6, cache_in=1, n_out=8, cache_out=0,
            donate=(1, 2, 3, 4))
    return PROGRAMS.get(
        "paged_spec_chunk", cfg, scfg,
        (int(n_windows), int(window), int(ngram), int(page_size)), policy,
        lambda: jax.jit(
            make_paged_spec_decode_chunk(cfg, scfg, n_windows, window,
                                         ngram, page_size, policy=policy),
            donate_argnums=(1, 2, 3, 4)),
    )


# ---------------------------------------------------------------------------
# Host generate loop (chunked)
# ---------------------------------------------------------------------------


def generate(
    params, cfg, prompt_tokens, *, n_new: int, scfg: Optional[ServeConfig] = None,
    policy=None, extras: Optional[Dict[str, Any]] = None, seed: int = 0,
):
    """Prefill the prompt, then decode ``n_new`` tokens through the chunked
    path: the remaining budget is covered by power-of-two chunk buckets
    (at most ceil((n_new-1)/chunk) + log2(chunk) dispatches instead of
    n_new-1 — the bucketing bounds the jit cache).

    prompt_tokens: (B, S) int32.  Returns (B, n_new) int32.
    """
    B, S = prompt_tokens.shape
    scfg = scfg or ServeConfig(max_len=S + n_new)
    batch = {"tokens": prompt_tokens, **(extras or {})}
    prefill_step = jax.jit(make_prefill_step(cfg, scfg, policy=policy))
    logits, caches = prefill_step(params, batch)
    mask = scfg.logit_mask(cfg)
    if mask is not None:
        logits = logits + mask.astype(logits.dtype)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    offset = S
    if cfg.family == "vlm" and extras and "extra_embeds" in extras:
        offset = S + extras["extra_embeds"].shape[1]

    out = [tok[:, None]]
    left = n_new - 1
    state = SlotState(
        tokens=tok,
        cur_pos=jnp.full((B,), offset, jnp.int32),
        active=jnp.ones((B,), bool),
        remaining=jnp.full((B,), max(left, 0), jnp.int32),
        eos=jnp.full((B,), -1, jnp.int32),
    )
    key = jax.random.PRNGKey(seed)
    while left > 0:
        T = chunk_bucket(min(left, max(scfg.chunk, 1)))
        fn = decode_chunk_program(cfg, scfg, T, policy=policy)
        key, sub = jax.random.split(key)
        caches, state, toks, _, _ = fn(params, caches, state, sub)
        out.append(jnp.moveaxis(toks, 0, 1))
        left -= T
    return jnp.concatenate(out, axis=1)
