"""Continuous batching over fixed decode slots — chunked, donated hot path.

The decode program has a fixed batch shape (XLA requirement); the batcher
multiplexes a dynamic request stream onto B fixed slots:

* new requests are prefilled **right-sized** (the joining rows only,
  bucketed to powers of two so the jit cache stays small) and their caches
  scattered into free slots with per-slot ``.at[:, slot].set`` writes — one
  fused admission dispatch, no full-tree ``jnp.where`` merge;
* decode runs in **chunks**: one ``lax.scan`` program advances all slots T
  steps with EOS/max-token detection on device, so the host pays one
  dispatch and one blocking sync per T tokens instead of per token.  T
  adapts to queue pressure (short chunks while requests wait, long chunks
  when the queue is dry) over the same power-of-two buckets;
* cache and slot-state buffers are **donated** into both programs
  (``jax.jit(..., donate_argnums=...)``), so XLA updates the ring-buffer KV
  in place — without donation every token copies the entire cache tree;
* slots free on EOS/max-tokens and are immediately refillable — the
  dynamic-workload serving pattern of the paper's private-cloud scenario,
  with the slot pool playing the role of the core pool at request
  granularity.

Invariants:

* ``self.caches``/``self.state`` always refer to the *latest* donated
  outputs; any previously exported reference is dead.  External consumers
  (e.g. ``ServingExecutor.register_state`` for mid-run resizes) must pull
  through :meth:`live_state` and hand back migrated trees via
  :meth:`adopt_state` — never hold the raw arrays across a step.
* ``slot_req[i] is not None`` ⟺ slot i is active on device; the host mirror
  is reconciled from the fetched ``emitted`` mask after every chunk.
* A slot that finishes mid-chunk keeps decoding with its position frozen,
  overwriting only its own ring slot; admission re-seeds the cache before
  reuse (see ``serving.engine``).

Host-side bookkeeping is numpy; device work happens only in the two jitted
programs.

**Paged mode** (``paged=True``): the per-slot dense ring buffers are replaced
by one pre-allocated pool of fixed-size KV pages (the cache analogue of the
paper's instruction-frame tile) with per-slot page tables — see
``serving.engine.PageState``.  Admission is gated on *page availability*
instead of slot count alone: each joining request reserves its worst-case
footprint (``ceil((prompt_len + max_new)/page_size)`` pages, or just the
prompt pages with ``reserve_pages=False``) in a host-side
:class:`~repro.serving.kv_cache.PagedKVPool` ledger, so the pool can hold
far more slots than dense rings of the same HBM would (slots whose actual
use is below ``max_len`` stop paying for it).  Page faults during decode are
handled on device inside the chunk scan; a slot denied a page (pool dry or
``kv_pages`` quota hit — only possible without reservations) deactivates,
and the host requeues its request at the queue head
(``stats.oom_requeues``) — keeping its generated tokens when
prompt+output still fits the prompt bucket (resume-on-OOM: re-admission
prefills the concatenation instead of restarting).  The single post-chunk
sync additionally carries ``active`` and ``free_top`` so the host ledger
stays reconciled.

**Prefix sharing** (``prefix_cache=True``, paged + pure-attention archs):
admission consults a :class:`~repro.serving.prefix_cache.PrefixCache`
(refcounted radix tree over the pool at page granularity, namespaced by
``Request.namespace``): hits map cached physical pages read-only into the
slot's table and prefill only the uncached suffix
(``engine.cached_admit_program``); misses insert their prefix pages for
the next request — but only with **recurrence evidence** (another pending
request carries the same prefix, or the cache's ghost index saw it
before), so single-use tails never spend cache pages (ownership of
inserted pages moves to the namespace — ``PagedKVPool.share`` — billed
once).  Cache-owned pages are pinned on
device (``PageState.pinned``) so finishing slots never push them to the
free stack; they return only through LRU eviction (admission pressure or a
``set_page_limit`` shrink, which evicts the cache *before* live requests
fault) via ``page_push_program``.

**Deadlines**: a ``Request.deadline`` (in the ``clock`` timebase) already
past at admission time sheds the request (``dropped`` /
``stats.deadline_drops``) instead of starting it hopelessly late.

**Fault guards** (the serving half of the fault-domain story —
``repro.core.faults`` is the hypervisor half): every chunk carries a
non-finite **logit sentinel** — a slot whose logits go NaN/inf is
deactivated on device before a poisoned token can be selected or emitted,
and its request is requeued with its pre-fault tokens intact
(``stats.poisoned_slots``).  An optional **watchdog** (``watchdog_s``)
bounds the wall time of one chunk dispatch+sync and retires the most
suspect slot instead of stalling every other request
(``stats.watchdog_trips``).  An opt-in **page-table audit** (``audit=True``,
paged mode) rides the existing post-chunk sync, cross-checks the fetched
tables against the no-double-mapping invariant (shared prefix pages are
exempt — they are read-only and multi-mapped by design), clears violating
entries, quarantines double-mapped physical pages out of circulation
forever, and requeues the slots whose KV integrity is suspect
(``stats.audit_repairs`` / ``stats.quarantined_pages``).  All three keep
the blast radius at the slot: untouched slots decode the same tokens they
would have without the fault.  ``inject_stall`` / ``inject_kv_corruption``
are seeded-chaos hooks for tests and ``benchmarks/bench_chaos.py``.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import (
    TP_POLICY, check_tp, make_tp_mesh, permute_params_for_tp,
    tp_cache_specs, tp_param_specs, tp_put_replicated, tp_shardings,
)
from repro.models.attention import check_attn_impl
from repro.models.moe import EXPERT_STATS
from repro.models.transformer import (
    Caches, init_caches, init_paged_caches, period_structure, windowed_layers,
)
from repro.obs import MetricsRegistry, Telemetry
from .config import ServingConfig, config_from_legacy_kwargs
from .kv_cache import PagedKVPool, PageQuotaError, pages_for, tree_bytes
from .prefix_cache import PrefixCache, PrefixNode
from .engine import (
    DraftState,
    PageState,
    ServeConfig,
    SlotState,
    admit_program,
    cached_admit_program,
    chunk_bucket,
    decode_chunk_program,
    init_draft_state,
    init_page_state,
    init_slot_state,
    page_push_program,
    paged_admit_program,
    paged_decode_chunk_program,
    paged_spec_decode_chunk_program,
    spec_decode_chunk_program,
)


@dataclasses.dataclass
class Request:
    """One generation request.

    ``namespace`` keys the shared-prefix cache: requests (possibly from
    different tenants multiplexed on one batcher) share cached prompt pages
    only within a namespace.  Sharing is **opt-in**: the default ``None``
    never shares — callers that want reuse pick a namespace key (and
    thereby accept that admission timing reveals prefix reuse within it).
    Note: prompts are left-padded to the batcher's ``prompt_len`` bucket,
    so only requests whose prompts have equal *total* length align
    positions and can share a prefix (see ``prefix_cache`` module docs).
    ``deadline`` (same clock as the batcher's ``clock`` callable) lets the
    batcher shed the request instead of starting it hopelessly late —
    ``dropped`` marks that outcome (``done`` is set too, with no output).
    """

    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int
    eos: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    namespace: Optional[str] = None
    deadline: Optional[float] = None
    dropped: bool = False
    # set when the request was requeued mid-flight (OOM / poison / watchdog)
    # and re-admitted: its row is left-padded differently than the original
    # prompt, which shifts page alignment for the prefix cache
    resumed: bool = False
    # batcher-clock stamps: ``submit`` and the first admission that popped
    # the request off the queue (a requeued request keeps its first stamp)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    # when the request last entered the queue: submit, or a requeue
    _t_queued: float = dataclasses.field(default=0.0, repr=False)
    # prefix-cache nodes this request currently pins (internal)
    _prefix_nodes: List[PrefixNode] = dataclasses.field(
        default_factory=list, repr=False)


# Every BatcherStats counter, in declaration order.  Each name is a view
# over the ``serving.<name>`` counter in the batcher's MetricsRegistry.
_STATS_FIELDS: Tuple[str, ...] = (
    "steps",                    # device decode steps executed (Σ chunk T)
    "chunks",                   # decode_chunk dispatches
    "prefills",                 # admission dispatches
    "completed",
    "slot_busy_steps",
    "slot_total_steps",
    "dispatches",               # all jitted dispatches (admit + chunk)
    "host_syncs",               # blocking device→host fetches
    "decode_tokens",            # tokens emitted by decode chunks
    "admit_tokens",             # first tokens emitted at admission
    "cache_bytes",              # resident cache-tree size (donated in place)
    "admit_scatter_bytes",      # bytes scattered at admission (vs. full-tree)
    # paged mode
    "oom_requeues",             # requests requeued after a denied page fault
    "oom_discarded_tokens",     # emitted tokens thrown away by requeues
    "oom_resumed",              # OOM requeues that kept their tokens
    "resumed_tokens_kept",      # tokens kept across requeues (any cause)
    "pages_in_use",             # device-allocated pages after the last sync
    "peak_pages_in_use",
    "peak_resident",            # most simultaneously-resident requests
    # device counters (ride back inside the per-chunk sync, paged modes)
    "device_pages_popped",      # pages popped off the free stack in-scan
    "device_pages_pushed",      # pages pushed back by in-scan frees
    "fault_denied_slots",       # slot-steps denied a page grant in-scan
    "device_draft_accepted",    # draft tokens accepted, counted on-device
    # expert share (models with experts; ride back in the same syncs):
    # decode chunks, then admissions — assignments routed to this chip's
    # experts, all assignments, (layer, held expert) pairs given >= 1 token
    "moe_held_assignments",
    "moe_assignments",
    "moe_held_experts_hit",
    "admit_moe_held_assignments",
    "admit_moe_assignments",
    "admit_moe_held_experts_hit",
    # prefix cache
    "prefix_hits",              # admissions that mapped >= 1 cached page
    "prefill_tokens_skipped",   # prompt tokens served from shared pages
    "prefix_inserts",           # pages newly indexed into the cache
    "prefix_evictions",         # cached pages reclaimed to the free stack
    "shared_pages",             # cache-owned pages right now (gauge)
    # deadlines
    "deadline_drops",           # requests shed before start (past deadline)
    # fault guards (NaN sentinel / watchdog / page-table audit)
    "poisoned_slots",           # slots retired by the non-finite sentinel
    "watchdog_trips",           # chunks that exceeded watchdog_s
    "audit_repairs",            # page-table entries the audit cleared
    "quarantined_pages",        # pool pages permanently out of circulation
    # speculative decode
    "spec_windows",             # draft-and-verify windows with >= 1 commit
    "drafted_tokens",           # draft tokens proposed in those windows
    "accepted_tokens",          # draft tokens the verify pass accepted
    # prefill/decode overlap
    "overlap_rounds",           # rounds with chunk + admission both in flight
    # prefix cache: resumed rows whose shifted padding missed the cache
    "resume_prefix_misses",
    # tensor parallelism
    "remeshes",                 # live tp-width migrations (hypervisor resizes)
    # admission cost (host clock, in the batcher's ``clock`` timebase)
    "admitted",                 # joins popped off the queue (a rejoin again)
    "admit_plan_us",            # µs spent planning admissions (admit.plan)
    "queue_wait_us",            # Σ µs each join waited since it was queued
    # prefill work: bucket rows × tokens each row computes, duplicate-pad
    # rows included (cached admission computes only the suffix), against
    # the real prompt tokens neither left padding nor served from cache
    "prefill_tokens_computed",
    "prefill_tokens_needed",
)
_STATS_FIELD_SET = frozenset(_STATS_FIELDS)


class BatcherStats:
    """The batcher's counter bundle, now backed by a ``MetricsRegistry``.

    Historically a plain dataclass of ints; each field is now a *view*
    over the ``serving.<field>`` counter in a registry (optionally
    per-tenant labeled), so ``batcher.stats.chunks`` and
    ``registry.counter("serving.chunks", tenant).value`` are literally the
    same number.  The keyword constructor, ``+=`` on fields, and every
    derived ratio property behave exactly as before.
    """

    __slots__ = ("_registry", "_tenant")

    def __init__(self, *, registry: Optional[MetricsRegistry] = None,
                 tenant: Optional[str] = None, **overrides: int):
        object.__setattr__(self, "_registry",
                           registry if registry is not None
                           else MetricsRegistry())
        object.__setattr__(self, "_tenant", tenant)
        for name in _STATS_FIELDS:
            self._registry.counter(f"serving.{name}", self._tenant)
        for name, value in overrides.items():
            if name not in _STATS_FIELD_SET:
                raise TypeError(
                    f"BatcherStats got an unexpected field {name!r}")
            setattr(self, name, value)

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry

    def __getattr__(self, name: str) -> int:
        if name in _STATS_FIELD_SET:
            return self._registry.counter(
                f"serving.{name}", self._tenant).value
        raise AttributeError(name)

    def __setattr__(self, name: str, value) -> None:
        if name in _STATS_FIELD_SET:
            self._registry.counter(
                f"serving.{name}", self._tenant).value = value
        else:
            object.__setattr__(self, name, value)

    def as_dict(self) -> Dict[str, int]:
        return {name: getattr(self, name) for name in _STATS_FIELDS}

    def __repr__(self) -> str:
        body = ", ".join(f"{k}={v}" for k, v in self.as_dict().items())
        return f"BatcherStats({body})"

    @property
    def prefix_tokens_saved(self) -> int:
        """Alias of ``prefill_tokens_skipped``: every prompt token served
        from a shared page is exactly one prefill token not re-run."""
        return self.prefill_tokens_skipped

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify pass accepted — the
        speculative win factor: tokens per window = 1 + rate·(W-1)."""
        return self.accepted_tokens / max(self.drafted_tokens, 1)

    @property
    def occupancy(self) -> float:
        return self.slot_busy_steps / max(self.slot_total_steps, 1)

    @property
    def tokens(self) -> int:
        """Tokens actually *delivered*: a restarted (OOM-requeued) request's
        discarded emissions were device work but not throughput — without
        the correction, over-subscribed tokens/s would be inflated by
        exactly the thrashing the residency throttle exists to limit."""
        return self.decode_tokens + self.admit_tokens \
            - self.oom_discarded_tokens

    @property
    def dispatches_per_token(self) -> float:
        return self.dispatches / max(self.tokens, 1)

    @property
    def syncs_per_token(self) -> float:
        return self.host_syncs / max(self.tokens, 1)

    @property
    def decode_dispatches_per_token(self) -> float:
        """Dispatches on the pure-decode path: 1/T when chunks run full."""
        return self.chunks / max(self.decode_tokens, 1)


class ContinuousBatcher:
    """Fixed-slot continuous batcher for one tenant's model.

    Construct with a validated :class:`~repro.serving.config.ServingConfig`::

        ContinuousBatcher(params, cfg, ServingConfig(slots=4, prompt_len=8,
                                                     max_len=32))

    The pre-config keyword constructor
    (``ContinuousBatcher(params, cfg, slots=4, ...)``) still works as a thin
    deprecation shim — every legacy kwarg maps 1:1 onto a config field —
    but emits a ``DeprecationWarning``.
    """

    def __init__(self, params, cfg, config: Optional[ServingConfig] = None,
                 *, policy=None, mesh=None,
                 clock: Optional[Callable[[], float]] = None,
                 telemetry: Optional[Telemetry] = None, **legacy):
        if config is None:
            offending = ", ".join(sorted(legacy)) if legacy else "<none>"
            warnings.warn(
                f"ContinuousBatcher(**kwargs) is deprecated — move the "
                f"legacy kwarg(s) [{offending}] onto a ServingConfig: "
                f"ContinuousBatcher(params, cfg, ServingConfig(...))",
                DeprecationWarning, stacklevel=2)
            config = config_from_legacy_kwargs(**legacy)
        elif legacy:
            raise TypeError(
                f"pass either a ServingConfig or legacy kwargs, not both "
                f"(got config and {sorted(legacy)})")
        self.params = params
        self.cfg = cfg
        self.config = config
        slots, prompt_len = config.slots, config.prompt_len
        paged, page_size = config.paged, config.page_size
        prefix_cache = config.prefix_cache
        self.B = slots
        self.prompt_len = prompt_len
        self.chunk = max(1, config.chunk)
        scfg = ServeConfig(max_len=config.max_len, attn_impl=config.attn_impl,
                           chunk=self.chunk)
        self.scfg = scfg
        # structural / capability rules were validated by ServingConfig;
        # the model-dependent rules live here, where cfg is known
        windowed = windowed_layers(cfg)
        if windowed:
            check_attn_impl(config.attn_impl, "sliding_window")
        if prefix_cache and (
                any(s.mixer != "attn" for s in period_structure(cfg))
                or cfg.family in ("audio", "vlm")):
            raise ValueError(
                "prefix caching requires a pure-attention arch (SSM state "
                "is not positional; audio/vlm prompts shift positions)")
        if config.speculative and windowed:
            raise ValueError(
                "speculative decode does not support windowed attention "
                "layers: every layer must attend in full")
        if config.speculative and (
                any(s.mixer != "attn" for s in period_structure(cfg))
                or cfg.family in ("audio", "vlm")):
            raise ValueError(
                "speculative decode requires a pure-attention text arch "
                "(SSM state cannot be rolled back to the accepted prefix)")
        self._policy = policy
        # tensor parallelism: resolve the tenant sub-mesh before any device
        # state is allocated, so params/caches land sharded from the start
        self.tp = int(config.tp)
        self._mesh = None
        self._device = None           # single-device pin (width-1 lease)
        self._host_params = None      # un-permuted host copy, for re-meshing
        if mesh is not None:
            if "tp" not in getattr(mesh, "axis_names", ()):
                raise ValueError(
                    "batcher meshes must be flat ('tp',) meshes "
                    "(distributed.sharding.make_tp_mesh)")
            if int(mesh.shape["tp"]) != self.tp:
                raise ValueError(
                    f"mesh is tp={int(mesh.shape['tp'])} wide but "
                    f"ServingConfig.tp={self.tp}")
        if self.tp > 1:
            if policy is not None:
                raise ValueError(
                    "tp>1 installs its own TPShardPolicy; custom activation "
                    "policies are single-device")
            check_tp(cfg, self.tp)
            self._mesh = mesh if mesh is not None else make_tp_mesh(self.tp)
            self._policy = TP_POLICY
            self._host_params = jax.device_get(params)
            self.params = jax.device_put(
                permute_params_for_tp(self._host_params, cfg, self.tp),
                tp_shardings(self._mesh, tp_param_specs(cfg)))
        elif mesh is not None:
            # a width-1 lease still names WHICH device the tenant runs on
            self._device = list(mesh.devices.flat)[0]
            self.params = jax.device_put(params, self._device)
        self.paged = paged
        self._clock = clock if clock is not None else time.monotonic
        self._has_deadlines = False
        self.queue: Deque[Request] = deque()
        self.slot_req: List[Optional[Request]] = [None] * slots
        self.state: SlotState = init_slot_state(slots)
        self.prefix: Optional[PrefixCache] = None
        if isinstance(prefix_cache, PrefixCache):
            assert prefix_cache.page_size == page_size
            self.prefix = prefix_cache
        elif prefix_cache:
            self.prefix = PrefixCache(page_size)
        if paged:
            self.page_size = max(1, page_size)
            self.max_pages = pages_for(config.max_len, self.page_size)
            # default pool == dense capacity; pass a smaller n_pages to
            # over-subscribe (the bench's equal-HBM capacity argument)
            self.n_pages = config.n_pages if config.n_pages is not None \
                else slots * self.max_pages
            self.reserve_pages = config.reserve_pages
            self._page_limit = min(config.page_quota, self.n_pages) \
                if config.page_quota is not None else self.n_pages
            self.kv_pool = PagedKVPool(self.n_pages, self.page_size)
            self.caches: Caches = init_paged_caches(
                cfg, slots, self.n_pages, self.page_size)
            if not self.caches.kv:
                raise ValueError("paged mode needs at least one attn layer")
            self.pages: Optional[PageState] = init_page_state(
                slots, self.n_pages, self.max_pages, quota=self._page_limit)
            self._admit_fn = paged_admit_program(
                cfg, scfg, policy=self._policy, mesh=self._mesh)
        else:
            self.caches = init_caches(cfg, slots, config.max_len)
            self.pages = None
            self._admit_fn = admit_program(
                cfg, scfg, policy=self._policy, mesh=self._mesh)
        # speculative decode: the chunk unit becomes a draft-and-verify
        # window; the drafter history is device state donated like the rest
        self._spec = bool(config.speculative)
        self._draft_window = config.draft_window
        self._draft_ngram = config.draft_ngram
        self._draft_hist = config.draft_hist
        self.draft: Optional[DraftState] = (
            init_draft_state(slots, config.draft_hist) if self._spec
            else None)
        self._overlap = bool(config.overlap)
        # telemetry: registry backs every BatcherStats field; the tracer
        # (NULL_TRACER by default — zero-cost) records the round's phase
        # spans (round > admit.plan/dispatch/sync/finish and
        # chunk.dispatch/sync/finish) and the fault instants
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._tracer = self.telemetry.tracer
        self._track = self.telemetry.track
        self.stats = BatcherStats(registry=self.telemetry.registry,
                                  tenant=self.telemetry.tenant,
                                  cache_bytes=tree_bytes(self.caches))
        # fault guards: watchdog_s bounds the wall time of one chunk
        # dispatch+sync (None = off); audit=True cross-checks the fetched
        # page tables against the no-double-mapping invariant every chunk
        self._watchdog_s = config.watchdog_s
        self._audit = bool(config.audit) and paged
        self._stall: Optional[tuple] = None      # inject_stall chaos hook
        self._quarantined: set = set()           # page ids out of circulation
        self._key = jax.random.PRNGKey(0)
        self._stalled = 0           # consecutive zero-emission paged chunks
        self._admitted_pages_since_sync = 0
        # over-subscription throttle: after a denied page fault, cap
        # residency at the survivors so restarted requests stop thrashing
        # the ones still making progress; recover one slot per clean round
        self._resident_cap = slots
        if self._mesh is not None:
            self._place_state()

    # -- request intake ------------------------------------------------
    def submit(self, req: Request) -> None:
        assert req.prompt.shape[0] <= self.prompt_len
        if self.paged:
            assert self._request_pages(req) <= self.n_pages, \
                "request footprint exceeds the whole page pool"
        if req.deadline is not None:
            self._has_deadlines = True
        req.t_submit = req._t_queued = self._clock()
        self.queue.append(req)

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _shed_expired(self) -> None:
        """Drop queued requests whose deadline has already passed — serving
        them would burn slots on answers nobody is waiting for."""
        if not self._has_deadlines:
            return
        now = self._clock()
        kept: Deque[Request] = deque()
        for req in self.queue:
            if req.deadline is not None and now > req.deadline:
                req.done = True
                req.dropped = True
                self.stats.deadline_drops += 1
            else:
                kept.append(req)
        self.queue = kept

    # -- paged-mode ledger ----------------------------------------------
    def _request_pages(self, req: Request) -> int:
        """Ledger reservation for one request: its worst-case footprint
        (bucketed prompt + full decode budget) when reserving, prompt pages
        only when running over-subscribed."""
        toks = self.prompt_len + (req.max_new if self.reserve_pages else 0)
        return pages_for(toks, self.page_size)

    def set_page_limit(self, n_pages: int) -> None:
        """Adjust the tenant's ``kv_pages`` lease cap mid-run (hypervisor
        kv resize).  Takes effect on the next dispatch; shrinking below the
        current allocation only blocks further growth — resident pages
        drain as their slots complete.  With a prefix cache attached, a
        shrink **evicts unpinned cache entries first** (shared pages count
        against the lease like any allocation), so the cache pays for the
        smaller lease before live requests start faulting against it."""
        assert self.paged, "page limits only apply to paged batchers"
        self._page_limit = max(0, min(int(n_pages), self.n_pages))
        self.pages = self.pages._replace(quota=jnp.int32(self._page_limit))
        if self.prefix is not None:
            est = self.stats.pages_in_use + self._admitted_pages_since_sync
            if est > self._page_limit:
                self._evict_cached(est - self._page_limit)

    def _evict_cached(self, n: int) -> int:
        """Reclaim up to ``n`` pages from the prefix cache (LRU, refcount-0
        only): drop them from the shared ledger and push them back onto the
        device free stack.  Returns how many pages came back."""
        if self.prefix is None or n <= 0:
            return 0
        pids = self.prefix.evict(n)
        if not pids:
            return 0
        self.kv_pool.drop_shared(pids)
        self.stats.prefix_evictions += len(pids)
        self.stats.shared_pages = self.kv_pool.shared
        # pad the pid vector to a power-of-two bucket (-1 = no-op) so the
        # push program compiles log2(n_pages) shapes, not one per eviction
        width = 1 << (len(pids) - 1).bit_length() if len(pids) > 1 else 1
        vec = np.full((width,), -1, dtype=np.int32)
        vec[: len(pids)] = pids
        self.pages = page_push_program()(self.pages, jnp.asarray(vec))
        self.stats.dispatches += 1
        self.stats.pages_in_use = max(0, self.stats.pages_in_use - len(pids))
        return len(pids)

    def _page_shortfall(self, need: int, pop_need: Optional[int] = None,
                        ) -> int:
        """Pages missing before ``need`` can be admitted: the worst deficit
        over the lease bound, the ledger bound, and (without reservations)
        the device free-stack estimate for ``pop_need`` (the pages the
        admission dispatch will actually pop — the prompt's uncached pages;
        defaults to ``need``).  0 means the admission fits.  Every evicted
        cache page relieves all three bounds at once, so this is exactly
        how many pages an eviction pass must reclaim — evicting a whole
        request footprint instead would flush warm entries that were never
        in the way."""
        if pop_need is None:
            pop_need = need
        short = max(0, self.kv_pool.used + need - self._page_limit)
        short = max(short, need - self.kv_pool.available)
        if not self.reserve_pages:
            # the ledger only reserved prompt pages; residents' decode pages
            # live on device.  Bound admission by the device allocation seen
            # at the last sync (plus prompts admitted since), and keep one
            # page of headroom whenever someone is already resident so at
            # least one slot can take the decode-time fault and progress.
            device_avail = (self.n_pages - self.stats.pages_in_use
                            - self._admitted_pages_since_sync)
            short = max(
                short,
                pop_need + int(any(r is not None for r in self.slot_req))
                - device_avail)
        return short

    def _pages_available(self, need: int, pop_need: Optional[int] = None,
                         ) -> bool:
        return self._page_shortfall(need, pop_need) == 0

    # -- mid-run migration (Hypervisor resize between chunks) -----------
    def live_state(self) -> Dict[str, Any]:
        """Current device state, for ``TwoStageCompiler.reconfigure``
        migration.  Pull-only: the returned arrays are donated (dead) after
        the next step — register this *method* (not its result) with
        ``ServingExecutor.register_state``.  Paged batchers also carry the
        page tables / free stack, so a resize migrates the whole pool."""
        out = {"caches": self.caches, "slots": self.state}
        if self.paged:
            out["pages"] = self.pages
        if self._spec:
            # the drafter history migrates with the caches so re-admitted
            # tenants keep speculating mid-request (tenancy live-state
            # migration moves the whole dict with one device_put)
            out["draft"] = self.draft
        return out

    def adopt_state(self, state: Dict[str, Any]) -> None:
        """Adopt a migrated state tree; decode resumes at the same token."""
        self.caches = state["caches"]
        self.state = state["slots"]
        if self.paged:
            self.pages = state["pages"]
        if self._spec:
            self.draft = state["draft"]

    def _place_state(self) -> None:
        """device_put the donated device state with its layout: KV head axis
        split over the tp mesh, slot/page/draft bookkeeping replicated (or
        everything onto the default device when single-device), so
        steady-state chunks never pay a layout transfer inside a dispatch."""
        mesh = self._mesh
        if mesh is None:
            dev = self._device
            self.caches = jax.device_put(self.caches, dev)
            self.state = jax.device_put(self.state, dev)
            if self.pages is not None:
                self.pages = jax.device_put(self.pages, dev)
            if self.draft is not None:
                self.draft = jax.device_put(self.draft, dev)
            self._key = jax.device_put(self._key, dev)
            return
        self.caches = jax.device_put(
            self.caches,
            tp_shardings(mesh, tp_cache_specs(self.cfg, paged=self.paged)))
        self.state = tp_put_replicated(mesh, self.state)
        if self.pages is not None:
            self.pages = tp_put_replicated(mesh, self.pages)
        if self.draft is not None:
            self.draft = tp_put_replicated(mesh, self.draft)
        self._key = tp_put_replicated(mesh, self._key)

    def remesh(self, tp: Optional[int] = None, *, mesh=None) -> None:
        """Live-migrate this batcher onto a new TP width / device set.

        The hypervisor's elastic-resize path: snapshot the donated device
        state to host (:meth:`live_state`), swap in the new mesh + sharded
        programs (registry hits when the mesh was seen before), re-place
        params — re-permuting the swiglu pack from the kept un-permuted
        host copy, since the column permutation depends on tp — and adopt
        the state back.  State *values* are untouched, so the decode stream
        is token-identical across the move; resident requests, queued
        requests, and the drafter history all ride along.
        """
        if mesh is not None:
            if "tp" not in getattr(mesh, "axis_names", ()):
                raise ValueError(
                    "batcher meshes must be flat ('tp',) meshes "
                    "(distributed.sharding.make_tp_mesh)")
            new_tp = int(mesh.shape["tp"])
            if tp is not None and int(tp) != new_tp:
                raise ValueError(
                    f"tp={tp} conflicts with the mesh width {new_tp}")
        elif tp is None:
            raise ValueError("remesh needs a tp width or a mesh")
        else:
            new_tp = int(tp)
        if new_tp > 1:
            if self.config.attn_impl != "xla":
                raise ValueError(
                    f"tp={new_tp} requires attn_impl='xla' (the "
                    f"{self.config.attn_impl!r} kernels are single-device)")
            check_tp(self.cfg, new_tp)
        if self._host_params is None:
            # currently single-device: the resident params ARE the host
            # layout (no permutation was applied)
            self._host_params = jax.device_get(self.params)
        state = jax.device_get(self.live_state())
        self.config = dataclasses.replace(self.config, tp=new_tp)
        self.tp = new_tp
        if new_tp > 1:
            self._mesh = (mesh if mesh is not None
                          else make_tp_mesh(new_tp))
            self._device = None
            self._policy = TP_POLICY
            self.params = jax.device_put(
                permute_params_for_tp(self._host_params, self.cfg, new_tp),
                tp_shardings(self._mesh, tp_param_specs(self.cfg)))
        else:
            dev = list(mesh.devices.flat)[0] if mesh is not None else None
            self._mesh = None
            self._device = dev
            self._policy = None
            self.params = (jax.device_put(self._host_params, dev)
                           if dev is not None
                           else jax.device_put(self._host_params))
        if self.paged:
            self._admit_fn = paged_admit_program(
                self.cfg, self.scfg, policy=self._policy, mesh=self._mesh)
        else:
            self._admit_fn = admit_program(
                self.cfg, self.scfg, policy=self._policy, mesh=self._mesh)
        self.adopt_state(state)
        self._place_state()
        self.stats.remeshes += 1
        if self._tracer.enabled:
            self._tracer.instant("remesh", self._track, args={"tp": new_tp})

    # -- fault guards: requeue, watchdog, page-table audit ----------------
    def inject_stall(self, slot: int, seconds: float) -> None:
        """Chaos hook: add ``seconds`` to the next chunk's measured wall
        time and blame ``slot``, so tests and the chaos bench can trip the
        watchdog deterministically without a real hang."""
        self._stall = (int(slot), float(seconds))

    def inject_kv_corruption(self, slot: int, *,
                             pid: Optional[int] = None) -> None:
        """Chaos hook: overwrite one of ``slot``'s mapped page-table
        entries with ``pid`` (default: an out-of-range id), simulating a
        flipped bit in the table.  Passing another slot's physical id
        forges a double mapping.  ``audit=True`` detects and self-heals
        either on the next chunk sync."""
        assert self.paged, "page corruption applies to paged batchers"
        row = np.asarray(jax.device_get(self.pages.table[slot]))
        mapped = np.nonzero(row >= 0)[0]
        j = int(mapped[0]) if mapped.size else 0
        bad = int(pid) if pid is not None else self.n_pages + 7
        self.pages = self.pages._replace(
            table=self.pages.table.at[slot, j].set(bad))

    def _requeue_slot(self, slot: int, req: Request) -> bool:
        """Retire ``slot``'s request to the queue head.  Generated tokens
        are KEPT when prompt+output still fit the prompt bucket
        (re-admission prefills the concatenation and decoding resumes —
        the resume-on-OOM discipline); otherwise the request restarts from
        its prompt and the discarded emissions stay out of
        ``stats.tokens``.  Returns True when the tokens were kept."""
        self.slot_req[slot] = None
        if self.paged:
            if self.prefix is not None:
                self._release_prefix(req)
            self.kv_pool.free(req.rid)
        kept = bool(req.out) and \
            len(req.prompt) + len(req.out) <= self.prompt_len
        if kept:
            self.stats.resumed_tokens_kept += len(req.out)
            req.resumed = True
        else:
            self.stats.oom_discarded_tokens += len(req.out)
            req.out.clear()
        req._t_queued = self._clock()
        self.queue.appendleft(req)
        return kept

    def _host_release_slot(self, slot: int) -> None:
        """Host-side analogue of the in-chunk finish path: deactivate
        ``slot`` on device and (paged) push its private pages back to the
        free stack, clearing its table row.  Cache-owned (pinned) pages
        are left to the refcount ledger; quarantined and out-of-range ids
        are never pushed."""
        self.state = self.state._replace(
            active=self.state.active.at[slot].set(False))
        if not self.paged:
            return
        row, pin = jax.device_get(
            (self.pages.table[slot], self.pages.pinned[slot]))
        self.stats.host_syncs += 1
        private = np.asarray(row)[int(pin):]
        pids = [int(p) for p in private
                if 0 <= p < self.n_pages and int(p) not in self._quarantined]
        self.pages = self.pages._replace(
            table=self.pages.table.at[slot].set(-1),
            pinned=self.pages.pinned.at[slot].set(0))
        if pids:
            width = 1 << (len(pids) - 1).bit_length() if len(pids) > 1 else 1
            vec = np.full((width,), -1, dtype=np.int32)
            vec[: len(pids)] = pids
            self.pages = page_push_program()(self.pages, jnp.asarray(vec))
            self.stats.dispatches += 1
            self.stats.pages_in_use = max(
                0, self.stats.pages_in_use - len(pids))

    def _watchdog_trip(self, stall_slot: Optional[int]) -> None:
        """A chunk exceeded ``watchdog_s``: retire the most suspect slot
        (the injected one when the stall was synthetic, else the slot with
        the most generated tokens — the longest-running lane) and requeue
        its request, instead of letting one wedged lane stall every
        request multiplexed on this batcher.  Tokens emitted before the
        trip are kept whenever they still fit the prompt bucket."""
        self.stats.watchdog_trips += 1
        self._tracer.instant("watchdog_trip", self._track)
        candidates = [i for i, r in enumerate(self.slot_req)
                      if r is not None]
        if stall_slot is not None and self.slot_req[stall_slot] is not None:
            victim = stall_slot
        elif candidates:
            victim = max(candidates,
                         key=lambda i: (len(self.slot_req[i].out), -i))
        else:
            return
        req = self.slot_req[victim]
        self._host_release_slot(victim)
        self._requeue_slot(victim, req)

    def _run_audit(self, table_np: np.ndarray) -> None:
        """Cross-check the fetched page tables against the
        no-double-mapping invariant: every physical id maps at most one
        (slot, logical) entry unless it is cache-owned (shared prefix
        pages are read-only and legitimately multi-mapped).  Violations
        self-heal — out-of-range entries are cleared, a double-mapped
        private page is unmapped everywhere and **quarantined** (never
        returned to the free stack; billed to a ``"__quarantine__"``
        ledger owner so admission control sees the shrunken pool) — and
        every slot that lost a mapping is requeued: its KV integrity is
        suspect, but its already-emitted tokens are host-side and kept."""
        shared = self.kv_pool.shared_ids()
        owner: Dict[int, tuple] = {}
        clear: set = set()               # (slot, logical) entries to wipe
        corrupt: set = set()             # pool pids leaving circulation
        suspects: set = set()            # slots whose KV integrity is gone
        B, maxp = table_np.shape
        for i in range(B):
            for j in range(maxp):
                pid = int(table_np[i, j])
                if pid < 0:
                    continue
                if pid >= self.n_pages or pid in self._quarantined:
                    clear.add((i, j))
                    suspects.add(i)
                    continue
                if pid in shared:
                    continue
                prev = owner.get(pid)
                if prev is None:
                    owner[pid] = (i, j)
                else:
                    clear.add(prev)
                    clear.add((i, j))
                    corrupt.add(pid)
                    suspects.add(prev[0])
                    suspects.add(i)
        if not clear:
            return
        entries = sorted(clear)
        rows = jnp.asarray([e[0] for e in entries], dtype=jnp.int32)
        cols = jnp.asarray([e[1] for e in entries], dtype=jnp.int32)
        self.pages = self.pages._replace(
            table=self.pages.table.at[rows, cols].set(-1))
        self.stats.audit_repairs += len(entries)
        if self._tracer.enabled:
            self._tracer.instant("audit_repair", self._track,
                                 args={"entries": len(entries)})
        new_q = corrupt - self._quarantined
        self._quarantined |= corrupt
        self.stats.quarantined_pages = len(self._quarantined)
        if new_q:
            try:
                self.kv_pool.alloc("__quarantine__", len(new_q))
            except PageQuotaError:
                pass        # ledger over-subscribed; device truth governs
        for i in sorted(suspects):
            req = self.slot_req[i]
            if req is None:
                continue
            self._host_release_slot(i)
            self._requeue_slot(i, req)
        # leak reconciliation: the corrupt entry overwrote some page's only
        # mapping, orphaning it — neither mapped, free, shared, nor
        # quarantined.  Its owner was just requeued, so the contents are
        # dead; the page hardware itself is fine (the *table* was corrupt).
        # Reclaim orphans to the free stack so corruption never shrinks the
        # pool beyond the quarantined pages.
        tab, free_arr, top = jax.device_get(
            (self.pages.table, self.pages.free, self.pages.free_top))
        self.stats.host_syncs += 1
        tab = np.asarray(tab)
        known = set(tab[tab >= 0].tolist())
        known |= set(np.asarray(free_arr)[: int(top)].tolist())
        known |= shared | self._quarantined
        leaked = [p for p in range(self.n_pages) if p not in known]
        if leaked:
            width = 1 << (len(leaked) - 1).bit_length() \
                if len(leaked) > 1 else 1
            vec = np.full((width,), -1, dtype=np.int32)
            vec[: len(leaked)] = leaked
            self.pages = page_push_program()(self.pages, jnp.asarray(vec))
            self.stats.dispatches += 1
            self.stats.audit_repairs += len(leaked)

    # -- admission: right-sized prefill + per-slot scatter ---------------
    def _padded_row(self, req: Request) -> np.ndarray:
        """The request's prompt-bucket row: prompt (plus any tokens kept by
        a resume-on-OOM requeue) left-padded with 0s to ``prompt_len``.
        Memoized per (request, emitted-token count) — the witness scan asks
        for every queued request's row each admission round."""
        cached = getattr(req, "_row_cache", None)
        if cached is not None and cached[0] == len(req.out):
            return cached[1]
        row = np.zeros((self.prompt_len,), dtype=np.int32)
        toks = np.asarray(req.prompt, dtype=np.int32)
        if req.out:
            toks = np.concatenate(
                [toks, np.asarray(req.out, dtype=np.int32)])
        row[self.prompt_len - len(toks):] = toks
        req._row_cache = (len(req.out), row)
        return row

    def _release_prefix(self, req: Request) -> None:
        """Unpin the request's cached-prefix pages (tree refcounts + ledger
        refcounts).  Refcount-0 pages stay cached until an eviction."""
        if req._prefix_nodes:
            self.prefix.release(req._prefix_nodes)
            self.kv_pool.release([n.page_id for n in req._prefix_nodes])
            req._prefix_nodes = []

    def _queue_path_counts(self) -> Dict[Any, int]:
        """How many pending requests carry each page-aligned prefix path —
        the round's sharing witness for the insert heuristic.  Bounded to
        the queue's first 16·B entries so a deep backlog doesn't make
        admission O(queue²); sharing deeper in the queue is still caught by
        the ghost index when those requests reach the front."""
        counts: Dict[Any, int] = {}
        if self.prefix is None:
            return counts
        ps = self.page_size
        max_share = self.prefix.max_shareable(self.prompt_len)
        for n_seen, r in enumerate(self.queue):
            if n_seen >= 16 * self.B:
                break
            if r.namespace is None:
                continue
            row = self._padded_row(r)
            for i in range(max_share):
                key = (r.namespace, i, row[:(i + 1) * ps].tobytes())
                counts[key] = counts.get(key, 0) + 1
        return counts

    def _plan_join(self, req: Request, planned_paths: set,
                   witness: Dict[Any, int]):
        """Prefix-cache plan for one joining request: the cached page path
        (hits), and how many of the following full pages this admission will
        insert.  Inserts are contiguous from the hit depth, capped at the
        deepest prefix with **recurrence evidence** — shared by another
        pending request (queue witness) or seen in an earlier lookup (ghost
        index) — so single-use tails never consume cache pages; and they
        skip paths another join of this same round already claimed (its
        physical ids are unknown until that dispatch's sync, so a duplicate
        maps private pages and converges to sharing on a later round)."""
        if self.prefix is None or req.namespace is None:
            return [], 0
        row = self._padded_row(req)
        max_share = self.prefix.max_shareable(self.prompt_len)
        nodes = self.prefix.lookup(req.namespace, row, max_pages=max_share)
        if req.resumed and not nodes:
            # the resume-on-OOM row (prompt + kept tokens) is left-padded
            # differently than the original prompt, so it cannot hit the
            # pages the original inserted.  The lookup above IS the
            # re-attempt — it aligns with other requests resumed at the
            # same output length (and the note_seen below indexes this
            # shifted row so recurring resumes converge to sharing) — but a
            # miss here is a distinct phenomenon from a cold prompt:
            # count it so capacity planning can see resume-induced misses.
            self.stats.resume_prefix_misses += 1
        seen_depth = self.prefix.note_seen(req.namespace, row,
                                           max_pages=max_share)
        ps = self.page_size
        queue_depth = 0
        for i in range(max_share):
            key = (req.namespace, i, row[:(i + 1) * ps].tobytes())
            if witness.get(key, 0) < 2:     # this request counts once
                break
            queue_depth = i + 1
        worth = max(seen_depth, queue_depth, len(nodes))
        inserts = 0
        for i in range(len(nodes), min(max_share, worth)):
            path = (req.namespace, tuple(int(t) for t in row[:(i + 1) * ps]))
            if path in planned_paths:
                break
            planned_paths.add(path)
            inserts += 1
        return nodes, inserts

    def _admit(self, *, defer: bool = False) -> List[Dict[str, Any]]:
        """Admission: plan the joins (span ``admit.plan``), then one
        prefill dispatch per group (``admit.dispatch``).  With
        ``defer=False`` the post-dispatch host work (reading first tokens,
        completing done-at-admission requests, prefix inserts, draft
        seeding) happens inline and ``[]`` is returned; with ``defer=True``
        each dispatch is returned as a pending record for
        :meth:`_finish_admit` — the overlap path dispatches admission
        behind the in-flight decode chunk and merges both at one point per
        round."""
        if not self.queue:
            return []
        with self._tracer.span("admit.plan", self._track) as sp:
            if self._tracer.enabled:
                sp.set_metadata(queued=len(self.queue))
            t0 = self._clock()
            self._shed_expired()
            groups = self._plan_admission()
            self.stats.admit_plan_us += round((self._clock() - t0) * 1e6)
            if self._tracer.enabled:
                sp.set_metadata(joins=sum(len(g) for _, g in groups))
        if self.paged:
            pending = [self._dispatch_paged(group, k) for k, group in groups]
            if pending:
                self.stats.shared_pages = self.kv_pool.shared
        else:
            pending = [self._dispatch_dense(group) for _, group in groups]
        if defer:
            return pending
        for rec in pending:
            self._finish_admit(rec)
        return []

    def _pop_join(self) -> Request:
        """Pop the queue head for a join: stamp its first admission and
        count the join and its wait since it was (re)queued."""
        req = self.queue.popleft()
        now = self._clock()
        if req.t_admit is None:
            req.t_admit = now
        self.stats.admitted += 1
        self.stats.queue_wait_us += round((now - req._t_queued) * 1e6)
        return req

    def _plan_admission(self) -> List[Tuple[int, List[Dict[str, Any]]]]:
        """Pick this round's joins and group them into dispatches:
        ``[(cached pages k, joins)]``.  Dense: every queued request a free
        slot can take, one group.  Paged: head-of-line by page
        availability, with prefix-cache plans, one group per cached-prefix
        depth (the suffix length is a static program shape, bounded by
        prompt_len / page_size programs)."""
        free = self._free_slots()
        if not free or not self.queue:
            return []
        joins: List[Dict[str, Any]] = []
        if not self.paged:
            while free and self.queue:
                joins.append({"slot": free.pop(0), "req": self._pop_join()})
            return [(0, joins)]
        planned_paths: set = set()
        witness = self._queue_path_counts()
        resident = sum(r is not None for r in self.slot_req)
        prompt_pages = pages_for(self.prompt_len, self.page_size)
        while free and self.queue:
            if resident + len(joins) >= self._resident_cap:
                break
            req = self.queue[0]
            nodes, inserts = self._plan_join(req, planned_paths, witness)
            k = len(nodes)
            if nodes:
                # pin the hit path NOW: the pressure-eviction below must
                # never reclaim pages this join is about to map
                self.prefix.acquire(nodes)
                self.kv_pool.acquire([n.page_id for n in nodes])
                req._prefix_nodes = list(nodes)
            # admission by page availability: the queue head joins only when
            # its ledger reservation (minus cache-served pages) fits the
            # pool AND the lease cap (head-of-line — a later smaller request
            # never jumps); under pressure, LRU cache entries are evicted
            # back to the free stack before giving up
            need = self._request_pages(req) - k
            pop = prompt_pages - k
            short = self._page_shortfall(need, pop)
            if short:
                self._evict_cached(short)
                if not self._pages_available(need, pop):
                    if nodes:
                        self._release_prefix(req)
                    break
            self.kv_pool.alloc(req.rid, need)
            if nodes:
                self.stats.prefix_hits += 1
                self.stats.prefill_tokens_skipped += k * self.page_size
            self._admitted_pages_since_sync += pop
            joins.append({"slot": free.pop(0), "req": self._pop_join(),
                          "k": k, "pin": k + inserts, "pop": pop,
                          "nodes": nodes})
        by_depth: Dict[int, List[Dict[str, Any]]] = {}
        for join in joins:
            by_depth.setdefault(join["k"], []).append(join)
        return sorted(by_depth.items())

    def _count_prefill(self, group: List[Dict[str, Any]], nb: int,
                       k: int) -> None:
        """Prefill work of one admission dispatch: ``nb`` bucket rows of
        the suffix after ``k`` cached pages computed, against each join's
        real tokens in that suffix (its row minus left padding)."""
        start = k * self.page_size if self.paged else 0
        self.stats.prefill_tokens_computed += nb * (self.prompt_len - start)
        for join in group:
            req = join["req"]
            pad = self.prompt_len - len(req.prompt) - len(req.out)
            self.stats.prefill_tokens_needed += \
                self.prompt_len - max(start, pad)

    def _dispatch_dense(self, joins: List[Dict[str, Any]],
                        ) -> Dict[str, Any]:
        """One dense-ring admission dispatch (no paging); returns the
        pending record for :meth:`_finish_admit`."""
        with self._tracer.span("admit.dispatch", self._track) as sp:
            n = len(joins)
            nb = min(1 << (n - 1).bit_length() if n > 1 else 1, self.B)
            if self._tracer.enabled:
                sp.set_metadata(rids=[j["req"].rid for j in joins], rows=nb,
                                k=0)
            toks = np.zeros((nb, self.prompt_len), dtype=np.int32)
            slots = np.zeros((nb,), dtype=np.int32)
            budget = np.zeros((nb,), dtype=np.int32)
            eos = np.full((nb,), -1, dtype=np.int32)
            for j, join in enumerate(joins):
                slot, req = join["slot"], join["req"]
                toks[j] = self._padded_row(req)
                slots[j] = slot
                budget[j] = req.max_new - len(req.out)
                if req.eos is not None:
                    eos[j] = req.eos
            # pad a partial bucket by repeating row 0: duplicate-index
            # scatters then write identical values, which is deterministic
            for j in range(n, nb):
                toks[j] = toks[0]
                slots[j] = slots[0]
                budget[j] = budget[0]
                eos[j] = eos[0]
            pos0 = np.full((nb,), self.prompt_len, dtype=np.int32)
            nxt, self.caches, self.state = self._admit_fn(
                self.params, {"tokens": jnp.asarray(toks)}, self.caches,
                self.state, jnp.asarray(slots), jnp.asarray(pos0),
                jnp.asarray(budget), jnp.asarray(eos),
            )
        self.stats.prefills += 1
        self.stats.dispatches += 1
        self.stats.admit_scatter_bytes += int(
            self.stats.cache_bytes * nb / max(self.B, 1)
        )
        self._count_prefill(joins, nb, 0)
        return {"kind": "dense", "joins": joins, "nxt": nxt}

    def _dispatch_paged(self, group: List[Dict[str, Any]],
                        k: int) -> Dict[str, Any]:
        """One paged admission dispatch for joins sharing ``k`` cached
        prefix pages: cold program at k == 0, cached-suffix program
        otherwise.  Both return the written page-table rows, from which the
        planned full-page inserts learn their physical ids.  Returns the
        pending record for :meth:`_finish_admit` (no host sync here)."""
        with self._tracer.span("admit.dispatch", self._track) as sp:
            n = len(group)
            nb = min(1 << (n - 1).bit_length() if n > 1 else 1, self.B)
            if self._tracer.enabled:
                sp.set_metadata(rids=[j["req"].rid for j in group], rows=nb,
                                k=k)
            ps = self.page_size
            S = self.prompt_len - k * ps
            toks = np.zeros((nb, S), dtype=np.int32)
            slots = np.zeros((nb,), dtype=np.int32)
            budget = np.zeros((nb,), dtype=np.int32)
            eos = np.full((nb,), -1, dtype=np.int32)
            pin = np.zeros((nb,), dtype=np.int32)
            pids = np.zeros((nb, max(k, 1)), dtype=np.int32)
            rows = [self._padded_row(join["req"]) for join in group]
            for j, join in enumerate(group):
                req = join["req"]
                toks[j] = rows[j][k * ps:]
                slots[j] = join["slot"]
                budget[j] = req.max_new - len(req.out)
                if req.eos is not None:
                    eos[j] = req.eos
                pin[j] = join["pin"]
                if k:
                    pids[j] = [node.page_id for node in join["nodes"]]
            for j in range(n, nb):      # duplicate-pad with row 0 (see dense)
                toks[j] = toks[0]
                slots[j] = slots[0]
                budget[j] = budget[0]
                eos[j] = eos[0]
                pin[j] = pin[0]
                pids[j] = pids[0]
            pos0 = np.full((nb,), self.prompt_len, dtype=np.int32)
            real = np.zeros((nb,), dtype=bool)
            real[:n] = True
            if k:
                fn = cached_admit_program(self.cfg, self.scfg, k,
                                          policy=self._policy,
                                          mesh=self._mesh)
                out = fn(
                    self.params, {"tokens": jnp.asarray(toks)}, self.caches,
                    self.state, self.pages, jnp.asarray(slots),
                    jnp.asarray(pos0), jnp.asarray(budget),
                    jnp.asarray(eos), jnp.asarray(real), jnp.asarray(pids),
                    jnp.asarray(pin),
                )
            else:
                out = self._admit_fn(
                    self.params, {"tokens": jnp.asarray(toks)},
                    self.caches, self.state, self.pages,
                    jnp.asarray(slots), jnp.asarray(pos0),
                    jnp.asarray(budget), jnp.asarray(eos),
                    jnp.asarray(real), jnp.asarray(pin),
                )
            # a model with experts adds the share's counts (6th output)
            (nxt, self.caches, self.state, self.pages, out_rows), experts = \
                out[:5], out[5:]
        self.stats.prefills += 1
        self.stats.dispatches += 1
        self.stats.admit_scatter_bytes += int(
            self.stats.cache_bytes * nb * S
            / max(self.B * self.prompt_len, 1)
        )
        self._count_prefill(group, nb, k)
        return {"kind": "paged", "joins": group, "k": k, "nxt": nxt,
                "out_rows": out_rows, "rows": rows, "experts": experts}

    def _count_experts(self, counts, prefix: str) -> None:
        """Add an expert-share count vector (``moe.EXPERT_STATS``)."""
        for name, v in zip(EXPERT_STATS, counts):
            setattr(self.stats, prefix + name,
                    getattr(self.stats, prefix + name) + int(v))

    def _finish_admit(self, rec: Dict[str, Any]) -> None:
        """Post-dispatch half of one admission: read the first tokens (one
        host sync per record, span ``admit.sync``), then the host work
        (``admit.finish``)."""
        with self._tracer.span("admit.sync", self._track):
            if rec["kind"] == "paged":
                nxt_np, rows_np, *experts = jax.device_get(
                    (rec["nxt"], rec["out_rows"]) + rec["experts"])
                for e in experts:
                    self._count_experts(e, "admit_moe_")
            else:
                nxt_np = np.asarray(jax.device_get(rec["nxt"]))
                rows_np = None
        self.stats.host_syncs += 1
        with self._tracer.span("admit.finish", self._track):
            self._account_admit(rec, nxt_np, rows_np)

    def _account_admit(self, rec: Dict[str, Any], nxt_np: np.ndarray,
                       rows_np: Optional[np.ndarray]) -> None:
        """Append the first tokens, complete done-at-admission requests,
        run the planned prefix inserts, and seed the drafter history for
        the slots that stay resident."""
        k = rec.get("k", 0)
        seeds: List[Tuple[int, Request]] = []
        for j, join in enumerate(rec["joins"]):
            slot, req = join["slot"], join["req"]
            tok = int(nxt_np[j])
            req.out.append(tok)
            self.stats.admit_tokens += 1
            hit_eos = req.eos is not None and tok == req.eos
            if len(req.out) >= req.max_new or hit_eos:
                req.done = True
                self.stats.completed += 1
                if rec["kind"] == "paged":
                    if self.prefix is not None:
                        self._release_prefix(req)
                    self.kv_pool.free(req.rid)
                    # done at admission: the device never popped its prompt
                    # pages (a non-activating row allocates nothing), so
                    # take it back out of the since-sync estimate — else
                    # admit-only rounds leak the counter and starve
                    # over-subscribed admission with the pool entirely free
                    self._admitted_pages_since_sync -= join["pop"]
                continue
            self.slot_req[slot] = req
            seeds.append((slot, req))
            inserts = join.get("pin", 0) - k
            if inserts > 0:
                new_pids = rows_np[j, k:join["pin"]]
                if (new_pids >= 0).all():
                    created = self.prefix.insert(
                        req.namespace, rec["rows"][j], new_pids,
                        start_page=k)
                    assert len(created) == inserts, (created, inserts)
                    cpids = [node.page_id for node in created]
                    self.kv_pool.share(req.rid, req.namespace, cpids)
                    self.kv_pool.acquire(cpids)
                    self.prefix.acquire(created)
                    req._prefix_nodes.extend(created)
                    self.stats.prefix_inserts += len(created)
        if self._spec and seeds:
            self._seed_draft(seeds)
        self.stats.peak_resident = max(
            self.stats.peak_resident,
            sum(r is not None for r in self.slot_req))

    def _seed_draft(self, seeds: List[Tuple[int, Request]]) -> None:
        """Seed the drafter history for freshly admitted slots from the
        host-known token stream (prompt + emitted tokens, newest last) —
        one fused scatter per admission round, no sync.  Resumed requests
        re-seed with their kept output, so the n-gram index warms back up
        immediately after a migration or requeue."""
        N = self._draft_hist
        rows = np.full((len(seeds), N), -1, dtype=np.int32)
        ns = np.zeros((len(seeds),), dtype=np.int32)
        slots = np.array([s for s, _ in seeds], dtype=np.int32)
        for j, (_, req) in enumerate(seeds):
            toks = np.asarray(req.prompt, dtype=np.int32)
            if req.out:
                toks = np.concatenate(
                    [toks, np.asarray(req.out, dtype=np.int32)])
            tail = toks[-N:]
            rows[j, N - len(tail):] = tail
            ns[j] = len(tail)
        idx = jnp.asarray(slots)
        self.draft = DraftState(
            hist=self.draft.hist.at[idx].set(jnp.asarray(rows)),
            n=self.draft.n.at[idx].set(jnp.asarray(ns)),
        )

    # -- chunk sizing: adaptive to queue pressure ------------------------
    def _pick_chunk(self, active: List[int]) -> int:
        """Queue pressure → short chunks (the earliest completion bounds
        admission latency); dry queue → chunks up to the longest remaining
        budget.  Sizes snap to power-of-two buckets (bounded jit cache)."""
        rem = [self.slot_req[i].max_new - len(self.slot_req[i].out)
               for i in active]
        horizon = min(rem) if self.queue else max(rem)
        return chunk_bucket(max(1, min(horizon, self.chunk)))

    def _chunk_fn(self, n_steps: int) -> Callable:
        if self._spec:
            if self.paged:
                return paged_spec_decode_chunk_program(
                    self.cfg, self.scfg, n_steps, self._draft_window,
                    self._draft_ngram, self.page_size, policy=self._policy,
                    mesh=self._mesh)
            return spec_decode_chunk_program(
                self.cfg, self.scfg, n_steps, self._draft_window,
                self._draft_ngram, policy=self._policy, mesh=self._mesh)
        if self.paged:
            return paged_decode_chunk_program(
                self.cfg, self.scfg, n_steps, self.page_size,
                policy=self._policy, mesh=self._mesh)
        return decode_chunk_program(self.cfg, self.scfg, n_steps,
                                    policy=self._policy, mesh=self._mesh)

    def _dispatch_chunk(self, active: List[int]) -> Dict[str, Any]:
        """Dispatch one decode chunk (speculative: T draft-and-verify
        windows; otherwise T decode steps) without syncing, under span
        ``chunk.dispatch``; returns the pending record for
        :meth:`_finish_chunk`.  When admission will be dispatched behind
        this chunk (overlap), the fetch handles that the admit program
        would donate are snapshotted with cheap device-side copies
        first."""
        with self._tracer.span("chunk.dispatch", self._track) as sp:
            T = self._pick_chunk(active)
            if self._tracer.enabled:
                sp.set_metadata(T=T, active=len(active))
            t0 = self._clock()
            fetch = self._launch_chunk(T)
        self.stats.chunks += 1
        self.stats.dispatches += 1
        return {"fetch": fetch, "t0": t0, "T": T, "active": active}

    def _launch_chunk(self, T: int) -> tuple:
        """Call the T-step chunk program and return what its sync fetches."""
        self._key, sub = jax.random.split(self._key)
        ctr = None     # (4,) int32 device counters, paged modes only
        if self._spec:
            if self.paged:
                (self.caches, self.state, self.pages, self.draft, toks,
                 emitted, poisoned, ctr) = self._chunk_fn(T)(
                    self.params, self.caches, self.state, self.pages,
                    self.draft, sub)
            else:
                (self.caches, self.state, self.draft, toks, emitted,
                 poisoned) = self._chunk_fn(T)(
                    self.params, self.caches, self.state, self.draft, sub)
            self.stats.steps += T * self._draft_window
        elif self.paged:
            (self.caches, self.state, self.pages, toks, emitted,
             poisoned, ctr) = self._chunk_fn(T)(
                self.params, self.caches, self.state, self.pages, sub
            )
            self.stats.steps += T
        else:
            self.caches, self.state, toks, emitted, poisoned = \
                self._chunk_fn(T)(self.params, self.caches, self.state, sub)
            self.stats.steps += T
        fetch = (toks, emitted, poisoned)
        if self.paged:
            act, top = self.state.active, self.pages.free_top
            tab = self.pages.table if self._audit else None
            if self._overlap and self.queue and \
                    any(r is None for r in self.slot_req):
                # an admission CAN dispatch behind this chunk this round
                # (queued work + a free slot), and the admit program donates
                # state/pages: copy the few arrays this round's sync still
                # needs so the fetch survives the donation (B bools + a
                # scalar + the table).  Rounds with nothing to admit skip
                # the copies — the donation never happens.
                act, top = jnp.copy(act), jnp.copy(top)
                tab = jnp.copy(tab) if tab is not None else None
            fetch += (act, top)
            if tab is not None:
                fetch += (tab,)
            # the device-counter vector rides LAST in the same fetch (it is
            # a fresh chunk output, never donated, so no copy needed even
            # when overlap admission dispatches behind this chunk)
            fetch += (ctr,)
        return fetch

    def _finish_chunk(self, pending: Dict[str, Any],
                      *, keep_admitted_pages: int = 0) -> None:
        """Sync one dispatched chunk (span ``chunk.sync``) and run all host
        bookkeeping (``chunk.finish``): token emission, completion,
        poison/OOM requeues, page accounting, audit, watchdog.
        ``keep_admitted_pages`` is the number of pages admission
        dispatched *behind* this chunk has popped — the fetched
        ``free_top`` predates those pops, so they survive the counter
        reset."""
        with self._tracer.span("chunk.sync", self._track):
            fetched = jax.device_get(pending["fetch"])       # ONE host sync
        elapsed = self._clock() - pending["t0"]
        with self._tracer.span("chunk.finish", self._track):
            self._account_chunk(pending, fetched, elapsed,
                                keep_admitted_pages)

    def _account_chunk(self, pending: Dict[str, Any], fetched: tuple,
                       elapsed: float, keep_admitted_pages: int) -> None:
        """Host bookkeeping of one synced chunk (see :meth:`_finish_chunk`)."""
        T, active = pending["T"], pending["active"]
        stall_slot: Optional[int] = None
        if self._stall is not None:
            stall_slot, extra = self._stall
            self._stall = None
            elapsed += extra
        toks_np, emit_np, poison_np = fetched[0], fetched[1], fetched[2]
        self.stats.host_syncs += 1
        if self._spec:
            # toks/emitted are (T, B, W); emitted is a per-window prefix
            # mask over the committed tokens.  Busy/total measure *query
            # positions*, so occupancy now reflects speculative efficiency
            # (rejected drafts are idle device work).
            W = self._draft_window
            self.stats.slot_total_steps += self.B * T * W
            self.stats.slot_busy_steps += int(emit_np.sum())
            for i in active:
                req = self.slot_req[i]
                for t in range(T):
                    c = int(emit_np[t, i].sum())
                    if c == 0:
                        break       # deactivated (EOS/budget/OOM/poison)
                    req.out.extend(int(x) for x in toks_np[t, i, :c])
                    self.stats.decode_tokens += c
                    self.stats.spec_windows += 1
                    self.stats.drafted_tokens += W - 1
                    self.stats.accepted_tokens += c - 1
                self._maybe_complete(i, req)
        else:
            self.stats.slot_total_steps += self.B * T
            self.stats.slot_busy_steps += int(emit_np.sum())
            for i in active:
                req = self.slot_req[i]
                for t in range(T):
                    if not emit_np[t, i]:
                        break
                    req.out.append(int(toks_np[t, i]))
                    self.stats.decode_tokens += 1
                self._maybe_complete(i, req)
        # non-finite sentinel: the device deactivated the flagged slots
        # before selecting or emitting a token (and, paged, recycled their
        # pages in the same step), so no poisoned value reached any output
        # stream; requeue the victims — pre-fault tokens are host-side
        # and survive
        for i in active:
            req = self.slot_req[i]
            if req is not None and bool(poison_np[i]):
                self.stats.poisoned_slots += 1
                if self._tracer.enabled:
                    self._tracer.instant("poisoned_slot", self._track,
                                         args={"slot": i})
                self._requeue_slot(i, req)
        if self.paged:
            active_np = fetched[3]
            # device counters: in-scan paging/accept activity that rode
            # back inside this same sync (last element of the fetch)
            ctr_np = fetched[-1]
            self.stats.device_pages_popped += int(ctr_np[0])
            self.stats.device_pages_pushed += int(ctr_np[1])
            self.stats.fault_denied_slots += int(ctr_np[2])
            self.stats.device_draft_accepted += int(ctr_np[3])
            if len(ctr_np) > 4:
                self._count_experts(ctr_np[4:], "moe_")
            self._stalled = self._stalled + 1 \
                if int(emit_np.sum()) == 0 else 0
            # a slot that deactivated without finishing was denied a page
            # (pool dry / quota hit): requeue its request at the head.  When
            # prompt + generated still fit the prompt bucket, the generated
            # tokens are KEPT — re-admission prefills prompt+output and
            # decoding resumes where the eviction cut it off; only an
            # overflowing request restarts from its prompt (the discarded
            # emissions stay out of ``stats.tokens``).  Note the resumed
            # row is left-padded differently than the original prompt, so
            # it does NOT hit the original's cached prefix pages — only
            # other requests resumed at the same output length align
            # (counted as ``resume_prefix_misses`` at re-admission)
            oomed = 0
            for i in active:
                req = self.slot_req[i]
                if req is not None and not bool(active_np[i]):
                    if self._tracer.enabled:
                        self._tracer.instant("oom_requeue", self._track,
                                             args={"slot": i})
                    if self._requeue_slot(i, req):
                        self.stats.oom_resumed += 1
                    self.stats.oom_requeues += 1
                    oomed += 1
            if oomed:
                self._resident_cap = max(
                    1, sum(r is not None for r in self.slot_req))
            elif self._resident_cap < self.B:
                self._resident_cap += 1
            self.stats.pages_in_use = self.n_pages - int(fetched[4])
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, self.stats.pages_in_use)
            self._admitted_pages_since_sync = keep_admitted_pages
            if self._audit:
                self._run_audit(np.asarray(fetched[5]))
        if self._watchdog_s is not None and elapsed > self._watchdog_s:
            self._watchdog_trip(stall_slot)

    def _maybe_complete(self, slot: int, req: Request) -> None:
        """Retire ``slot`` if its request just hit EOS or its budget."""
        hit_eos = req.eos is not None and req.out and req.out[-1] == req.eos
        if len(req.out) >= req.max_new or hit_eos:
            req.done = True
            self.slot_req[slot] = None
            self.stats.completed += 1
            if self.paged:
                if self.prefix is not None:
                    self._release_prefix(req)
                self.kv_pool.free(req.rid)

    # -- one scheduling round ---------------------------------------------
    def step(self) -> None:
        """One scheduling round.

        Serial (default): admit, then decode one chunk — two dispatches,
        two syncs, strictly ordered.

        Overlap (``overlap=True``): dispatch the decode chunk first
        (no sync), then run admission **behind it** — all of admission's
        host-side planning (queue scan, prefix lookups, row packing) plus
        its prefill dispatch happen while the chunk is still computing, and
        the device serializes the two programs through the donated cache
        tree.  One merge point per round: the chunk's sync, then each
        admission's.  The chunk ran against pre-admission state, so its
        fetched ``active``/``free_top`` never see the new slots; this
        round's admission pops are carried across the counter reset."""
        if not self._overlap:
            with self._tracer.span("round", self._track):
                self._admit()
                active = [i for i, r in enumerate(self.slot_req)
                          if r is not None]
                if not active:
                    return
                self._finish_chunk(self._dispatch_chunk(active))
            return
        with self._tracer.span("round", self._track):
            active = [i for i, r in enumerate(self.slot_req)
                      if r is not None]
            pending = self._dispatch_chunk(active) if active else None
            pops_before = self._admitted_pages_since_sync
            admits = self._admit(defer=True)
            round_pops = self._admitted_pages_since_sync - pops_before
            if pending is not None and admits:
                self.stats.overlap_rounds += 1
            if pending is not None:
                self._finish_chunk(pending, keep_admitted_pages=round_pops)
            for rec in admits:
                self._finish_admit(rec)

    def run(self, *, max_steps: int = 10_000) -> BatcherStats:
        while (self.queue or any(r is not None for r in self.slot_req)) and \
                self.stats.steps < max_steps:
            before = self.stats.dispatches
            self.step()
            if self.stats.dispatches == before and \
                    not any(r is not None for r in self.slot_req):
                break   # starved: queued work cannot be admitted (page limit)
            if self._stalled >= 8:
                break   # page-fault livelock: the pool cannot fit even one
                        # request's footprint at the current quota
        return self.stats
