"""Telemetry plane: metrics registry, log-bucketed histograms, Chrome-trace
tracer, registry-backed BatcherStats views, device counters riding the
per-chunk sync, per-tenant SLO quantiles, and injectable clocks.

Layered like the module: pure-python registry/tracer first (no JAX), then
the serving integration (device counters, ≤1-dispatch/≤1-sync contract
with telemetry enabled, trace export from a real run).
"""

import glob
import itertools
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import (
    Histogram, MetricsRegistry, NULL_TRACER, Telemetry, Tracer, percentile,
)
from repro.serving.batcher import BatcherStats, _STATS_FIELDS


# ---------------------------------------------------------------------------
# percentile + histogram (pure python)
# ---------------------------------------------------------------------------

class TestPercentile:
    def test_empty_is_nan(self):
        assert math.isnan(percentile([], 0.5))

    def test_matches_sorted_index(self):
        vals = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(vals, 0.0) == 1.0
        assert percentile(vals, 0.5) == 3.0
        assert percentile(vals, 0.99) == 5.0
        assert percentile(vals, 1.0) == 5.0      # clamped to last element

    def test_single_value(self):
        assert percentile([7.0], 0.5) == 7.0
        assert percentile([7.0], 0.99) == 7.0


class TestHistogram:
    def test_counts_and_extremes_exact(self):
        h = Histogram()
        for v in (0.5, 2.0, 8.0, 0.25):
            h.record(v)
        assert h.count == 4
        assert h.total == pytest.approx(10.75)
        assert h.min == 0.25 and h.max == 8.0
        assert h.mean == pytest.approx(10.75 / 4)

    def test_quantile_relative_error_bounded(self):
        """Log-bucketed quantiles are within one bucket (base 1.08 → ~8%
        relative error) of the exact percentile on a lognormal sample."""
        rng = np.random.default_rng(0)
        vals = np.exp(rng.normal(0.0, 1.5, size=5000)).tolist()
        h = Histogram()
        for v in vals:
            h.record(v)
        for q in (0.5, 0.95, 0.99):
            exact = percentile(vals, q)
            assert abs(h.quantile(q) - exact) / exact < 0.09, q

    def test_quantile_clamped_to_observed_range(self):
        h = Histogram()
        h.record(3.0)
        assert h.quantile(0.0) == 3.0
        assert h.quantile(0.99) == 3.0

    def test_nonpositive_values_bucket(self):
        h = Histogram()
        h.record(0.0)
        h.record(-1.0)
        h.record(2.0)
        assert h.count == 3
        assert h.min == -1.0
        assert h.quantile(0.0) == -1.0           # zero-bucket rank 0

    def test_empty_quantile_nan(self):
        assert math.isnan(Histogram().quantile(0.5))

    def test_quantiles_keys(self):
        h = Histogram()
        h.record(1.0)
        assert set(h.quantiles()) == {"p50", "p95", "p99"}


class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("req", "a").inc(3)
        reg.counter("req", "b").inc()
        reg.gauge("occ").set(0.5)
        reg.histogram("lat", "a").record(0.2)
        assert reg.counter("req", "a").value == 3   # get-or-create, same obj
        assert sorted(reg.labels("req")) == ["a", "b"]
        snap = reg.snapshot()
        path = reg.export(str(tmp_path / "m.json"))
        assert json.load(open(path)) == snap
        assert snap["counters"]["req{a}"] == 3
        assert snap["gauges"]["occ"] == 0.5
        assert snap["histograms"]["lat{a}"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer (pure python, injectable clock)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0
        self.calls = 0

    def __call__(self):
        self.calls += 1
        self.t += 1.0
        return self.t


class TestTracer:
    def test_span_and_instant_timing(self):
        clk = _FakeClock()
        tr = Tracer(clock=clk)
        with tr.span("work", "tenantA", args={"k": 1}):
            tr.instant("mark", "tenantA")
        assert [e["ph"] for e in tr.events] == ["i", "X"]
        span = tr.events[1]
        assert span["name"] == "work" and span["dur"] == 2.0
        assert tr.tracks() == ["tenantA"]

    def test_instant_ts_override_for_sim_time(self):
        clk = _FakeClock()
        tr = Tracer(clock=clk)
        tr.instant("ev", "hyp", ts=42.5)
        assert tr.events[0]["ts"] == 42.5
        assert clk.calls == 0                    # sim time, clock untouched

    def test_chrome_export_schema(self, tmp_path):
        tr = Tracer(clock=_FakeClock())
        with tr.span("round", "a"):
            pass
        tr.instant("fault", "b")
        path = tr.export(str(tmp_path / "t.json"))
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas} == {"a", "b"}
        xs = [e for e in evs if e["ph"] == "X"]
        ins = [e for e in evs if e["ph"] == "i"]
        assert len(xs) == 1 and len(ins) == 1
        assert ins[0]["s"] == "t"
        # min-ts normalized to 0 and seconds scaled to integer-ish µs
        assert min(e["ts"] for e in xs + ins) == 0
        assert xs[0]["dur"] == pytest.approx(1e6)
        # tracks get distinct tids under one pid
        assert len({e["tid"] for e in xs + ins}) == 2

    def test_disabled_is_zero_cost(self):
        clk = _FakeClock()
        tr = Tracer(clock=clk, enabled=False)
        with tr.span("x", "a"):
            tr.instant("y", "a")
        assert tr.events == [] and clk.calls == 0
        # the shared singleton behaves the same
        with NULL_TRACER.span("x", "a"):
            NULL_TRACER.instant("y", "a")
        assert NULL_TRACER.events == []

    def test_span_set_metadata_adds_args(self):
        """A site names what it found once it knows it; on the disabled
        span the call is a no-op that never touches the clock."""
        tr = Tracer(clock=_FakeClock())
        with tr.span("admit.plan", "a", args={"queued": 3}) as sp:
            sp.set_metadata(joins=2)
        assert tr.events[0]["args"] == {"queued": 3, "joins": 2}
        clk = _FakeClock()
        off = Tracer(clock=clk, enabled=False)
        with off.span("admit.plan", "a") as sp:
            sp.set_metadata(joins=2)
        assert off.events == [] and clk.calls == 0

    def test_obs_importable_without_jax(self):
        """The profiler sink imports JAX lazily: ``repro.obs`` and the
        in-memory tracer work where JAX cannot be imported."""
        import repro.obs

        src = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(repro.obs.__file__))))
        code = (f"import sys; sys.path.insert(0, {src!r})\n"
                "sys.modules['jax'] = None\n"
                "from repro.obs import Tracer\n"
                "with Tracer().span('x'):\n    pass\n")
        subprocess.run([sys.executable, "-c", code], check=True)

    def test_max_events_drops_counted(self):
        tr = Tracer(clock=_FakeClock(), max_events=2)
        for _ in range(5):
            tr.instant("e", "a")
        assert len(tr.events) == 2 and tr.dropped == 3


# ---------------------------------------------------------------------------
# BatcherStats as a registry view (no JAX)
# ---------------------------------------------------------------------------

class TestBatcherStatsView:
    def test_fresh_stats_ratio_properties_defined(self):
        """Every derived ratio is finite/defined on a fresh (all-zero)
        stats object — no ZeroDivisionError on an idle batcher."""
        st = BatcherStats()
        assert st.tokens == 0
        assert st.acceptance_rate == 0.0
        assert st.occupancy == 0.0
        assert st.prefix_tokens_saved == 0.0
        assert st.dispatches_per_token == 0.0
        assert st.syncs_per_token == 0.0
        assert st.decode_dispatches_per_token == 0.0

    def test_kwargs_seed_and_unknown_field_rejected(self):
        st = BatcherStats(cache_bytes=123)
        assert st.cache_bytes == 123
        with pytest.raises(TypeError):
            BatcherStats(not_a_field=1)
        with pytest.raises(AttributeError):
            BatcherStats().no_such_counter

    @pytest.mark.parametrize("seed", range(3))
    def test_registry_view_equals_legacy_fields(self, seed):
        """Property-style: after random counter churn the attribute view,
        ``as_dict()``, and the raw registry all agree."""
        rng = np.random.default_rng(seed)
        reg = MetricsRegistry()
        st = BatcherStats(registry=reg, tenant="t0")
        shadow = {f: 0 for f in _STATS_FIELDS}
        for _ in range(200):
            f = _STATS_FIELDS[rng.integers(len(_STATS_FIELDS))]
            k = int(rng.integers(1, 5))
            setattr(st, f, getattr(st, f) + k)
            shadow[f] += k
        assert st.as_dict() == shadow
        for f in _STATS_FIELDS:
            assert getattr(st, f) == shadow[f]
            assert reg.counter(f"serving.{f}", "t0").value == shadow[f]

    def test_two_tenants_share_registry_without_collision(self):
        reg = MetricsRegistry()
        a = BatcherStats(registry=reg, tenant="a")
        b = BatcherStats(registry=reg, tenant="b")
        a.chunks += 3
        b.chunks += 5
        assert a.chunks == 3 and b.chunks == 5
        assert sorted(reg.labels("serving.chunks")) == ["a", "b"]


# ---------------------------------------------------------------------------
# serving integration: device counters, contract, trace from a real run
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")

from repro.configs import get_reduced                         # noqa: E402
from repro.models import init_params                          # noqa: E402
from repro.serving import ServingConfig                       # noqa: E402
from repro.serving.batcher import ContinuousBatcher, Request  # noqa: E402


#: the batcher's phase spans; each lies inside a ``round`` span
PHASES = ("admit.plan", "admit.dispatch", "admit.sync", "admit.finish",
          "chunk.dispatch", "chunk.sync", "chunk.finish")


def _assert_phases_in_rounds(spans):
    """``spans``: (name, start, end).  Every phase span lies inside a
    ``round`` span."""
    rounds = [(s, e) for n, s, e in spans if n == "round"]
    for n, s, e in spans:
        if n in PHASES:
            assert any(rs <= s and e <= re_ for rs, re_ in rounds), (n, s)


def _host_events(log_dir):
    """(name, start_ns, end_ns, stats) of every event on the profiler's
    host planes, read back from the trace the session wrote."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats))
                           for ev in line.events)
    return out


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen3-0.6b")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _run(params, cfg, sc, n=8, *, telemetry=None, max_new=10, seed=3):
    rng = np.random.default_rng(seed)
    b = ContinuousBatcher(params, cfg, sc, telemetry=telemetry)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        size=1 + i % 6).astype(np.int32),
                    max_new=max_new + i % 4)
            for i in range(n)]
    for r in reqs:
        b.submit(r)
    stats = b.run(max_steps=4000)
    return b, reqs, stats


class TestServingTelemetry:
    def test_contract_and_trace_with_telemetry_enabled(self, qwen):
        """Tracing must not add dispatches or syncs: a clean paged run keeps
        dispatches == syncs == chunks + prefills, and the exported trace
        carries the round and phase spans on the tenant track, one
        dispatch span per dispatch and one sync span per sync."""
        cfg, params = qwen
        tel = Telemetry(tracer=Tracer(), tenant="tenantA")
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=64)
        _, reqs, st = _run(params, cfg, sc, telemetry=tel)
        assert all(r.done for r in reqs)
        assert st.dispatches == st.chunks + st.prefills
        assert st.host_syncs == st.chunks + st.prefills
        names = [e["name"] for e in tel.tracer.events]
        assert set(PHASES) | {"round"} <= set(names)
        assert names.count("chunk.dispatch") == st.chunks
        assert names.count("admit.dispatch") == st.prefills
        assert names.count("chunk.sync") + names.count("admit.sync") \
            == st.host_syncs
        assert tel.tracer.tracks() == ["tenantA"]
        _assert_phases_in_rounds(
            [(e["name"], e["ts"], e["ts"] + e["dur"])
             for e in tel.tracer.events if e["ph"] == "X"])
        # stats landed in the shared registry under the tenant label
        assert tel.registry.counter("serving.chunks", "tenantA").value \
            == st.chunks

    def test_device_counters_page_conservation(self, qwen):
        """In-scan pops ride back and cover every decode page fault: a
        clean run pops at least one page per boundary crossing and pushes
        back the in-scan frees."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=4,
                           n_pages=96)
        b, reqs, st = _run(params, cfg, sc, max_new=14)
        assert all(r.done for r in reqs)
        assert st.device_pages_popped > 0
        assert st.device_pages_pushed > 0
        assert st.fault_denied_slots == 0        # pool never dry
        # pops never exceed the pool and the ledger reconciled at each sync
        assert st.device_pages_popped <= st.chunks * sc.chunk * sc.slots

    def test_fault_denied_counted_on_device(self, qwen):
        """Over-subscribe the quota: in-scan page denials are observed on
        device and ride back.  (No exact ordering vs ``oom_requeues`` — a
        requeue can also originate at re-admission, outside the scan.)"""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=16, page_quota=5, reserve_pages=False)
        _, reqs, st = _run(params, cfg, sc, seed=17)
        assert all(r.done for r in reqs)
        assert st.oom_requeues > 0, "quota never exercised the denial path"
        assert st.fault_denied_slots > 0, \
            "device never observed the in-scan denials"

    def test_device_draft_accepted_matches_host(self, qwen):
        """The on-device accepted-token count agrees with the host-side
        commit accounting in a clean speculative paged run."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=48, chunk=4,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=96, speculative=True, draft_window=4)
        _, reqs, st = _run(params, cfg, sc, n=6)
        assert all(r.done for r in reqs)
        assert st.spec_windows > 0
        assert st.device_draft_accepted == st.accepted_tokens

    def test_telemetry_off_by_default_and_identical_tokens(self, qwen):
        """The default batcher gets NULL_TRACER and the token streams are
        identical with tracing on (observability never changes decode)."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=64)
        b, plain, _ = _run(params, cfg, sc)
        assert b._tracer is NULL_TRACER
        _, traced, _ = _run(params, cfg, sc,
                            telemetry=Telemetry(tracer=Tracer()))
        assert [r.out for r in plain] == [r.out for r in traced]


class TestProfilerSink:
    def test_span_lands_on_host_plane_with_args(self, tmp_path):
        """A profiler-sink span is a ``TraceAnnotation``: inside a profiler
        session it lands on the host plane with its track and args (a list
        as its text); nothing is kept in memory, and pre-measured stamps
        are refused."""
        tr = Tracer(profiler=True)
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("admit.plan", "tenantA", args={"queued": 3}) as sp:
                sp.set_metadata(joins=2, rids=[4, 5])
            tr.instant("oom_requeue", "tenantA", args={"slot": 1})
        evs = {n: (s, e, st) for n, s, e, st in _host_events(tmp_path)}
        assert evs["admit.plan"][2] == {"track": "tenantA", "queued": 3,
                                        "joins": 2, "rids": "[4, 5]"}
        assert evs["oom_requeue"][2] == {"track": "tenantA", "slot": 1}
        assert evs["admit.plan"][1] <= evs["oom_requeue"][0]
        assert tr.events == []
        with pytest.raises(ValueError):
            tr.complete("recovery", "tenantA", 0.0, 1.0)
        with pytest.raises(ValueError):
            tr.instant("arrival", "tenantA", ts=1.0)

    @pytest.mark.parametrize("overlap", [False, True])
    def test_serving_run_phases_nested_in_rounds(self, qwen, tmp_path,
                                                 overlap):
        """A paged serving run under the profiler sink puts ``round`` and
        every admission and chunk phase on the host plane, on the batcher's
        track, one ``chunk.dispatch`` per chunk and one ``admit.dispatch``
        per prefill, each phase inside a round."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=64, overlap=overlap)
        tel = Telemetry(tracer=Tracer(profiler=True), tenant="tenantA")
        with jax.profiler.trace(str(tmp_path)):
            _, reqs, st = _run(params, cfg, sc, telemetry=tel)
        assert all(r.done for r in reqs)
        spans = [(n, s, e) for n, s, e, stats in _host_events(tmp_path)
                 if stats.get("track") == "tenantA"]
        names = [n for n, _, _ in spans]
        assert set(PHASES) | {"round"} <= set(names)
        assert names.count("chunk.dispatch") == st.chunks
        assert names.count("admit.dispatch") == st.prefills
        _assert_phases_in_rounds(spans)


def _requests(cfg, lengths, *, max_new=4, seed=0, namespace=None,
              prefix=None, rid0=0):
    rng = np.random.default_rng(seed)
    out = []
    for j, n in enumerate(lengths):
        prompt = rng.integers(1, cfg.vocab, size=n).astype(np.int32)
        if prefix is not None:
            prompt[:len(prefix)] = prefix
        out.append(Request(rid=rid0 + j, prompt=prompt, max_new=max_new,
                           namespace=namespace))
    return out


class TestAdmissionCounters:
    def test_submit_before_admit_and_requeue_keeps_first_stamp(self, qwen):
        """Every request is stamped at submit and at its first admission,
        in that order; an OOM-requeued request rejoins (``admitted`` counts
        it again) but keeps its first ``t_admit``."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=8, max_len=64, chunk=8,
                           attn_impl="xla", paged=True, page_size=8,
                           n_pages=16, page_quota=5, reserve_pages=False)
        ticks = itertools.count()
        b = ContinuousBatcher(params, cfg, sc,
                              clock=lambda: float(next(ticks)))
        rng = np.random.default_rng(17)
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab,
                                            size=1 + i % 6).astype(np.int32),
                        max_new=10 + i % 4)
                for i in range(8)]
        for r in reqs:
            b.submit(r)
        first = {}
        for _ in range(4000):
            if not (b.queue or any(r is not None for r in b.slot_req)):
                break
            b.step()
            for r in reqs:
                if r.t_admit is not None:
                    first.setdefault(r.rid, r.t_admit)
        assert all(r.done for r in reqs)
        assert b.stats.oom_requeues > 0, "quota never forced a requeue"
        assert b.stats.admitted > len(reqs)
        for r in reqs:
            assert r.t_submit <= r.t_admit == first[r.rid]

    @pytest.mark.parametrize("paged", [True, False])
    def test_three_joins_in_a_bucket_of_four(self, qwen, paged):
        """3 requests join one round: the bucket is 4 rows, one of them a
        duplicate of row 0.  Computed: 4 rows x 8 tokens; needed: the
        prompts' own 5 + 8 + 3 tokens; each waited 2.5 s on the clock."""
        cfg, params = qwen
        now = [0.0]
        sc = ServingConfig(slots=4, prompt_len=8, max_len=32, chunk=4,
                           attn_impl="xla", paged=paged, page_size=4,
                           n_pages=64 if paged else None)
        b = ContinuousBatcher(params, cfg, sc, clock=lambda: now[0])
        for r in _requests(cfg, (5, 8, 3)):
            b.submit(r)
        now[0] = 2.5
        b.step()
        st = b.stats
        assert st.prefills == 1 and st.admitted == 3
        assert st.prefill_tokens_computed == 4 * 8
        assert st.prefill_tokens_needed == 5 + 8 + 3
        assert st.queue_wait_us == 3 * 2_500_000
        assert st.admit_plan_us == 0                # the clock stood still

    def test_cached_admission_counts_the_suffix(self, qwen):
        """A cached admission computes only the suffix after the cached
        pages: 3 hits on a 2-page (8-token) prefix of 16-token prompts
        compute 4 rows x 8 tokens and need 3 x 8."""
        cfg, params = qwen
        sc = ServingConfig(slots=4, prompt_len=16, max_len=32, chunk=4,
                           attn_impl="xla", paged=True, page_size=4,
                           n_pages=64, prefix_cache=True)
        b = ContinuousBatcher(params, cfg, sc)
        doc = np.random.default_rng(5).integers(1, cfg.vocab, size=8)
        for r in _requests(cfg, (16, 16), namespace="d", prefix=doc):
            b.submit(r)             # the pair's recurrence inserts the doc
        b.run()
        assert b.stats.prefix_inserts == 2
        before = b.stats.as_dict()
        for r in _requests(cfg, (16, 16, 16), namespace="d", prefix=doc,
                           seed=1, rid0=10):
            b.submit(r)
        b.step()
        d = {k: v - before[k] for k, v in b.stats.as_dict().items()}
        assert d["prefix_hits"] == 3 and d["prefills"] == 1
        assert d["admitted"] == 3
        assert d["prefill_tokens_computed"] == 4 * 8
        assert d["prefill_tokens_needed"] == 3 * 8


# ---------------------------------------------------------------------------
# executor SLO quantiles + injectable clock (bookkeeping only)
# ---------------------------------------------------------------------------

class TestExecutorObservability:
    @pytest.fixture()
    def vpool(self):
        from repro.serving.tenancy import VirtualAcceleratorPool

        return VirtualAcceleratorPool(devices=list(jax.devices()) * 8,
                                      devices_per_core=1)

    def test_slo_report_quantiles(self, vpool):
        from repro.serving.tenancy import ServingExecutor

        from repro.core.hypervisor import RequestRecord

        ex = ServingExecutor(vpool)
        lats = [0.1 * (i + 1) for i in range(20)]      # 0.1 .. 2.0
        for lt in lats:
            ex.record_latency("a", lt, slo=1.0)
        ex.note_drop(RequestRecord("b", 0, t_arrival=0.0))
        rep = ex.slo_report()
        assert rep["a"]["requests"] == 20
        assert rep["a"]["p50_latency"] == pytest.approx(
            percentile(lats, 0.5), rel=0.09)
        assert rep["a"]["p99_latency"] == pytest.approx(
            percentile(lats, 0.99), rel=0.09)
        assert rep["a"]["p50_latency"] <= rep["a"]["p95_latency"] \
            <= rep["a"]["p99_latency"]
        # a tenant that only dropped has no latency sample → None, not 0
        assert rep["b"]["dropped"] == 1
        assert rep["b"]["p99_latency"] is None

    def test_legacy_slo_counts_view(self, vpool):
        from repro.serving.tenancy import ServingExecutor

        from repro.core.hypervisor import RequestRecord

        ex = ServingExecutor(vpool)
        ex.record_latency("a", 0.2, slo=0.5)
        ex.record_latency("a", 0.9, slo=0.5)
        ex.note_drop(RequestRecord("a", 0, t_arrival=0.0))
        assert ex._slo_counts == {"a": {"n": 3, "met": 1, "dropped": 1}}

    def test_injectable_clock_times_remesh(self, vpool):
        """A fake clock makes the reconfigure timing deterministic — the
        logged t_remesh is exactly the clock delta across the callback."""
        from repro.serving.tenancy import ServingExecutor, SwitchMode

        clk = _FakeClock()
        ex = ServingExecutor(vpool, clock=clk)
        vpool.lease("a", 2)
        ex.register_remesh("a", lambda mesh: None)
        ex.exec_resize("a", 4, 0.0, SwitchMode.TASK_LEVEL)
        assert ex.reconfig_log[-1]["t_remesh"] == pytest.approx(1.0)

    def test_executor_telemetry_traces_reconfig(self, vpool):
        from repro.serving.tenancy import ServingExecutor, SwitchMode

        tel = Telemetry(tracer=Tracer(clock=_FakeClock()))
        ex = ServingExecutor(vpool, telemetry=tel, clock=_FakeClock())
        vpool.lease("a", 2)
        ex.register_remesh("a", lambda mesh: None)
        ex.exec_resize("a", 4, 0.0, SwitchMode.TASK_LEVEL)
        names = [e["name"] for e in tel.tracer.events]
        assert "remesh" in names


class TestHypervisorTelemetry:
    def test_events_land_on_tenant_tracks(self):
        from repro.core.hypervisor import (
            Hypervisor, ResourcePool, TenantSpec,
        )

        tel = Telemetry(tracer=Tracer(clock=_FakeClock()))
        hv = Hypervisor(ResourcePool(16), telemetry=tel)
        hv.admit(TenantSpec("a", 8))
        hv.admit(TenantSpec("b", 8))
        hv.run(1.0)
        kinds = {e["name"] for e in tel.tracer.events}
        assert "arrival" in kinds
        assert {"a", "b"} <= set(tel.tracer.tracks())
        assert tel.registry.counter("hypervisor.events.arrival").value >= 2
