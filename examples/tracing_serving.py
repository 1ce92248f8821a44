"""Unified telemetry plane: one trace across hypervisor + serving.

    PYTHONPATH=src python examples/tracing_serving.py

Parts 1 and 2 land in ONE :class:`repro.obs.Telemetry` bundle — a shared
``MetricsRegistry`` plus a shared in-memory ``Tracer`` — and export as a
single Chrome-trace JSON (open it at https://ui.perfetto.dev) with one
track per tenant plus a hypervisor track:

1. **Pool chaos (sim time)** — a seeded :class:`FaultInjector` drops core
   deaths onto a three-tenant hypervisor run.  Every event-loop event
   becomes an instant on its tenant's track (``ts=`` carries the sim
   clock), and each displaced tenant's re-placement becomes a
   ``recovery`` span.
2. **Two-tenant paged serving (wall time)** — ``tenant-a`` decodes on a
   tensor-sharded paged batcher and is re-meshed tp=1→2 live by the
   ``ServingExecutor`` (a ``remesh`` span); ``tenant-b`` runs with a
   starved ``kv_pages`` quota so denied in-scan page faults requeue
   (``oom_requeue`` instants + the ``fault_denied_slots`` device
   counter).  Both batchers label the same registry with their tenant, so
   their ``round`` spans — each holding the admission phases
   (``admit.plan``/``admit.dispatch``/``admit.sync``/``admit.finish``) and
   the chunk phases (``chunk.dispatch``/``chunk.sync``/``chunk.finish``) —
   interleave on separate tracks, and per-request latencies feed
   ``slo_report`` p50/p95/p99.

The committed sample trace in ``examples/traces/`` was produced by this
script (``max_events`` bounds its size).

3. **Profiler sink (device clock)** — how an operator lays the batcher's
   phases against the device's own trace: the same batcher spans, with
   ``Tracer(profiler=True)``, become ``jax.profiler`` annotations on the
   profiler's host plane while a ``jax.profiler.trace`` session runs.  The
   session's directory (``experiments/tracing_profile/``) opens in
   TensorBoard's profile plugin or Perfetto, spans beside the device ops;
   the script reads the spans back with ``jax.profiler.ProfileData``.
   Simulated time cannot be placed on that clock, so the hypervisor's
   sim-time events stay on the in-memory tracer of parts 1 and 2.
"""

import glob
import os
import sys
import time
from collections import Counter

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")

import numpy as np
import jax

from repro.configs import get_reduced
from repro.core import FaultInjector, Hypervisor, ResourcePool, TenantSpec
from repro.models import init_params
from repro.obs import Telemetry, Tracer
from repro.serving import ServingConfig
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.tenancy import (
    ServingExecutor, SwitchMode, VirtualAcceleratorPool,
)

PROMPT_LEN, MAX_NEW = 8, 12


def pool_chaos(tel: Telemetry) -> None:
    """Seeded faults over a 16-core hypervisor run — sim-time instants on
    tenant tracks, recovery spans when displaced tenants are re-placed."""
    hv = Hypervisor(ResourcePool(16), telemetry=tel)
    for name in ("gold", "silver", "bronze"):
        hv.schedule_arrival(TenantSpec(name, requested_cores=8, min_cores=2),
                            at=0.0)
    inj = FaultInjector(16, seed=1337, death_rate=0.6, slow_rate=0.2,
                        repair_after=1.5)
    faults = inj.inject(hv.queue, 6.0)
    hv.run(8.0)
    rec = hv.recovery_log
    print(f"pool chaos: {len(faults)} seeded faults, "
          f"{len(rec)} recoveries traced")


def requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        size=2 + i % 6).astype(np.int32),
                    max_new=MAX_NEW)
            for i in range(n)]


def serving(tel: Telemetry, clock) -> ServingExecutor:
    """Two paged tenants under load: a live tp re-mesh on tenant-a, a
    starved page quota on tenant-b, per-request latencies into the SLO
    report — all on the shared telemetry bundle."""
    cfg = get_reduced("qwen3-0.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))

    vpool = VirtualAcceleratorPool(devices=jax.devices(), devices_per_core=1)
    ex = ServingExecutor(vpool, clock=clock, telemetry=tel)
    ex.exec_admit(TenantSpec("tenant-a", requested_cores=1, artifact=None),
                  1, at=clock())

    tel_a = Telemetry(registry=tel.registry, tracer=tel.tracer,
                      tenant="tenant-a")
    tel_b = Telemetry(registry=tel.registry, tracer=tel.tracer,
                      tenant="tenant-b")
    # tenant-a mirrors bench_sharded's paged+tp shape (chunk=4, page_size=4)
    # so the tp=2 re-mesh compile stays example-sized on emulated devices
    a = ContinuousBatcher(
        params, cfg,
        ServingConfig(slots=4, prompt_len=PROMPT_LEN,
                      max_len=PROMPT_LEN + MAX_NEW + 4, chunk=4,
                      paged=True, page_size=4, n_pages=64, tp=1),
        mesh=vpool.tp_mesh_for(vpool.pool.lease_of("tenant-a")),
        telemetry=tel_a, clock=clock)
    b = ContinuousBatcher(
        params, cfg,
        ServingConfig(slots=4, prompt_len=PROMPT_LEN,
                      max_len=PROMPT_LEN + MAX_NEW + 4, chunk=8,
                      paged=True, page_size=8, n_pages=16, page_quota=5,
                      reserve_pages=False),
        telemetry=tel_b, clock=clock)
    ex.register_remesh("tenant-a", lambda mesh: a.remesh(mesh=mesh))

    t_submit = {}
    reqs = {}
    for who, batcher in (("tenant-a", a), ("tenant-b", b)):
        reqs[who] = requests(cfg, 8, seed={"tenant-a": 3, "tenant-b": 17}[who])
        for r in reqs[who]:
            t_submit[(who, r.rid)] = clock()
            batcher.submit(r)

    # interleave the tenants by hand so their round spans overlap on the
    # trace; re-mesh tenant-a to 2 devices a few rounds in
    def busy(batcher):
        return batcher.queue or any(r is not None for r in batcher.slot_req)

    pending = {"tenant-a": a, "tenant-b": b}
    done_at = {}
    steps = 0
    while pending:
        for who, batcher in list(pending.items()):
            batcher.step()
            for req in reqs[who]:
                key = (who, req.rid)
                if req.done and key not in done_at:
                    done_at[key] = clock()
                    ex.record_latency(who, done_at[key] - t_submit[key],
                                      slo=30.0)  # wall time incl. compiles
            if not busy(batcher):
                del pending[who]
        steps += 1
        if steps == 2:
            ex.exec_resize("tenant-a", 2, clock(), SwitchMode.TASK_LEVEL)
            print(f"re-meshed tenant-a tp=1 -> tp=2 "
                  f"(t_remesh={ex.reconfig_log[-1]['t_remesh']*1e3:.0f} ms)")

    assert b.stats.oom_requeues > 0, "quota never starved tenant-b"
    print(f"serving: tenant-a {a.stats.tokens} tokens "
          f"({a.stats.remeshes} re-mesh), tenant-b {b.stats.tokens} tokens "
          f"({b.stats.oom_requeues} OOM requeues, "
          f"{b.stats.fault_denied_slots} denied in-scan)")
    return ex


def profiled_serving(log_dir: str) -> None:
    """One paged batcher traced by the JAX profiler: its phase spans land
    on the profiler's host plane, on the device trace's clock."""
    from jax.profiler import ProfileData

    cfg = get_reduced("qwen3-0.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(
        params, cfg,
        ServingConfig(slots=4, prompt_len=PROMPT_LEN,
                      max_len=PROMPT_LEN + MAX_NEW + 4, chunk=4,
                      paged=True, page_size=4, n_pages=64),
        telemetry=Telemetry(tracer=Tracer(profiler=True),
                            tenant="tenant-c"))
    for r in requests(cfg, 8, seed=5):
        b.submit(r)
    with jax.profiler.trace(log_dir):
        b.run()
    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    spans = Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events
        if dict(ev.stats).get("track") == "tenant-c")
    print(f"profiler sink: {b.stats.tokens} tokens; spans on the host "
          f"plane: " + ", ".join(f"{k} x{v}" for k, v in sorted(
              spans.items())))
    print(f"  profile in {log_dir} (TensorBoard profile plugin / Perfetto)")


def main() -> None:
    base = time.perf_counter()
    clock = lambda: time.perf_counter() - base  # noqa: E731 — shared origin
    tel = Telemetry(tracer=Tracer(clock=clock, max_events=3000))

    pool_chaos(tel)
    ex = serving(tel, clock)

    for tenant, rep in sorted(ex.slo_report().items()):
        print(f"  slo[{tenant}]: n={rep['requests']} "
              f"attainment={rep['attainment']:.2f} "
              f"p50={rep['p50_latency']:.3f}s p99={rep['p99_latency']:.3f}s")

    out_dir = os.path.join(os.path.dirname(__file__), "traces")
    os.makedirs(out_dir, exist_ok=True)
    trace = tel.tracer.export(
        os.path.join(out_dir, "tracing_serving.trace.json"))
    metrics = tel.registry.export(
        os.path.join(out_dir, "tracing_serving.metrics.json"))
    print(f"tracks: {', '.join(tel.tracer.tracks())}")
    print(f"wrote {trace} ({os.path.getsize(trace) // 1024} KiB, "
          f"{len(tel.tracer.events)} events, {tel.tracer.dropped} dropped) "
          f"and {metrics} — open the trace at https://ui.perfetto.dev")

    log_dir = os.path.join("experiments", "tracing_profile")
    profiled_serving(log_dir)


if __name__ == "__main__":
    main()
