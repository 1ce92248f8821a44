"""Tensor-sharded serving: token identity, live re-meshing, registry keys.

The shard_map programs need more than one device, so every test that
actually executes a sharded batcher runs in a subprocess with
``--xla_force_host_platform_device_count=8`` set *before* jax imports
(same pattern as ``test_multidevice.py``) — the flag must never leak into
this single-device session.  Registry key semantics are unit-tested
in-process against fabricated meshes: ``ProgramRegistry.mesh_key`` only
reads ``axis_names`` / device shape / device ids.
"""

import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest


def _run_subprocess(script: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"      # 8 emulated host devices, never a chip
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
        timeout=900,
    )
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-4000:])
    return p.stdout


PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax

    from repro.configs import get_reduced
    from repro.models import init_params
    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher, Request

    assert jax.device_count() == 8

    cfg = dataclasses.replace(get_reduced("qwen3-0.6b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))

    def reqs(c, n=6, max_new=12, prefix=0):
        rng = np.random.default_rng(0)
        shared = rng.integers(1, c.vocab, size=prefix).astype(np.int32)
        tail_max = 8 - prefix          # prompts must fit prompt_len=8
        out = []
        for i in range(n):
            tail = rng.integers(1, c.vocab,
                                size=1 + i % tail_max).astype(np.int32)
            out.append(Request(rid=i,
                               prompt=np.concatenate([shared, tail]),
                               max_new=max_new))
        return out

    def sc(tp, paged=False, spec=False, prefix=False, chunk=4):
        return ServingConfig(slots=3, prompt_len=8, max_len=36, chunk=chunk,
                             tp=tp, paged=paged, page_size=4,
                             n_pages=64 if paged else None,
                             prefix_cache=prefix or None,
                             speculative=spec, draft_window=4)

    def run_batcher(p, c, scfg, rs=None, **req_kw):
        b = ContinuousBatcher(p, c, scfg)
        rs = rs if rs is not None else reqs(c, **req_kw)
        for r in rs:
            b.submit(r)
        b.run(max_steps=500)
        return b, [list(map(int, r.out)) for r in rs]
""")


SCRIPT_TP2_IDENTITY = PRELUDE + textwrap.dedent("""
    # -- tp=2 == tp=1, all four serving modes ---------------------------
    for paged, spec, prefix in ((False, False, False), (True, False, False),
                                (True, False, True), (False, True, False)):
        kw = {"prefix": 4} if prefix else {}
        b1, ref = run_batcher(params, cfg, sc(1, paged, spec, prefix), **kw)
        b2, got = run_batcher(params, cfg, sc(2, paged, spec, prefix), **kw)
        assert got == ref, (paged, spec, prefix, got, ref)
        # sharding must not change the dispatch discipline: same number of
        # device dispatches and host syncs as the single-device run
        assert b2.stats.dispatches == b1.stats.dispatches
        assert b2.stats.host_syncs == b1.stats.host_syncs
        assert b2.stats.host_syncs <= b2.stats.dispatches
        print(f"IDENTITY paged={paged} spec={spec} prefix={prefix}")

    # -- tp=2 == the plain-jit generate() oracle ------------------------
    from repro.serving.engine import generate
    rng = np.random.default_rng(1)
    prompts = rng.integers(1, cfg.vocab, size=(3, 8)).astype(np.int32)
    oracle = np.asarray(generate(params, cfg, prompts, n_new=10))
    rs = [Request(rid=i, prompt=prompts[i], max_new=10) for i in range(3)]
    _, got = run_batcher(params, cfg, sc(2), rs=rs)
    assert got == [list(map(int, row)) for row in oracle], (got, oracle)
    print("ORACLE-OK")

    # -- a second same-shape tp=2 batcher hits the program registry -----
    from repro.serving.engine import PROGRAMS
    n_before, hits_before = len(PROGRAMS), dict(PROGRAMS.hits)
    run_batcher(params, cfg, sc(2))
    assert len(PROGRAMS) == n_before, "same mesh+shape must not rebuild"
    assert any(PROGRAMS.hits[k] > hits_before.get(k, 0)
               for k in PROGRAMS.hits), "re-keying onto an existing mesh must hit"
    print("SHARDED-IDENTITY-OK")
""")


SCRIPT_TP4_AND_REGISTRY = PRELUDE + textwrap.dedent("""
    # tp=4 divides n_kv_heads only at 4 kv heads on the reduced config
    cfg4 = dataclasses.replace(cfg, n_kv_heads=4)
    params4 = init_params(cfg4, jax.random.PRNGKey(0))

    for paged in (False, True):
        _, ref = run_batcher(params4, cfg4, sc(1, paged))
        _, got = run_batcher(params4, cfg4, sc(4, paged))
        assert got == ref, (paged, got, ref)
        print(f"TP4 paged={paged} identical")

    # -- two live batchers at different TP widths never collide ---------
    from repro.serving.engine import PROGRAMS
    PROGRAMS.clear()

    def drive(b):
        rs = reqs(cfg4)
        for r in rs:
            b.submit(r)
        b.run(max_steps=500)

    b2 = ContinuousBatcher(params4, cfg4, sc(2))
    drive(b2)
    keys2 = set(PROGRAMS._cache)
    b4 = ContinuousBatcher(params4, cfg4, sc(4))
    drive(b4)
    keys4 = set(PROGRAMS._cache) - keys2
    assert keys4, "the wider batcher must register its own programs"
    # every key carries its mesh fingerprint; widths differ
    width2 = {k[-1][1] for k in keys2 if k[-1] is not None}
    width4 = {k[-1][1] for k in keys4 if k[-1] is not None}
    assert width2 == {(2,)} and width4 == {(4,)}, (width2, width4)

    # hit counters stay per-key: b4's traffic never credits b2's programs
    hits2_before = {k: PROGRAMS.hits[k] for k in keys2}
    drive(b4)
    assert {k: PROGRAMS.hits[k] for k in keys2} == hits2_before
    # ... and b2's own traffic does credit b2's keys
    drive(b2)
    assert any(PROGRAMS.hits[k] > hits2_before[k] for k in keys2)
    print("SHARDED-REGISTRY-OK")
""")


SCRIPT_REMESH = PRELUDE + textwrap.dedent("""
    # -- live 1 -> 2 -> 1 re-mesh mid-stream, token-identical -----------
    for paged, spec in ((False, False), (True, False), (True, True)):
        _, ref = run_batcher(params, cfg, sc(1, paged, spec), max_new=20)
        b = ContinuousBatcher(params, cfg, sc(1, paged, spec))
        rs = reqs(cfg, max_new=20)
        for r in rs:
            b.submit(r)
        b.step(); b.step()
        b.remesh(2)
        b.step(); b.step()
        b.remesh(1)
        b.run(max_steps=500)
        got = [list(map(int, r.out)) for r in rs]
        assert got == ref, (paged, spec, got, ref)
        assert b.stats.remeshes == 2
        print(f"REMESH paged={paged} spec={spec} identical")

    # speculative: the n-gram draft state survives the re-mesh (the drafter
    # keeps accepting after migration — acceptance rate stays > 0)
    b = ContinuousBatcher(params, cfg, sc(1, spec=True))
    rs = reqs(cfg, max_new=24)
    for r in rs:
        b.submit(r)
    b.step(); b.step()
    b.remesh(2)
    before = b.stats.accepted_tokens
    b.run(max_steps=500)
    assert b.stats.accepted_tokens > before, \
        "drafter stopped accepting after the re-mesh"
    print("DRAFT-SURVIVES-OK")

    # -- EOS landing mid-chunk across a re-mesh -------------------------
    _, probe = run_batcher(params, cfg, sc(1), max_new=20)
    eos0 = probe[0][5]                       # fires inside a chunk, not at
    def eos_reqs():                          # an admission boundary
        rs = reqs(cfg, max_new=20)
        rs[0] = Request(rid=0, prompt=rs[0].prompt, max_new=20, eos=eos0)
        return rs
    _, ref = run_batcher(params, cfg, sc(1), rs=eos_reqs())
    assert len(ref[0]) < 20 and ref[0][-1] == eos0
    b = ContinuousBatcher(params, cfg, sc(1))
    rs = eos_reqs()
    for r in rs:
        b.submit(r)
    b.step()
    b.remesh(2)
    b.run(max_steps=500)
    got = [list(map(int, r.out)) for r in rs]
    assert got == ref, (got, ref)
    print("EOS-MID-CHUNK-OK")

    # -- hypervisor-driven: exec_resize re-meshes the live batcher ------
    from repro.core.hypervisor import TenantSpec
    from repro.serving.tenancy import ServingExecutor, VirtualAcceleratorPool
    _, ref = run_batcher(params, cfg, sc(1), max_new=20)
    vpool = VirtualAcceleratorPool(devices=jax.devices(), devices_per_core=1)
    ex = ServingExecutor(vpool)
    ex.exec_admit(TenantSpec(name="t", requested_cores=1, artifact=None),
                  1, at=0.0)
    b = ContinuousBatcher(params, cfg, sc(1))
    ex.register_remesh("t", lambda mesh: b.remesh(mesh=mesh))
    rs = reqs(cfg, max_new=20)
    for r in rs:
        b.submit(r)
    b.step(); b.step()
    ex.exec_resize("t", 2, at=1.0, mode=None)
    assert b.tp == 2
    b.step(); b.step()
    ex.exec_resize("t", 1, at=2.0, mode=None)
    assert b.tp == 1 and b.stats.remeshes == 2
    b.run(max_steps=500)
    got = [list(map(int, r.out)) for r in rs]
    assert got == ref, (got, ref)
    assert any("t_remesh" in e for e in ex.reconfig_log)
    print("SHARDED-REMESH-OK")
""")


@pytest.mark.slow
def test_tp2_token_identity_all_modes_and_oracle():
    """tp=2 through the batcher is token-identical to tp=1 and to the
    plain-jit ``generate`` oracle, for dense / paged / prefix-cached /
    speculative serving, with the same dispatch + host-sync counts; a
    second same-shape batcher reuses the compiled sharded programs."""
    out = _run_subprocess(SCRIPT_TP2_IDENTITY)
    assert "ORACLE-OK" in out
    assert "SHARDED-IDENTITY-OK" in out


@pytest.mark.slow
def test_tp4_identity_and_registry_width_isolation():
    """tp=4 decode is token-identical, and two live batchers at different
    TP widths keep disjoint registry keys with per-key hit counters."""
    out = _run_subprocess(SCRIPT_TP4_AND_REGISTRY)
    assert "SHARDED-REGISTRY-OK" in out


@pytest.mark.slow
def test_live_remesh_token_identity():
    """Re-meshing a live batcher 1 -> 2 -> 1 mid-stream (donated caches
    resharded via live_state/adopt_state) never changes a single token —
    dense, paged, speculative (draft state survives), EOS mid-chunk, and
    the hypervisor-driven ``exec_resize`` path."""
    out = _run_subprocess(SCRIPT_REMESH)
    assert "DRAFT-SURVIVES-OK" in out
    assert "EOS-MID-CHUNK-OK" in out
    assert "SHARDED-REMESH-OK" in out


# ---------------------------------------------------------------------------
# registry key semantics: in-process, no devices needed
# ---------------------------------------------------------------------------

def _fake_mesh(ids, axis="tp"):
    devs = np.array([SimpleNamespace(id=i) for i in ids], dtype=object)
    return SimpleNamespace(axis_names=(axis,), devices=devs)


class TestMeshKeyedRegistry:
    def test_mesh_fingerprint_separates_widths_and_device_sets(self):
        from repro.serving.engine import ProgramRegistry

        base = ("chunk", None, None, (4,), 0)
        k_none = ProgramRegistry.make_key(*base, mesh=None)
        k2 = ProgramRegistry.make_key(*base, mesh=_fake_mesh([0, 1]))
        k4 = ProgramRegistry.make_key(*base, mesh=_fake_mesh([0, 1, 2, 3]))
        k2b = ProgramRegistry.make_key(*base, mesh=_fake_mesh([2, 3]))
        assert len({k_none, k2, k4, k2b}) == 4, \
            "width or device-set change must change the key"
        # identical mesh -> identical key (a re-mesh back must cache-hit)
        assert k2 == ProgramRegistry.make_key(*base, mesh=_fake_mesh([0, 1]))

    def test_hits_are_per_key_and_dropped_on_eviction(self):
        from repro.serving.engine import ProgramRegistry

        reg = ProgramRegistry(maxsize=2)
        ka = ("a",)
        kb = ("b",)
        reg.get_raw(ka, None, lambda: "A")
        reg.get_raw(kb, None, lambda: "B")
        assert reg.hits == {ka: 0, kb: 0}
        assert reg.get_raw(ka, None, lambda: "never") == "A"
        assert reg.hits[ka] == 1 and reg.hits[kb] == 0
        # third key evicts the LRU entry (kb) along with its counter
        reg.get_raw(("c",), None, lambda: "C")
        assert kb not in reg.hits and ka in reg.hits
        reg.clear()
        assert reg.hits == {}
