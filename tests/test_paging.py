"""Paged KV pool: token identity vs the dense path, device page-table /
free-list invariants, quota enforcement under over-subscription, and
mid-run migration of the paged state."""

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import init_params
from repro.serving.batcher import ContinuousBatcher, Request
from repro.serving.kv_cache import PagedKVPool, PageQuotaError, pages_for

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_reduced("qwen3-0.6b")
    return cfg, init_params(cfg, KEY)


def _prompts(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=1 + i % 6).astype(np.int32)
            for i in range(n)]


def _run(params, cfg, prompts, *, eos_map=None, max_new=10, chunk=8,
         steps=None, **kw):
    """Serve ``prompts`` to the end, or for ``steps`` rounds only."""
    b = ContinuousBatcher(params, cfg, slots=4, prompt_len=8, max_len=64,
                          chunk=chunk, **kw)
    reqs = [Request(rid=i, prompt=p, max_new=max_new + i % 4,
                    eos=(eos_map or {}).get(i))
            for i, p in enumerate(prompts)]
    for r in reqs:
        b.submit(r)
    if steps is None:
        b.run(max_steps=4000)
    for _ in range(steps or 0):
        b.step()
    return b, reqs


def _assert_pool_invariants(b):
    """No page mapped twice, and mapped + free partitions the pool."""
    tab = np.asarray(b.pages.table)
    free = np.asarray(b.pages.free)
    top = int(b.pages.free_top)
    mapped = tab[tab >= 0].tolist()
    assert len(mapped) == len(set(mapped)), "page mapped to two slots"
    assert sorted(set(mapped) | set(free[:top].tolist())) == \
        list(range(b.n_pages)), "free-list conservation violated"
    # host ledger never exceeds the lease cap
    assert b.kv_pool.used <= b._page_limit
    b.kv_pool.check()


class TestPagedIdentity:
    """Paging must be a pure memory-layout change: request token streams
    identical to the dense ring-buffer path (which test_serving pins to the
    per-step reference, so identity is transitive)."""

    def test_paged_matches_dense(self, qwen):
        cfg, params = qwen
        prompts = _prompts(cfg, 8)
        _, dense = _run(params, cfg, prompts)
        bp, paged = _run(params, cfg, prompts, paged=True, page_size=8)
        for a, g in zip(dense, paged):
            assert a.done and g.done
            assert a.out == g.out, (a.rid, a.out, g.out)
        _assert_pool_invariants(bp)

    def test_page_boundary_crossing(self, qwen):
        """page_size=4 forces several boundary crossings (prompt bucket is 8
        = 2 pages, decode crosses into pages 2..5); streams stay identical
        and slots really span multiple pages."""
        cfg, params = qwen
        prompts = _prompts(cfg, 6, seed=5)
        _, dense = _run(params, cfg, prompts, max_new=14)
        bp, paged = _run(params, cfg, prompts, max_new=14, paged=True,
                         page_size=4)
        for a, g in zip(dense, paged):
            assert a.out == g.out, (a.rid, a.out, g.out)
        assert bp.stats.peak_pages_in_use > pages_for(8, 4), \
            "decode never faulted past the prompt pages"
        _assert_pool_invariants(bp)

    def test_eos_mid_chunk(self, qwen):
        """A request whose EOS lands mid-chunk finishes at the same token
        under paging, and its pages return to the free list."""
        cfg, params = qwen
        prompts = _prompts(cfg, 6, seed=7)
        _, probe = _run(params, cfg, prompts)
        eos_map = {0: probe[0].out[3]}
        _, dense = _run(params, cfg, prompts, eos_map=eos_map)
        bp, paged = _run(params, cfg, prompts, eos_map=eos_map, paged=True,
                         page_size=8)
        for a, g in zip(dense, paged):
            assert a.done and g.done
            assert a.out == g.out, (a.rid, a.out, g.out)
        assert paged[0].out[-1] == eos_map[0]
        assert len(paged[0].out) < 10
        # everything completed: every page is back on the free stack
        assert int(bp.pages.free_top) == bp.n_pages
        _assert_pool_invariants(bp)

    def test_chunk_one_matches_chunk_eight(self, qwen):
        """chunk==per-step identity *under paging*: the fused paged scan
        emits the same streams as single-step paged chunks."""
        cfg, params = qwen
        prompts = _prompts(cfg, 6, seed=11)
        _, one = _run(params, cfg, prompts, chunk=1, paged=True, page_size=8)
        _, eight = _run(params, cfg, prompts, chunk=8, paged=True,
                        page_size=8)
        for a, g in zip(one, eight):
            assert a.out == g.out, (a.rid, a.out, g.out)


class TestPoolInvariants:
    def test_conservation_across_churn(self, qwen):
        """Admit/complete cycles over an over-subscribed pool (with
        reservations) keep the table/free-list partition exact."""
        cfg, params = qwen
        prompts = _prompts(cfg, 10, seed=13)
        b, reqs = _run(params, cfg, prompts, paged=True, page_size=8,
                       n_pages=6)
        assert all(r.done for r in reqs)
        assert b.stats.peak_pages_in_use <= 6
        _assert_pool_invariants(b)

    def test_quota_enforced_on_oversubscription(self, qwen):
        """A kv_pages lease below the pool caps device allocation; denied
        faults requeue (oom_requeues) and everything still completes."""
        cfg, params = qwen
        prompts = _prompts(cfg, 8, seed=17)
        b, reqs = _run(params, cfg, prompts, paged=True, page_size=8,
                       n_pages=16, page_quota=5, reserve_pages=False)
        assert all(r.done for r in reqs)
        assert b.stats.peak_pages_in_use <= 5, \
            "device allocation exceeded the kv_pages quota"
        assert b.stats.oom_requeues > 0, \
            "over-subscription never exercised the denial path"
        _assert_pool_invariants(b)

    def test_page_limit_resize_cycle(self, qwen):
        """Shrinking the page lease mid-run throttles allocation (drain, no
        revocation); growing it back restores throughput.  Conservation
        holds at every sync."""
        cfg, params = qwen
        prompts = _prompts(cfg, 8, seed=19)
        b = ContinuousBatcher(params, cfg, slots=4, prompt_len=8, max_len=64,
                              chunk=4, paged=True, page_size=8, n_pages=16,
                              reserve_pages=False)
        reqs = [Request(rid=i, prompt=p, max_new=10) for i, p in
                enumerate(prompts)]
        for r in reqs:
            b.submit(r)
        b.step()
        b.set_page_limit(4)                      # hypervisor shrank the lease
        for _ in range(4):
            b.step()
            _assert_pool_invariants(b)
        assert int(b.pages.quota) == 4
        b.set_page_limit(16)                     # lease grew back
        b.run(max_steps=4000)
        assert all(r.done for r in reqs)
        _assert_pool_invariants(b)

    def test_admit_only_rounds_do_not_starve_admission(self, qwen):
        """Requests that finish at admission (max_new=1) pop no device
        pages; the host's since-sync estimate must not leak and starve an
        entirely free pool (regression: over-subscribed admission counter
        only reset after a decode chunk)."""
        cfg, params = qwen
        b = ContinuousBatcher(params, cfg, slots=4, prompt_len=8, max_len=64,
                              chunk=4, paged=True, page_size=8, n_pages=4,
                              reserve_pages=False)
        rng = np.random.default_rng(31)
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, cfg.vocab,
                                            size=1 + i % 6).astype(np.int32),
                        max_new=1)
                for i in range(12)]
        for r in reqs:
            b.submit(r)
        b.run(max_steps=2000)
        assert all(r.done for r in reqs), [r.done for r in reqs]
        assert b._admitted_pages_since_sync == 0
        _assert_pool_invariants(b)

    def test_submit_rejects_impossible_footprint(self, qwen):
        cfg, params = qwen
        b = ContinuousBatcher(params, cfg, slots=2, prompt_len=8, max_len=64,
                              chunk=4, paged=True, page_size=8, n_pages=2)
        with pytest.raises(AssertionError):
            b.submit(Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                             max_new=40))


class TestLedger:
    """Host-side PagedKVPool: counts, quotas, conservation errors."""

    def test_alloc_free_quota(self):
        pool = PagedKVPool(10, 16)
        pool.set_quota("a", 6)
        assert pool.alloc("a", 4) == 4
        assert pool.alloc("b", 5) == 5
        assert pool.available == 1
        with pytest.raises(PageQuotaError):
            pool.alloc("a", 3)                   # quota (4+3 > 6)
        with pytest.raises(PageQuotaError):
            pool.alloc("b", 2)                   # pool (9+2 > 10)
        assert pool.free("a", 2) == 2
        assert pool.held_by("a") == 2
        assert pool.free("b") == 5               # free-all
        assert pool.available == 8
        pool.check()

    def test_oversubscribed_quotas_are_legal(self):
        """Quota sum may exceed the pool (that IS over-subscription); only
        actual reservations are bounded."""
        pool = PagedKVPool(10, 16)
        pool.set_quota("a", 8)
        pool.set_quota("b", 8)
        pool.alloc("a", 6)
        with pytest.raises(PageQuotaError):
            pool.alloc("b", 5)
        pool.alloc("b", 4)
        pool.check()


class TestMigration:
    def test_resize_between_chunks_migrates_paged_state(self, qwen):
        """A hypervisor resize between chunks migrates caches AND page
        tables/free list; paged decode resumes token-identically."""
        from repro.core import TenantSpec
        from repro.serving.tenancy import (
            VirtualAcceleratorPool, make_serving_hypervisor,
        )
        import jax.numpy as jnp

        cfg, params = qwen
        prompts = _prompts(cfg, 3, seed=23)

        def reqs():
            return [Request(rid=i, prompt=p, max_new=9)
                    for i, p in enumerate(prompts)]

        def batcher():
            return ContinuousBatcher(params, cfg, slots=4, prompt_len=8,
                                     max_len=64, chunk=4, paged=True,
                                     page_size=8)

        ref = batcher()
        ref_reqs = reqs()
        for r in ref_reqs:
            ref.submit(r)
        ref.run(max_steps=2000)

        pool = VirtualAcceleratorPool(devices=jax.devices() * 4,
                                      devices_per_core=1)
        hv, ex = make_serving_hypervisor(pool, policy="no_realloc")

        def mesh_builder(n):
            import jax.sharding as jsh
            devs = np.array(jax.devices() * n, dtype=object)[:n].reshape(n, 1)
            return jsh.Mesh(devs, ("data", "model"))

        ex.compiler.static_compile(
            "decode", lambda x: x, (jax.ShapeDtypeStruct((4,), jnp.float32),),
            lease_sizes=[1, 2], mesh_builder=mesh_builder)
        assert hv.admit(TenantSpec("t", 1, artifact="decode"))

        b = batcher()
        ex.register_state("t", b.live_state, on_migrate=b.adopt_state)
        got_reqs = reqs()
        for r in got_reqs:
            b.submit(r)
        b.step()
        hv.resize_request("t", 2)
        assert ex.reconfig_log and "t_migrate" in ex.reconfig_log[-1]
        b.run(max_steps=2000)
        for a, g in zip(ref_reqs, got_reqs):
            assert a.out == g.out
        _assert_pool_invariants(b)

    def test_kv_lease_drives_batcher_page_limit(self, qwen):
        """Full loop: hypervisor kv_pages grant -> ServingExecutor
        exec_kv_resize -> ContinuousBatcher.set_page_limit; shrink lands on
        the device quota and a second tenant's admission re-splits pages."""
        from repro.core import TenantSpec
        from repro.serving.tenancy import (
            VirtualAcceleratorPool, make_serving_hypervisor,
        )

        cfg, params = qwen
        pool = VirtualAcceleratorPool(devices=jax.devices() * 4,
                                      devices_per_core=1, kv_pages=16)
        hv, ex = make_serving_hypervisor(pool, policy="even_split")
        b = ContinuousBatcher(params, cfg, slots=4, prompt_len=8, max_len=64,
                              chunk=4, paged=True, page_size=8, n_pages=16)
        assert hv.admit(TenantSpec("t", 2, requested_kv_pages=16,
                                   min_kv_pages=2))
        ex.register_kv_limit("t", b.set_page_limit)
        assert hv.kv_allocation() == {"t": 16}
        # second tenant arrives: the even split halves t's page lease and the
        # executor pushes the new cap into the live batcher
        assert hv.admit(TenantSpec("u", 2, requested_kv_pages=16,
                                   min_kv_pages=2))
        assert sum(hv.kv_allocation().values()) <= 16
        assert b._page_limit == hv.kv_allocation()["t"]
        assert int(b.pages.quota) == b._page_limit
        prompts = _prompts(cfg, 6, seed=29)
        reqs = [Request(rid=i, prompt=p, max_new=8)
                for i, p in enumerate(prompts)]
        for r in reqs:
            b.submit(r)
        b.run(max_steps=2000)
        assert all(r.done for r in reqs)
        assert b.stats.peak_pages_in_use <= hv.kv_allocation()["t"]
        _assert_pool_invariants(b)


@pytest.fixture(scope="module")
def qwen_f32():
    """f32 variant: Pallas-vs-XLA token identity needs both paths to see
    numerically equal inputs (bf16 would make argmax ties dtype-lottery)."""
    import dataclasses
    cfg = dataclasses.replace(get_reduced("qwen3-0.6b"), dtype="float32",
                              name="qwen3-0.6b-f32")
    return cfg, init_params(cfg, KEY)


class TestPallasPagedServing:
    """attn_impl="pallas" on the paged path: the in-kernel page-table walk
    (repro.kernels.paged_attention) must emit the same token streams as the
    materialized-gather XLA oracle, end to end through the batcher."""

    def test_pallas_matches_xla_tokens(self, qwen_f32):
        cfg, params = qwen_f32
        prompts = _prompts(cfg, 8)
        _, xla = _run(params, cfg, prompts, paged=True, page_size=8)
        bp, pal = _run(params, cfg, prompts, paged=True, page_size=8,
                       attn_impl="pallas")
        for a, g in zip(xla, pal):
            assert a.done and g.done
            assert a.out == g.out, (a.rid, a.out, g.out)
        _assert_pool_invariants(bp)

    def test_pallas_page_boundary_crossing(self, qwen_f32):
        """page_size=4 forces in-kernel walks over several boundary
        crossings and unmapped tail pages; streams stay identical."""
        cfg, params = qwen_f32
        prompts = _prompts(cfg, 6, seed=5)
        _, xla = _run(params, cfg, prompts, max_new=14, paged=True,
                      page_size=4)
        _, pal = _run(params, cfg, prompts, max_new=14, paged=True,
                      page_size=4, attn_impl="pallas")
        for a, g in zip(xla, pal):
            assert a.out == g.out, (a.rid, a.out, g.out)

    @pytest.mark.parametrize("speculative", [False, True],
                             ids=["decode", "spec"])
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_pool_holds_written_positions(self, qwen_f32, impl, speculative):
        """The paged decode chunk and the paged speculative chunk, on the
        kernel and on the XLA oracle, write the stacked pool in place:
        mid-run, every position a live slot has written holds, page by page
        through its table and in every layer, the K/V a prefill of the
        slot's committed tokens computes; run on, the streams equal the
        dense greedy ones."""
        from repro.models import prefill

        cfg, params = qwen_f32
        prompts = _prompts(cfg, 4, seed=13)
        _, dense = _run(params, cfg, prompts, max_new=24, chunk=4)
        expected = {r.rid: r.out for r in dense}
        b, reqs = _run(params, cfg, prompts, max_new=24, chunk=4,
                       paged=True, page_size=4, attn_impl=impl,
                       speculative=speculative, steps=2)
        cur = np.asarray(b.state.cur_pos)
        table = np.asarray(b.pages.table)
        live = [(i, r) for i, r in enumerate(b.slot_req) if r is not None]
        assert live and all(cur[i] > 8 for i, _ in live), cur
        rows = np.zeros((len(live), 64), np.int32)          # max_len
        for n, (i, r) in enumerate(live):
            seq = np.concatenate([np.zeros(8 - len(r.prompt), np.int32),
                                  r.prompt, expected[r.rid]])
            rows[n, :cur[i]] = seq[:cur[i]]
        _, ref = prefill(params, jax.numpy.asarray(rows), cfg, max_len=64)
        pool = b.caches.kv["0"]
        for n, (i, _) in enumerate(live):
            pos = np.arange(cur[i])
            for got, want in ((pool.k, ref.kv["0"].k), (pool.v, ref.kv["0"].v)):
                np.testing.assert_allclose(
                    np.asarray(got[:, table[i, pos // 4], pos % 4]),
                    np.asarray(want[:, n, :cur[i]]), rtol=1e-4, atol=1e-4)
        b.run(max_steps=4000)
        assert {r.rid: r.out for r in reqs} == expected


class TestAttnCapabilities:
    """Bad impl × mode combinations fail at construction time with a
    ValueError from the shared capability table — not three layers deep
    inside a jit trace."""

    def test_paged_rejects_naive_at_construction(self, qwen):
        cfg, params = qwen
        with pytest.raises(ValueError, match="paged"):
            ContinuousBatcher(params, cfg, slots=2, prompt_len=8, max_len=32,
                              paged=True, page_size=8, attn_impl="naive")

    def test_paged_rejects_flash_at_construction(self, qwen):
        # "flash" (the train-only custom-VJP path) already fails the dense
        # check inside ServeConfig, before the batcher's paged check
        cfg, params = qwen
        with pytest.raises(ValueError, match="not supported"):
            ContinuousBatcher(params, cfg, slots=2, prompt_len=8, max_len=32,
                              paged=True, page_size=8, attn_impl="flash")

    def test_serve_config_rejects_unknown_impl(self):
        from repro.serving.engine import ServeConfig
        with pytest.raises(ValueError, match="attn_impl"):
            ServeConfig(max_len=32, attn_impl="cuda")

    def test_table_covers_every_mode(self):
        from repro.models.attention import ATTN_CAPABILITIES, check_attn_impl
        for mode, impls in ATTN_CAPABILITIES.items():
            for impl in impls:
                assert check_attn_impl(impl, mode) == impl
        with pytest.raises(ValueError, match="unknown attention mode"):
            check_attn_impl("xla", "teleport")
