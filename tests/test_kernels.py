"""Pallas TPU kernels, validated in interpret mode against pure-jnp oracles.

Each kernel sweeps shapes/dtypes; assert_allclose vs ref.py.  interpret=True
executes the kernel body on CPU with TPU grid semantics (sequential innermost
axis, VMEM scratch carried across grid steps)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


def tol(dtype):
    # f32: block-K accumulation order differs from the fused reference
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=5e-4, atol=5e-4)


class TestMatmul:
    @pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 384),
                                       (512, 256, 128), (64, 1024, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, m, k, n, dtype):
        from repro.kernels.matmul import ops, ref

        ka, kb = jax.random.split(KEY)
        a = jax.random.normal(ka, (m, k), dtype)
        b = jax.random.normal(kb, (k, n), dtype)
        got = ops.matmul(a, b, block_m=128, block_n=128, block_k=128, interpret=True)
        want = ref.matmul_ref(a, b)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), **tol(dtype)
        )


class TestFlashAttention:
    @pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 128)])
    @pytest.mark.parametrize("seq,heads,kv_heads", [(512, 4, 2), (1024, 8, 8), (384, 4, 1)])
    def test_matches_ref(self, causal, window, seq, heads, kv_heads):
        from repro.kernels.flash_attention import ops, ref

        kq, kk, kv = jax.random.split(KEY, 3)
        B, dh = 2, 64
        q = jax.random.normal(kq, (B, seq, heads, dh), jnp.float32)
        k = jax.random.normal(kk, (B, seq, kv_heads, dh), jnp.float32)
        v = jax.random.normal(kv, (B, seq, kv_heads, dh), jnp.float32)
        got = ops.flash_attention(q, k, v, causal=causal, window=window,
                                  block_q=128, block_k=128, interpret=True)
        want = jnp.swapaxes(
            ref.flash_attention_ref(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                causal=causal, window=window,
            ), 1, 2,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        from repro.kernels.flash_attention import ops, ref

        kq, kk, kv = jax.random.split(KEY, 3)
        q = jax.random.normal(kq, (1, 256, 2, 64), jnp.bfloat16)
        k = jax.random.normal(kk, (1, 256, 2, 64), jnp.bfloat16)
        v = jax.random.normal(kv, (1, 256, 2, 64), jnp.bfloat16)
        got = ops.flash_attention(q, k, v, causal=True, interpret=True)
        want = jnp.swapaxes(
            ref.flash_attention_ref(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            ), 1, 2,
        )
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=3e-2, atol=3e-2)


class TestRMSNorm:
    @pytest.mark.parametrize("shape", [(4, 128, 256), (2, 64, 1024), (1, 8, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, shape, dtype):
        from repro.kernels.rmsnorm import ops, ref

        kx, ks = jax.random.split(KEY)
        x = jax.random.normal(kx, shape, dtype)
        s = jax.random.normal(ks, (shape[-1],), dtype)
        got = ops.rmsnorm(x, s, interpret=True)
        want = ref.rmsnorm_ref(x, s)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **tol(dtype))


class TestDecodeAttention:
    @pytest.mark.parametrize("C,H,Hkv", [(128, 4, 2), (1024, 8, 1), (384, 8, 8)])
    @pytest.mark.parametrize("window", [None, 64])
    def test_matches_ref(self, C, H, Hkv, window):
        from repro.kernels.decode_attention import ops, ref

        kq, kk, kv = jax.random.split(KEY, 3)
        B, dh = 2, 64
        q = jax.random.normal(kq, (B, H, dh), jnp.float32)
        k = jax.random.normal(kk, (B, C, Hkv, dh), jnp.float32)
        v = jax.random.normal(kv, (B, C, Hkv, dh), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
        cur = jnp.full((B,), C // 2, jnp.int32)
        got = ops.decode_attention(q, k, v, pos, cur, window=window,
                                   block_c=128, interpret=True)
        want = ref.decode_attention_ref(q, k, v, pos, cur, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestSSDScan:
    @pytest.mark.parametrize("S,chunk", [(256, 64), (512, 128), (384, 128)])
    def test_matches_naive(self, S, chunk):
        from repro.kernels.ssd_scan import ops, ref

        ks = jax.random.split(KEY, 5)
        B, nh, hd, G, N = 2, 4, 32, 1, 16
        x = jax.random.normal(ks[0], (B, S, nh, hd), jnp.float32) * 0.1
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
        Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
        got = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
        want = ref.ssd_naive(x, dt, A, Bm, Cm)
        want = want[0] if isinstance(want, tuple) else want
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_final_state_matches_chunked_oracle(self):
        from repro.kernels.ssd_scan import ops
        from repro.models.ssm import ssd_chunked

        ks = jax.random.split(KEY, 5)
        B, S, nh, hd, G, N = 1, 256, 2, 16, 1, 8
        x = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.1
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
        Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
        got_y, got_st = ops.ssd(x, dt, A, Bm, Cm, chunk=64, return_state=True,
                                interpret=True)
        ref_y, ref_st = ssd_chunked(x, dt, A, Bm, Cm, chunk=64, return_state=True)
        np.testing.assert_allclose(np.asarray(got_st), np.asarray(ref_st),
                                   rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(ref_y),
                                   rtol=2e-3, atol=2e-3)


class TestPagedDecodeAttention:
    """In-kernel page-table walk vs the materialized-gather oracle.

    The trash page is poisoned with large finite values (1e4) so any
    unmapped-page or beyond-cur_pos leak shows up as a loud mismatch
    instead of averaging away (NaN would poison the oracle too)."""

    def _pools(self, key, P, ps, Hkv, dh):
        kk, kv = jax.random.split(key)
        kp = jax.random.normal(kk, (P + 1, ps, Hkv, dh), jnp.float32)
        vp = jax.random.normal(kv, (P + 1, ps, Hkv, dh), jnp.float32)
        # poisoned trash page: leaks are loud, not averaged away
        return kp.at[P].set(1e4), vp.at[P].set(1e4)

    @pytest.mark.parametrize("H,Hkv", [(4, 2), (8, 1), (8, 8)])
    def test_matches_ref(self, H, Hkv):
        from repro.kernels.paged_attention import ops, ref

        B, dh, P, ps, maxp = 3, 32, 10, 8, 4
        kq, kp_key = jax.random.split(KEY)
        q = jax.random.normal(kq, (B, H, dh), jnp.float32)
        kp, vp = self._pools(kp_key, P, ps, Hkv, dh)
        # rows: unmapped holes mid-table; cur_pos mid-page (partial last
        # page), at a page boundary - 1, and at full capacity
        table = jnp.asarray([[0, 3, -1, -1], [5, -1, 7, -1], [2, 4, 6, 8]],
                            jnp.int32)
        cur = jnp.asarray([9, 23, 31], jnp.int32)
        got = ops.paged_decode_attention(q, kp, vp, table, cur, interpret=True)
        want = ref.paged_decode_attention_ref(q, kp, vp, table, cur)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_page_boundary_sweep(self):
        """cur_pos crossing every position of a 2-page window: the fused
        `pos <= cur_pos` mask must flip exactly one key per step."""
        from repro.kernels.paged_attention import ops, ref

        B, H, Hkv, dh, P, ps = 1, 4, 2, 32, 4, 8
        kq, kp_key = jax.random.split(KEY)
        kp, vp = self._pools(kp_key, P, ps, Hkv, dh)
        table = jnp.asarray([[1, 2]], jnp.int32)
        for cur in range(2 * ps):
            q = jax.random.normal(jax.random.fold_in(kq, cur), (B, H, dh),
                                  jnp.float32)
            c = jnp.asarray([cur], jnp.int32)
            got = ops.paged_decode_attention(q, kp, vp, table, c,
                                             interpret=True)
            want = ref.paged_decode_attention_ref(q, kp, vp, table, c)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=f"cur={cur}")

    def test_fully_unmapped_slot_is_finite(self):
        """An inactive slot (all pages -1) must not produce NaN/inf — the
        batcher keeps dead slots decoding with frozen positions."""
        from repro.kernels.paged_attention import ops

        B, H, Hkv, dh, P, ps = 2, 4, 2, 32, 4, 8
        q = jax.random.normal(KEY, (B, H, dh), jnp.float32)
        kp, vp = self._pools(jax.random.fold_in(KEY, 1), P, ps, Hkv, dh)
        table = jnp.full((B, 2), -1, jnp.int32)
        cur = jnp.zeros((B,), jnp.int32)
        got = ops.paged_decode_attention(q, kp, vp, table, cur, interpret=True)
        assert np.isfinite(np.asarray(got)).all()

    @pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "mid", "last"])
    @pytest.mark.parametrize("W", [1, 4], ids=["decode", "verify"])
    def test_stacked_pool_matches_layer_pool(self, W, layer):
        """The stacked pool read at ``layer`` == the layer's own pool: the
        kernel walks (layer, page) itself, identical bits, including the
        unmapped pages that load the (poisoned) trash page."""
        from repro.kernels.paged_attention import ops

        n_layers, B, H, Hkv, dh, P, ps = 5, 3, 4, 2, 32, 10, 8
        kq, kp_key = jax.random.split(jax.random.fold_in(KEY, W))
        pools = [self._pools(jax.random.fold_in(kp_key, i), P, ps, Hkv, dh)
                 for i in range(n_layers)]
        kp = jnp.stack([k for k, _ in pools])
        vp = jnp.stack([v for _, v in pools])
        q = jax.random.normal(kq, (B, W, H, dh), jnp.float32)
        table = jnp.asarray([[0, 3, -1, -1], [5, -1, 7, -1], [2, 4, 6, 8]],
                            jnp.int32)
        cur = jnp.asarray([9, 23, 32 - W], jnp.int32)
        if W == 1:
            call = functools.partial(ops.paged_decode_attention, q[:, 0])
        else:
            call = functools.partial(ops.paged_verify_attention, q)
        got = call(kp, vp, table, cur, jnp.int32(layer), interpret=True)
        want = call(kp[layer], vp[layer], table, cur, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestPrefixAttention:
    """Two-phase (cached prefix, fresh suffix) kernel vs the concat oracle."""

    @pytest.mark.parametrize("H,Hkv", [(4, 2), (8, 1), (8, 8)])
    @pytest.mark.parametrize("Lp,Sq,qo", [(28, 4, 0), (10, 7, 3), (33, 9, 0)])
    def test_matches_ref(self, H, Hkv, Lp, Sq, qo):
        from repro.kernels.prefix_attention import ops, ref

        B, dh, Sk = 2, 32, Sq + qo
        kq, kp, kv, kk2, kv2 = jax.random.split(KEY, 5)
        q = jax.random.normal(kq, (B, Sq, H, dh), jnp.float32)
        pk = jax.random.normal(kp, (B, Lp, Hkv, dh), jnp.float32)
        pv = jax.random.normal(kv, (B, Lp, Hkv, dh), jnp.float32)
        k = jax.random.normal(kk2, (B, Sk, Hkv, dh), jnp.float32)
        v = jax.random.normal(kv2, (B, Sk, Hkv, dh), jnp.float32)
        got = ops.prefix_flash_attention(q, pk, pv, k, v, q_offset=qo,
                                         block_q=8, block_k=16, interpret=True)
        want = ref.prefix_flash_attention_ref(q, pk, pv, k, v, q_offset=qo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_multi_block_both_phases(self):
        """Prefix and suffix each span several k blocks; q spans several
        q blocks — exercises the clamped index maps on both operands."""
        from repro.kernels.prefix_attention import ops, ref

        B, H, Hkv, dh, Lp, Sq = 1, 4, 2, 32, 21, 18
        kq, kp, kv, kk2, kv2 = jax.random.split(KEY, 5)
        q = jax.random.normal(kq, (B, Sq, H, dh), jnp.float32)
        pk = jax.random.normal(kp, (B, Lp, Hkv, dh), jnp.float32)
        pv = jax.random.normal(kv, (B, Lp, Hkv, dh), jnp.float32)
        k = jax.random.normal(kk2, (B, Sq, Hkv, dh), jnp.float32)
        v = jax.random.normal(kv2, (B, Sq, Hkv, dh), jnp.float32)
        got = ops.prefix_flash_attention(q, pk, pv, k, v, block_q=4,
                                         block_k=4, interpret=True)
        want = ref.prefix_flash_attention_ref(q, pk, pv, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_matches_self_attention_xla_path(self):
        """Kernel == the model's concat XLA path on bf16-cast prefix pages
        (the dtype round-trip cached admission actually performs)."""
        from repro.kernels.prefix_attention import ops
        from repro.models.attention import chunked_flash_attention

        B, H, Hkv, dh, Lp, Sq = 2, 4, 2, 32, 16, 8
        kq, kp, kv, kk2, kv2 = jax.random.split(KEY, 5)
        q = jax.random.normal(kq, (B, Sq, H, dh), jnp.float32)
        pk = jax.random.normal(kp, (B, Lp, Hkv, dh), jnp.float32)
        pv = jax.random.normal(kv, (B, Lp, Hkv, dh), jnp.float32)
        k = jax.random.normal(kk2, (B, Sq, Hkv, dh), jnp.float32)
        v = jax.random.normal(kv2, (B, Sq, Hkv, dh), jnp.float32)
        got = ops.prefix_flash_attention(q, pk, pv, k, v, interpret=True)
        want = chunked_flash_attention(
            q, jnp.concatenate([pk, k], axis=1),
            jnp.concatenate([pv, v], axis=1), causal=True, q_offset=Lp)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
