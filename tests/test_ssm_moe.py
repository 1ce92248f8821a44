"""SSM (Mamba-2 SSD) and MoE layer numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels.ssd_scan.ref import ssd_naive
from repro.models import init_params
from repro.models.ssm import (
    causal_conv, causal_conv_step, ssd_chunked,
    ssm_decode, ssm_forward,
)

KEY = jax.random.PRNGKey(0)


class TestSSD:
    @pytest.mark.parametrize("chunk", [32, 64, 128])
    def test_chunked_equals_naive(self, chunk):
        ks = jax.random.split(KEY, 5)
        B, S, nh, hd, G, N = 2, 128, 4, 16, 2, 8
        x = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.1
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
        Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
        Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
        got = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        want = ssd_naive(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_causal_conv_step_matches_full(self):
        kx, kw = jax.random.split(KEY)
        B, S, C, K = 2, 16, 8, 4
        x = jax.random.normal(kx, (B, S, C))
        w = jax.random.normal(kw, (K, C)) * 0.3
        full = causal_conv(x, w)
        state = jnp.zeros((B, K - 1, C))
        outs = []
        for t in range(S):
            y, state = causal_conv_step(x[:, t], state, w)
            outs.append(y)
        got = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)

    def test_prefill_decode_continuity(self):
        """ssm_forward over S tokens then ssm_decode of token S+1 equals
        ssm_forward over S+1 tokens (last output)."""
        cfg = get_reduced("mamba2-370m")
        params = init_params(cfg, KEY)
        lp = jax.tree.map(lambda a: a[0], params["blocks"][0])["ssm"]
        B, S = 2, 33
        x = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32) * 0.1
        full, _ = ssm_forward(lp, x, cfg, return_state=True)
        y_pre, state = ssm_forward(lp, x[:, :-1], cfg, return_state=True)
        y_dec, _ = ssm_decode(lp, x[:, -1:], state, cfg)
        np.testing.assert_allclose(
            np.asarray(y_dec[:, 0], np.float32), np.asarray(full[:, -1], np.float32),
            rtol=5e-2, atol=5e-2,
        )


class TestMoE:
    def test_grouped_equals_dense_decode(self):
        """The capacity-bucketed (train) MoE path equals the serving path
        (the no-drop expert share, here holding every expert) when capacity
        is unbounded — same experts, same weights."""
        from repro.models.moe import moe_grouped, moe_share

        cfg = get_reduced("mixtral-8x22b")
        params = init_params(cfg, KEY)
        lp = jax.tree.map(lambda a: a[0], params["blocks"][0])["moe"]
        x = jax.random.normal(KEY, (2, 8, cfg.d_model), jnp.dtype(cfg.dtype)) * 0.3
        S, k = 8, cfg.moe.top_k
        y_train, aux = moe_grouped(lp, x, cfg, capacity=S * k)   # no drops
        y_dec, _ = moe_share(lp, x, cfg)
        np.testing.assert_allclose(
            np.asarray(y_train, np.float32), np.asarray(y_dec, np.float32),
            rtol=5e-2, atol=5e-2,
        )
        assert float(aux) > 0.5

    def test_capacity_drops_are_bounded(self):
        """With capacity_factor-bounded buckets, outputs differ from the
        unbounded path only on the dropped fraction of tokens."""
        from repro.models.moe import moe_grouped

        cfg = get_reduced("mixtral-8x22b")
        params = init_params(cfg, KEY)
        lp = jax.tree.map(lambda a: a[0], params["blocks"][0])["moe"]
        x = jax.random.normal(KEY, (1, 32, cfg.d_model), jnp.dtype(cfg.dtype)) * 0.3
        y_unbounded, _ = moe_grouped(lp, x, cfg, capacity=32 * cfg.moe.top_k)
        y_capped, _ = moe_grouped(lp, x, cfg, capacity=2)
        diff_tokens = (
            jnp.abs((y_unbounded - y_capped).astype(jnp.float32)).max(-1) > 1e-3
        ).sum()
        assert int(diff_tokens) < 32  # some tokens survive with exact output

    def test_shared_experts_always_active(self):
        """DeepSeek-style shared experts contribute even when the router
        sends everything elsewhere."""
        from repro.models.moe import moe_apply

        cfg = get_reduced("deepseek-moe-16b")
        assert cfg.moe.n_shared_experts > 0
        params = init_params(cfg, KEY)
        lp = jax.tree.map(lambda a: a[0], params["blocks"][0])["moe"]
        x = jax.random.normal(KEY, (1, 4, cfg.d_model), jnp.dtype(cfg.dtype)) * 0.3
        y, _ = moe_apply(lp, x, cfg)
        # zero the routed experts: output must still be nonzero (shared path)
        lp2 = dict(lp)
        lp2["wi"] = jnp.zeros_like(lp["wi"])
        lp2["wo"] = jnp.zeros_like(lp["wo"])
        y2, _ = moe_apply(lp2, x, cfg)
        assert float(jnp.abs(y2.astype(jnp.float32)).max()) > 0

    def test_load_balance_loss_uniform_router(self):
        """A perfectly uniform router hits the theoretical minimum (≈1)."""
        from repro.models.moe import load_balance_loss

        E, T, K = 8, 1024, 2
        probs = jnp.full((T, E), 1.0 / E)
        # round-robin top-k assignment: perfectly balanced
        top_i = (jnp.arange(T * K) % E).reshape(T, K)
        loss = load_balance_loss(probs, top_i, E)
        assert float(loss) == pytest.approx(1.0, rel=1e-3)
