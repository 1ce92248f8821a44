"""Multi-device semantics tests, run in a subprocess so the 8-device
XLA_FLAGS never leaks into this (single-device) test session."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_reduced
    from repro.distributed.sharding import cache_specs, make_policy
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, make_prefill_step, make_serve_step

    from repro.launch.mesh import make_auto_mesh

    cfg = get_reduced("qwen3-0.6b")              # kv heads = 2 < model axis 4
    mesh = make_auto_mesh((2, 4), ("data", "model"))
    B, S = 4, 32
    policy = make_policy(cfg, mesh, batch=B)
    assert policy.kv_len_sharded, "cache length must be model-sharded here"
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = (jnp.arange(B * 8, dtype=jnp.int32).reshape(B, 8) * 3 + 1) % cfg.vocab

    scfg = ServeConfig(max_len=S)
    pre = jax.jit(make_prefill_step(cfg, scfg))
    step_ref = jax.jit(make_serve_step(cfg, scfg))
    logits0, caches = pre(params, {"tokens": toks})
    t0 = jnp.argmax(logits0[..., : cfg.vocab], -1).astype(jnp.int32)
    cur = jnp.full((B,), 8, jnp.int32)
    ref_next, ref_logits, ref_caches = step_ref(params, t0, caches, cur,
                                                jax.random.PRNGKey(1))

    with mesh:
        c_specs = cache_specs(cfg, mesh, batch=B)
        c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs,
                            is_leaf=lambda x: isinstance(x, P))
        caches_sh = jax.device_put(caches, c_sh)
        step_sh = jax.jit(make_serve_step(cfg, scfg, policy=policy))
        got_next, got_logits, caches2 = step_sh(params, t0, caches_sh, cur,
                                                jax.random.PRNGKey(1))
        # second step exercises the shard-local ring-buffer write
        got2, gl2, _ = step_sh(params, got_next, caches2, cur + 1,
                               jax.random.PRNGKey(2))
    ref2, rl2, _ = step_ref(params, ref_next, ref_caches, cur + 1,
                            jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(got_next), np.asarray(ref_next))
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(ref2))
    np.testing.assert_allclose(np.asarray(gl2, np.float32),
                               np.asarray(rl2, np.float32), rtol=2e-2, atol=2e-2)
    # dtype stability across the sharded path too
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(caches2.kv)
               if l.dtype != jnp.int32)
    print("MULTIDEVICE-OK")
""")


@pytest.mark.slow
def test_sharded_kv_decode_matches_reference():
    """The partial-manual shard_map slot update (length-sharded KV cache)
    produces the same tokens/logits as the single-device reference over two
    decode steps, on a forced 2×4 host mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"      # 8 emulated host devices, never a chip
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "MULTIDEVICE-OK" in p.stdout
