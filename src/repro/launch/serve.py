"""Serving launcher: multi-tenant virtualized inference on one "FPGA node".

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --tenants 2 --requests 16

Each tenant leases a disjoint core set from the VirtualAcceleratorPool
(SDM — the paper's isolation model), runs a ContinuousBatcher over its own
compiled programs, and can be resized at runtime through the TwoStageCompiler
without recompilation.  Decode runs the chunked/donated hot path (one device
dispatch and one host sync per --chunk tokens; see serving.batcher).  On
this CPU container cores are logical (1 device time-shared); on a real
slice each core is a chip/sub-mesh.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps fused per device dispatch (1 = per-step)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.runtime import require_backend, use_compile_cache

    platform = require_backend()
    use_compile_cache()

    from repro.configs import get_config, get_reduced
    from repro.models import init_params
    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher, Request
    from repro.serving.tenancy import VirtualAcceleratorPool

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    pool = VirtualAcceleratorPool(devices=list(jax.devices()) * max(16, args.tenants),
                                  devices_per_core=1)
    rng = np.random.default_rng(args.seed)

    print(f"[serve] arch={cfg.name} platform={platform} "
          f"device={jax.devices()[0].device_kind} tenants={args.tenants} "
          f"pool={pool.n_cores} cores")
    total_toks = 0
    t0 = time.time()
    for t in range(args.tenants):
        lease = pool.lease(f"tenant{t}", pool.n_cores // args.tenants)
        batcher = ContinuousBatcher(
            params, cfg,
            ServingConfig(slots=args.slots, prompt_len=args.prompt_len,
                          max_len=args.prompt_len + args.max_new + 2,
                          chunk=args.chunk),
        )
        for r in range(args.requests):
            plen = int(rng.integers(2, args.prompt_len))
            batcher.submit(Request(
                rid=r, prompt=rng.integers(1, cfg.vocab, size=plen).astype(np.int32),
                max_new=args.max_new,
            ))
        stats = batcher.run()
        print(f"  tenant{t}: lease={list(lease.cores)[:4]}..., "
              f"completed={stats.completed}/{args.requests}, "
              f"decode steps={stats.steps} in {stats.chunks} chunks "
              f"({stats.dispatches} dispatches, {stats.host_syncs} syncs, "
              f"{stats.dispatches_per_token:.3f} disp/token), "
              f"occupancy={stats.occupancy:.2f}")
        total_toks += stats.tokens
    dt = time.time() - t0
    print(f"[serve] done in {dt:.1f}s (~{total_toks/dt:,.0f} tokens/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
