"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py <cell> [--n-pages N]

Lowers the weight maker and the serving programs the cell's batcher runs
(the decode chunk at its longest length, the cold admission at every batch
bucket, the cached admission at the documents' depth at every bucket) at
the cell's real sizes, for one chip of a described ``v5e:2x2``, and prints
each program's ``memory_analysis``: arguments, outputs, temporaries, the
bytes the donated outputs alias, and their sum less the aliasing, the
program's peak.  This is how a cell's ``n_pages`` is chosen: the largest
program of the cell has to fit the chip's 16 GB.  Nothing runs, so this says
nothing about times.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool size to try instead of the mix's")
    ap.add_argument("--only", default="",
                    help="comma-separated program kinds: params,chunk,"
                         "admit,cached")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import family
    import run
    from repro.models import init_paged_caches
    from repro.serving import ServingConfig
    from repro.serving.engine import (
        ServeConfig, cached_admit_program, init_page_state, init_slot_state,
        paged_admit_program, paged_decode_chunk_program)
    from repro.serving.kv_cache import pages_for

    jax.config.update("jax_enable_compilation_cache", False)
    for name in ("decode_attention", "flash_attention", "paged_attention",
                 "prefix_attention"):
        ops = importlib.import_module(f"repro.kernels.{name}.ops")
        ops.default_interpret = lambda: False       # compile the kernels
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    spec = run.load_cell(args.cell, run.read_benchmark())
    config, mix = spec["config"], spec["mix"]
    fam = family.load(config)
    cfg = fam.model_config(config)
    serving = dict(mix["serving"])
    if args.n_pages is not None:
        serving["n_pages"] = args.n_pages
    scfg = ServingConfig(**serving)
    ecfg = ServeConfig(max_len=scfg.max_len, attn_impl=scfg.attn_impl,
                       chunk=scfg.chunk)
    B, ps = scfg.slots, scfg.page_size
    max_pages = pages_for(scfg.max_len, ps)
    n_pages = scfg.n_pages or B * max_pages
    only = set(filter(None, args.only.split(",")))

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    def spec_(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = shaped(jax.eval_shape(lambda: fam.make_params(config, 0, cfg)))
    caches = shaped(jax.eval_shape(
        lambda: init_paged_caches(cfg, B, n_pages, ps)))
    state = shaped(jax.eval_shape(lambda: init_slot_state(B)))
    pages = shaped(jax.eval_shape(
        lambda: init_page_state(B, n_pages, max_pages)))
    key = spec_((2,), jnp.uint32)
    pool = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    print(f"{args.cell}: slots={B} n_pages={n_pages} page_size={ps} "
          f"pool_bytes={pool} weight_bytes={weights}", flush=True)

    def report(name, lowered):
        m = lowered.compile().memory_analysis()
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"  {name}: args={m.argument_size_in_bytes} "
              f"out={m.output_size_in_bytes} temp={m.temp_size_in_bytes} "
              f"alias={m.alias_size_in_bytes} peak={peak} "
              f"({peak / 1e9:.2f} GB)", flush=True)

    if not only or "params" in only:
        # the seed is a constant of this program: its key is no argument
        report("make_params", jax.jit(
            lambda: fam.make_params(config, 0, cfg), out_shardings=one
        ).lower())
    if not only or "chunk" in only:
        fn = paged_decode_chunk_program(cfg, ecfg, scfg.chunk, ps)
        report(f"decode_chunk T={scfg.chunk}",
               fn.lower(params, caches, state, pages, key))
    P = scfg.prompt_len
    for nb in run.pow2_upto(B):
        v = spec_((nb,))
        real = spec_((nb,), jnp.bool_)
        if not only or "admit" in only:
            report(f"admit nb={nb}", paged_admit_program(cfg, ecfg).lower(
                params, {"tokens": spec_((nb, P))}, caches, state, pages,
                v, v, v, v, real, v))
        p = mix["prompt"]
        if "documents" in p and scfg.prefix_cache and (
                not only or "cached" in only):
            k = min(p["doc_tokens"] // ps, (P - 1) // ps)
            report(f"cached_admit k={k} nb={nb}",
                   cached_admit_program(cfg, ecfg, k).lower(
                       params, {"tokens": spec_((nb, P - k * ps))}, caches,
                       state, pages, v, v, v, v, real, spec_((nb, k)), v))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
