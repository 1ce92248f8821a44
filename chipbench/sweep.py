"""Find a cell's knee: the highest offered rate whose backlog does not grow.

    python chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2,3,4,5

One process on the chip: the weights and the warm-up of ``run.py`` once,
then for each rate a fresh batcher offered the cell's traffic at that rate
(the mix's ``knee_per_s`` and ``load`` are ignored), ``preroll_s`` seconds
before a window of ``--seconds``.  Per rate it prints the rate offered, the
rate completed, the backlog (requests arrived and not finished) when the
window opens and when it closes, and the tails.  The knee is written into
the mix file by hand, with the sweep's output in ``PERF.md``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload, run.read_benchmark())
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep.py: no TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(run.ROOT / "src"))
    import gc

    import family
    import traffic
    from clock import percentile

    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher

    run.use_compile_cache()
    config, mix = spec["config"], spec["mix"]
    fam = family.load(config)
    cfg, dims = fam.model_config(config), fam.Dims.from_config(config)
    scfg = ServingConfig(**mix["serving"])
    params = fam.make_params(config, args.seed, cfg)
    run.warm_shapes(params, cfg, scfg, mix, dims.vocab)
    print(f"setup {time.perf_counter() - t_start:.1f} s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.schedule(mix, args.seed, dims.vocab, rate=rate)
        b = ContinuousBatcher(params, cfg, scfg)
        if "documents" in mix["prompt"] and scfg.prefix_cache:
            run.fill_documents(b, plan, mix)
        t0 = time.perf_counter()
        win = run.Window(start=t0 + mix["preroll_s"],
                         end=t0 + mix["preroll_s"] + args.seconds)
        recs = run.drive(b, plan, t0, win)
        pop, ttft, tpot = run.latencies(recs, win)
        backlog_open = sum(1 for r in recs if r.arrival <= win.start
                           and (r.done is None or r.done > win.start))
        backlog_close = sum(1 for r in recs if r.done is None)
        done = sum(1 for r in recs if r.done is not None
                   and win.start <= r.done <= win.end)
        print(json.dumps({
            "rate_offered": rate, "arrived_per_s": len(pop) / args.seconds,
            "completed_per_s": done / args.seconds,
            "backlog_open": backlog_open, "backlog_close": backlog_close,
            "tokens_per_s": win.tokens / args.seconds,
            "ttft_p90_ms": percentile(ttft, 0.9) * 1e3,
            "tpot_p90_ms": percentile(tpot, 0.9) * 1e3,
            "queue_close": len(b.queue)}), flush=True)
        del b, recs
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
