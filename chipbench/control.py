"""Read the comparison's two sides on the chip: the program's widest gap on
a dozen seeds or more, and the float8 control's on the same requests.

    python chipbench/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3,...

One process, one run of ``run.py``'s cell per seed (a short window at the
cell's own load), each followed by the reference and the control on the
requests it sampled.  Prints one JSON line per seed: the program's verdict
(``correct``) and the control's (``control_correct``, the same test with the
control in the program's place), and both widest gaps.  The limit in the
mix's ``check`` is set between the largest program reading and the
smallest control reading (``PERF.md`` gives both).  The benchmark's own
runs never run the control.
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
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload, run.read_benchmark())
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(run.ROOT / "src"))
    for seed in (int(s) for s in args.seeds.split(",")):
        res, cmp_ = run.run_cell(spec, seed, args.seconds, False,
                                 time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "program": cmp_["widest_gap"]["value"],
                          "control": cmp_["control_widest_gap"]["value"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
