"""Shared helpers for the paper-reproduction benchmarks.

Every bench_*.py exposes ``run() -> list[dict]`` returning flat row dicts;
``benchmarks/run.py`` drives them all and emits CSV + a summary.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, List, Optional

from repro.core import (
    CNN_WORKLOADS,
    DynamicCompiler,
    StaticArtifact,
    StaticCompiler,
    Strategy,
    allocate,
    fpga_core,
)
from repro.launch.runtime import use_compile_cache

OUT_DIR = os.environ.get("BENCH_OUT", "experiments/bench")

# every bench shares one persistent compilation cache (JAX_COMPILATION_CACHE_DIR
# when set, else the checkout's .jax_cache)
use_compile_cache()

#: Table 3 of the paper (ResNet50 fps) — the calibration/validation target.
PAPER_TABLE3_RESNET50 = {
    1: {"W": 6.8, "OC": 4.2, "opt": 6.8, "single": 7.6, "linear": 7.6},
    2: {"W": 12.4, "OC": 9.0, "opt": 13.1, "single": 14.3, "linear": 15.1},
    4: {"W": 21.9, "OC": 26.8, "opt": 27.2, "single": 28.5, "linear": 30.2},
    8: {"W": 29.6, "OC": 46.1, "opt": 53.5, "single": 53.6, "linear": 60.5},
    16: {"W": 33.3, "OC": 85.5, "opt": 98.9, "single": 84.4, "linear": 120.9},
}

#: Table 2 of the paper (ms).
PAPER_TABLE2 = {
    "vgg16": {"static_s": 44.8, "dynamic_ms": (0.4, 0.65), "ctx_ms": (0.45, 0.83)},
    "resnet50": {"static_s": 46.8, "dynamic_ms": (0.86, 1.06), "ctx_ms": (0.89, 1.21)},
    "inception_v3": {"static_s": 34.9, "dynamic_ms": (1.06, 1.5), "ctx_ms": (1.12, 1.70)},
    "mobilenet": {"static_s": 14.7, "dynamic_ms": (0.53, 0.67), "ctx_ms": (0.56, 0.82)},
}

CNNS = ("vgg16", "resnet50", "inception_v3", "mobilenet")


@functools.lru_cache(maxsize=64)
def small_core(bw_factor: float = 1.0):
    hw = fpga_core(parallelism=512, ddr_port_bits=128)
    return hw.with_bandwidth(bw_factor) if bw_factor != 1.0 else hw


@functools.lru_cache(maxsize=64)
def static_artifact(cnn: str, n_tiles: int = 16, bw_factor: float = 1.0) -> StaticArtifact:
    wl = CNN_WORKLOADS[cnn]()
    return StaticCompiler(small_core(bw_factor), n_tiles=n_tiles).compile(wl)


@functools.lru_cache(maxsize=64)
def single_core_artifact(cnn: str, parallelism: int, bw_factor: float = 1.0):
    """Static single-core design at a given parallelism (paper baseline):
    ddr ports scale with size up to the 4-bank budget."""
    ddr = min(128 * (parallelism // 512), 4 * 512)
    hw = fpga_core(parallelism=parallelism, ddr_port_bits=max(ddr, 128))
    if bw_factor != 1.0:
        hw = hw.with_bandwidth(bw_factor)
    wl = CNN_WORKLOADS[cnn]()
    art = StaticCompiler(hw, n_tiles=1).compile(wl)
    return art, hw


def multi_core_fps(cnn: str, k: int, *, strategy: Optional[Strategy] = None,
                   bw_factor: float = 1.0, fastpath: bool = True) -> float:
    """fps of one task on k small cores.  ``strategy=None`` = optimized
    per-layer choice (the paper's two-stage compiler); otherwise forced."""
    art = static_artifact(cnn, bw_factor=bw_factor)
    hw = small_core(bw_factor)
    if strategy is None:
        dyn = DynamicCompiler(art)
        sch = dyn.compile(list(range(k)), single_core_fastpath=fastpath)
        return 1.0 / sch.estimated_latency(hw)
    total = 0.0
    for li in range(len(art.workload)):
        lut = art.lut(li, strategy)
        _, ms = allocate(lut.cached, k, run_overhead=lut.run_overhead,
                         precomputed=lut.precomputed)
        total += ms + hw.sync_latency
    return 1.0 / total


def single_core_fps(cnn: str, parallelism: int, *, bw_factor: float = 1.0) -> float:
    art, hw = single_core_artifact(cnn, parallelism, bw_factor)
    sch = DynamicCompiler(art).compile([0])
    return 1.0 / sch.estimated_latency(hw)


def write_csv(name: str, rows: List[Dict]) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.csv")
    if not rows:
        return path
    keys = list(rows[0].keys())
    with open(path, "w") as f:
        f.write(",".join(keys) + "\n")
        for r in rows:
            f.write(",".join(str(r.get(k, "")) for k in keys) + "\n")
    return path
