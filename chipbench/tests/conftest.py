"""The benchmark's own tests, run by path on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

They sit outside the repository's ``testpaths`` on purpose: they check the
yardstick (traffic, operation counts, trace reduction, the reference, the
control and the fault checks), not the program.
"""

import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def tiny_config(**over) -> dict:
    """A Qwen3 config file shrunk to test size (widths included: tests
    only)."""
    c = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    c.update(name="tiny", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=128,
             vocab_size=1000, num_hidden_layers=2)
    c.update(over)
    return c


def tiny_mix(**over) -> dict:
    mix = {"name": "tiny", "base_seed": 7, "horizon_s": 10,
           "shuffle_block": 8,
           "arrivals": {"process": "poisson", "knee_per_s": 20, "load": 0.8},
           "prompt": {"documents": 4, "doc_tokens": 48, "zipf_s": 1.1,
                      "question_tokens": 16},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 24},
           "namespace": "docs", "preroll_s": 0.5, "trace_s": 1.0,
           "warm": {"cold_batches": [1, 2], "cached_batches": [1, 2, 4]},
           "serving": {"slots": 4, "prompt_len": 64, "max_len": 96,
                       "attn_impl": "xla", "chunk": 8, "paged": True,
                       "page_size": 16, "n_pages": 40, "prefix_cache": True},
           "check": {"requests": 3, "limit": 0.01}}
    mix.update(over)
    return mix


@pytest.fixture(autouse=True)
def _cpu_cache(tmp_path, monkeypatch):
    """Keep compiled programs out of the checkout's cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
