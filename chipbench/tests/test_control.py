"""The control: the reference computed in float8 in the program's place
fails the comparison that the program, served in bfloat16, passes.  At test
size on the CPU; the same reading at each cell's own size on the chip is
``chipbench/control.py``."""

import time

from conftest import tiny_config, tiny_mix

import run

SEEDS = (2**31 + 11, 3**20)


def test_control_fails_where_the_program_passes():
    spec = {"cell": {"name": "tiny.docqa", "chips": 1},
            "config": tiny_config(), "mix": tiny_mix(),
            "end_to_end": [], "per_layer": []}
    for seed in SEEDS:
        res, cmp_ = run.run_cell(spec, seed, 1.5, False, time.perf_counter(),
                                 control=True)
        limit = cmp_["widest_gap"]["limit"]
        assert res["correct"], cmp_
        assert cmp_["widest_gap"]["value"] <= limit
        assert cmp_["control_widest_gap"]["value"] > limit, cmp_
        assert not res["control_correct"], cmp_
