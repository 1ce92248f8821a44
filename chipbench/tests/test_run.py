"""The command itself: no chip, no result."""

import json
import subprocess
import sys

from conftest import BENCH


def test_exits_nonzero_without_a_tpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-0.6b.docqa", "--seed", str(2**31 + 1), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=BENCH.parent, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    for line in p.stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_every_cell_names_files_that_exist():
    import run

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"], bench)
        assert spec["mix"]["serving"]["prompt_len"] >= 1
        for m in spec["per_layer"]:
            assert callable(run.reader(m["name"]))


def test_every_per_layer_metric_has_a_reader_file():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
