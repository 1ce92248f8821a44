"""The reduction from a profiler trace to busy time, program and kernel
time and labelled idle gaps: on a synthetic trace built here, and on a
trace the CPU profiler records (host spans only)."""

import glob

import pytest

import trace_reduce as tr
from trace_reduce import Event, Trace

MS = 1e6        # ns


def synthetic():
    """Window [0, 100) ms on one device: a chunk program 10-40 ms whose ops
    are two kernels and a matmul, an admission 60-70 ms; host spans say
    the host was in ``step`` 5-45 and ``wait`` 45-100 (with ``admit``
    58-72)."""
    ops = [Event("_paged_kernel", 10 * MS, 10 * MS),
           Event("fusion.1", 20 * MS, 5 * MS),
           Event("_paged_kernel", 30 * MS, 10 * MS),
           Event("fusion.2", 60 * MS, 10 * MS),
           Event("fusion.3", 95 * MS, 10 * MS)]          # ends past the close
    mods = [Event("jit_decode_chunk(12)", 10 * MS, 30 * MS),
            Event("jit_admit_step(3)", 60 * MS, 10 * MS)]
    host = [Event("window", 0, 100 * MS), Event("step", 5 * MS, 40 * MS),
            Event("wait", 45 * MS, 55 * MS), Event("admit", 58 * MS, 14 * MS),
            Event("unrelated", 0, 100 * MS)]
    return Trace(ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": mods}, host=host)


def test_busy_programs_and_kernels():
    r = tr.reduce(synthetic(), labels=("step", "wait", "admit"))
    assert r.window_s == pytest.approx(0.1)
    # 10-25, 30-40, 60-70, 95-100 ms
    assert r.busy_s == pytest.approx(0.040)
    assert r.module_s == pytest.approx({"jit_decode_chunk": 0.030,
                                        "jit_admit_step": 0.010})
    assert r.ops_matching(r"_paged_kernel") == pytest.approx(0.020)
    assert r.modules_matching(r"jit_decode_chunk$") == pytest.approx(0.030)
    assert r.top_ops(2)[0] == ["_paged_kernel", pytest.approx(0.020)]


def test_gaps_labelled_by_innermost_host_span():
    r = tr.reduce(synthetic(), labels=("step", "wait", "admit"))
    # idle: 0-10 (mid 5: step starts at 5 -> step), 25-30 (step),
    # 40-60 (mid 50: wait), 70-95 (mid 82.5: wait)
    assert r.idle_by_label() == [["wait", pytest.approx(0.045)],
                                 ["step", pytest.approx(0.015)]]
    assert sum(s for _, s in r.gaps) == pytest.approx(r.window_s - r.busy_s)


def test_no_window_is_an_error():
    t = synthetic()
    t.host = [e for e in t.host if e.name != "window"]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("step"):
            jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    t = tr.read_xplane(path)
    names = {e.name for e in t.host}
    assert {"window", "step"} <= names
    r = tr.reduce(t, labels=("step",))
    assert r.window_s > 0 and r.busy_s == 0.0      # no device plane here


def test_op_names_from_hlo_text():
    """A TPU trace names an op by its instruction's HLO text; the kernel
    reader matches the instruction name."""
    import re

    import readers

    text = ("%paged_verify_attention.11 = bf16[16,16,128]{2,1,0:T(8,128)}"
            " custom-call(bf16[16,8,2,128] %x), custom_call_target=\"tpu\"")
    assert tr.op_name(text) == "paged_verify_attention.11"
    assert re.search(readers.DECODE_ATTN_KERNEL, tr.op_name(text))
    assert tr.op_name("%while.51 = (s32[]) while(...)") == "while.51"
