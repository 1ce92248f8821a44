"""The per-layer metrics read from the batcher's own admission counters
(``admit_plan_ms``, ``queue_wait_ms``, ``prefill_pad_share``), and the
labelling of idle gaps by the batcher's nested phase spans."""

import numpy as np
import pytest
from conftest import tiny_config, tiny_mix

import run
import trace_reduce as tr
from trace_reduce import Event, Trace

MS = 1e6        # ns
NAMES = ("admit_plan_ms", "queue_wait_ms", "prefill_pad_share")


def measured(counters):
    return run.Measured(dims=None, page_size=16, peak={}, trace=None,
                        window=None, counters=counters, flops=None,
                        ttft_s=[])


def test_readers_on_synthetic_counters():
    m = measured({"admitted": 4, "admit_plan_us": 20_000,
                  "queue_wait_us": 100_000,
                  "prefill_tokens_computed": 4 * 256,
                  "prefill_tokens_needed": 3 * 256})
    assert run.reader("admit_plan_ms")(m) == pytest.approx(5.0)
    assert run.reader("queue_wait_ms")(m) == pytest.approx(25.0)
    assert run.reader("prefill_pad_share")(m) == pytest.approx(25.0)


@pytest.mark.parametrize("counters", [
    {"steps": 10},                                  # a program without them
    {"admitted": 0, "admit_plan_us": 5, "queue_wait_us": 0,
     "prefill_tokens_computed": 0, "prefill_tokens_needed": 0},
])
def test_nothing_to_read_is_none(counters):
    for name in NAMES:
        assert run.reader(name)(measured(counters)) is None


def test_gap_inside_a_phase_takes_the_phase_label():
    """The innermost span labels a gap: a gap inside ``admit.plan`` (the
    batcher's phase) is the plan's, not the outer ``admit``'s; one in
    ``admit`` outside any phase stays ``admit``'s."""
    ops = [Event("fusion.1", 0, 10 * MS), Event("fusion.2", 30 * MS, 10 * MS),
           Event("fusion.3", 45 * MS, 55 * MS)]
    host = [Event("window", 0, 100 * MS), Event("step", 5 * MS, 95 * MS),
            Event("admit", 8 * MS, 37 * MS),
            Event("admit.plan", 9 * MS, 21 * MS),
            Event("admit.dispatch", 30 * MS, 10 * MS)]
    t = Trace(ops={"/device:TPU:0": ops}, modules={}, host=host)
    r = tr.reduce(t, labels=("step", "admit", "admit.plan",
                             "admit.dispatch"))
    # idle 10-30 (mid 20: admit.plan), 40-45 (mid 42.5: admit)
    assert r.idle_by_label() == [["admit.plan", pytest.approx(0.020)],
                                 ["admit", pytest.approx(0.005)]]


def test_readers_on_a_served_run():
    """On the counters of a small paged, prefix-cached run (CPU): the three
    readers give numbers, and a round of three cached joins pads its bucket
    of four by one row."""
    import jax

    from families.qwen3.model import make_params, model_config
    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher, Request

    config, mix = tiny_config(), tiny_mix()
    cfg = model_config(config)
    params = make_params(config, 3, cfg)
    jax.block_until_ready(params)
    sc = ServingConfig(**mix["serving"])
    b = ContinuousBatcher(params, cfg, sc)
    rng = np.random.default_rng(0)
    doc = rng.integers(1, 1000, size=48)

    def ask(rid):
        q = rng.integers(1, 1000, size=sc.prompt_len - len(doc))
        return Request(rid=rid, prompt=np.concatenate([doc, q]).astype(
            np.int32), max_new=4, namespace="docs")

    for rid in range(2):
        b.submit(ask(rid))
    b.run()                     # the pair's recurrence caches the document
    c0 = b.stats.as_dict()
    for rid in range(2, 5):
        b.submit(ask(rid))
    b.run()
    c1 = b.stats.as_dict()
    m = measured({k: c1[k] - c0[k] for k in c1})
    assert m.counters["admitted"] == 3
    assert run.reader("prefill_pad_share")(m) == pytest.approx(25.0)
    assert run.reader("admit_plan_ms")(m) > 0
    assert run.reader("queue_wait_ms")(m) >= 0
