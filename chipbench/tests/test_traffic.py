"""The traffic generator: seeded, and drawn from the mix file's
parameters."""

import json
import math

import numpy as np
import pytest
from conftest import BENCH

import traffic


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def chat_mix():
    """A mix with drawn prompt lengths (no documents)."""
    m = mix("docqa")
    m.update(prompt={"dist": "lognormal", "median": 700, "sigma": 0.7,
                     "min": 128, "max": 2048},
             output={"dist": "lognormal", "median": 200, "sigma": 0.7,
                     "min": 32, "max": 1024},
             namespace=None,
             arrivals={"process": "poisson", "knee_per_s": 30, "load": 2.0})
    return m


def test_same_seed_same_schedule():
    a = traffic.schedule(mix("docqa"), 2**31 + 99, 151936)
    b = traffic.schedule(mix("docqa"), 2**31 + 99, 151936)
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]
    assert all((p.prompt == q.prompt).all() and p.max_new == q.max_new
               for p, q in zip(a, b))


def test_seeds_offer_the_same_sizes_in_another_order():
    a = traffic.schedule(chat_mix(), 1, 151936)
    b = traffic.schedule(chat_mix(), 2, 151936)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert [p.max_new for p in a] != [p.max_new for p in b]
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]


def test_document_prompts_are_exactly_the_bucket():
    m = mix("docqa")
    plan = traffic.schedule(m, 5, 151936)
    p = m["prompt"]
    assert {len(r.prompt) for r in plan} == {4096}
    assert p["doc_tokens"] + p["question_tokens"] == \
        m["serving"]["prompt_len"]
    # the same document gives the same first doc_tokens tokens
    by_doc = {}
    for r in plan:
        first = by_doc.setdefault(r.doc, r.prompt[:p["doc_tokens"]])
        assert (first == r.prompt[:p["doc_tokens"]]).all()
    assert len(by_doc) <= p["documents"]


def test_zipf_ranks_follow_the_file():
    rng = np.random.default_rng(0)
    ranks = traffic.zipf_ranks(12, 1.1, 200_000, rng)
    freq = np.bincount(ranks, minlength=12) / len(ranks)
    want = np.arange(1, 13) ** -1.1
    assert np.allclose(freq, want / want.sum(), atol=0.005)


@pytest.mark.parametrize("which", ["docqa", "chat"])
def test_lognormal_lengths_follow_the_file(which):
    d = (mix("docqa") if which == "docqa" else chat_mix())["output"]
    x = traffic.draw_lengths(d, 100_000, np.random.default_rng(0))
    assert x.min() >= d["min"] and x.max() <= d["max"]
    assert abs(np.median(x) - d["median"]) <= 2
    # the shares clipped at each end are the lognormal's tails there (a
    # length rounds to the bound from half a token beyond it)
    def cdf(v):
        z = (np.log(v) - np.log(d["median"])) / d["sigma"]
        return 0.5 * (1 + math.erf(z / math.sqrt(2)))

    assert (x == d["min"]).mean() == pytest.approx(cdf(d["min"] + 0.5),
                                                   abs=0.005)
    assert (x == d["max"]).mean() == pytest.approx(1 - cdf(d["max"] - 0.5),
                                                   abs=0.005)


def test_arrivals_are_poisson_at_the_offered_rate():
    m = chat_mix()
    plan = traffic.schedule(m, 3, 151936)
    rate = traffic.rate_per_s(m)
    gaps = np.diff([p.arrival_s for p in plan])
    assert abs(gaps.mean() * rate - 1) < 0.05
    assert plan[-1].arrival_s >= m["horizon_s"] * 0.9


def test_a_window_holds_the_same_work_whatever_the_seed():
    m = mix("docqa")
    block = m["shuffle_block"]
    a = traffic.schedule(m, 11, 151936)
    b = traffic.schedule(m, 12, 151936)
    for i in range(0, len(a) - block, block):
        assert a[i + block - 1].arrival_s == \
            pytest.approx(b[i + block - 1].arrival_s)
        assert sorted(p.max_new for p in a[i:i + block]) == \
            sorted(p.max_new for p in b[i:i + block])
