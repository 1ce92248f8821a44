"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (``run.run_cell``: the harness's look
for a chip is in ``main``) at test size on the CPU, with one fault planted
in the serving program:

* a decode step that returns its state unchanged: the paged attention
  leaves the K/V pool as it found it, so later tokens attend to stale
  pages;
* a token altered where it is produced: the decode chunk's token selection
  emits the next id after the greedy one.

The faults a serving cell on one chip cannot have (half of a training batch
left out, the exchange between chips) have no test here.
"""

import time

import jax
import pytest
from conftest import tiny_config, tiny_mix

import run

SEED = 2**31 + 21


@pytest.fixture
def fresh_programs():
    from repro.serving.engine import PROGRAMS

    PROGRAMS.clear()
    jax.clear_caches()
    yield
    PROGRAMS.clear()
    jax.clear_caches()


def _run():
    spec = {"cell": {"name": "tiny.docqa", "chips": 1},
            "config": tiny_config(), "mix": tiny_mix(),
            "end_to_end": [], "per_layer": []}
    return run.run_cell(spec, SEED, 1.5, False, time.perf_counter())


def test_sound_run_is_correct(fresh_programs):
    res, cmp_ = _run()
    assert res["correct"], cmp_


def test_state_left_unchanged(fresh_programs, monkeypatch):
    from repro.models import attention

    real = attention.paged_decode_attention

    def stale(params, x, cache, *a, **kw):
        y, _ = real(params, x, cache, *a, **kw)
        return y, cache

    monkeypatch.setattr(attention, "paged_decode_attention", stale)
    res, cmp_ = _run()
    assert not res["correct"], cmp_


def test_token_altered(fresh_programs, monkeypatch):
    from repro.serving import engine

    real = engine.select_token

    def off_by_one(logits, mask, scfg, key):
        return (real(logits, mask, scfg, key) + 1) % 1000

    monkeypatch.setattr(engine, "select_token", off_by_one)
    res, cmp_ = _run()
    assert not res["correct"], cmp_
