"""Witness for the fault that keeps the issue's chat cells out of
``BENCHMARK.json``: the batcher left-pads a prompt shorter than
``prompt_len`` with token 0 and attends to the padding, so what it serves
departs from the model's output on the prompt the user sent; it agrees
with the reference run over the padded row instead.  A prompt exactly
``prompt_len`` long has no padding and agrees.  When the batcher masks
its padding this test fails, and the chat cells can come back."""

import numpy as np
from conftest import tiny_config

from families.qwen3 import reference
from families.qwen3.model import make_params, model_config
from families.qwen3.weights import Dims

SEED = 2**31 + 77


def _serve(prompts, prompt_len=64):
    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher, Request

    c = tiny_config(torch_dtype="float32")
    cfg = model_config(c)
    b = ContinuousBatcher(make_params(c, SEED, cfg), cfg, ServingConfig(
        slots=4, prompt_len=prompt_len, max_len=prompt_len + 16,
        paged=True, page_size=16))
    reqs = [Request(rid=i, prompt=p, max_new=8) for i, p in
            enumerate(prompts)]
    for r in reqs:
        b.submit(r)
    b.run()
    return Dims.from_config(c), [list(r.out) for r in reqs]


def test_left_padding_changes_the_answer():
    rng = np.random.default_rng(3)
    short = [rng.integers(1, 1000, size=n).astype(np.int32)
             for n in (20, 33)]
    full = rng.integers(1, 1000, size=64).astype(np.int32)
    dims, outs = _serve(short + [full])
    plain = reference.served_gaps(dims, SEED, zip(short + [full], outs))
    padded = [np.concatenate([np.zeros(64 - len(p), np.int32), p])
              for p in short]
    as_served = reference.served_gaps(dims, SEED, zip(padded, outs))
    assert max(g.max() for g in plain[:2]) > 0.05       # departs
    assert max(g.max() for g in as_served) < 1e-3       # the padded row
    assert plain[2].max() < 1e-3                        # no padding: agrees
