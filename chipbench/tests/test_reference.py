"""The plain reference against the program's own prefill and paged decode,
on the CPU at test size, comparing logits.  The program runs in float32
here, on the same weight values (bfloat16 numbers), so the two agree to
float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import tiny_config

from families.qwen3 import reference
from families.qwen3.model import make_params, model_config
from families.qwen3.weights import Dims

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def setup():
    c = tiny_config(torch_dtype="float32")
    cfg = model_config(c)
    return c, cfg, Dims.from_config(c), make_params(c, SEED, cfg)


def test_prefill_logits_match(setup):
    from repro.models import forward, logits_fn

    c, cfg, dims, params = setup
    toks = np.random.default_rng(0).integers(1, dims.vocab, size=40)
    with jax.default_matmul_precision("highest"):
        h = forward(params, jnp.asarray(toks)[None], cfg).hidden
        got = np.asarray(logits_fn(params, h, cfg)[0, :, :dims.vocab])
    want = reference.logits(dims, SEED, toks, np.arange(40))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_decode_logits_match(setup, impl):
    """Feed a sequence one token at a time through the program's paged
    decode step (K/V written into pool pages across page boundaries) and
    compare each step's logits with the reference's."""
    from repro.models import decode_step, init_paged_caches

    c, cfg, dims, params = setup
    S, ps = 40, 16
    toks = np.random.default_rng(1).integers(1, dims.vocab, size=S)
    caches = init_paged_caches(cfg, 1, 8, ps)
    table = jnp.asarray([[5, 2, 7, -1]], jnp.int32)    # scattered pages
    got = []
    with jax.default_matmul_precision("highest"):
        for p in range(S):
            lg, caches = decode_step(
                params, jnp.asarray(toks[p:p + 1]), caches,
                jnp.asarray([p], jnp.int32), cfg, impl=impl,
                page_table=table)
            got.append(np.asarray(lg[0, :dims.vocab]))
    want = reference.logits(dims, SEED, toks, np.arange(S))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=0)


def test_gaps_of_reference_tokens_are_zero(setup):
    c, cfg, dims, params = setup
    prompt = np.random.default_rng(2).integers(1, dims.vocab, size=20)
    seq = prompt
    out = []
    for _ in range(5):                   # greedy by the reference itself
        lg = reference.logits(dims, SEED, seq, [len(seq) - 1])
        out.append(int(lg[0].argmax()))
        seq = np.append(seq, out[-1])
    (g,) = reference.served_gaps(dims, SEED, [(prompt, out)])
    assert g.shape == (5,) and (g == 0).all()
