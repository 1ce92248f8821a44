"""The mellum family: found by its config's ``model_type`` with no edit to
the harness, its config mapped onto the program's, and its plain reference
and float8 control on the CPU at test size.  The reference against the
program's cold prefill, paged decode, cached-prefix admission and
``ContinuousBatcher`` is a test of the program:
``tests/test_attn_kinds_expert_share.py``."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import BENCH

import family

SEED = 2**33 + 9
TOL = 2e-4


def tiny_mellum(**over) -> dict:
    """The Mellum2 config file shrunk to test size (widths included: tests
    only): one period of 3 windowed layers (window 8) and a full YaRN one,
    8 published experts of which 4 are held, top-2."""
    c = json.loads((BENCH / "configs" / "mellum2-12b-a2.5b-ep4.json")
                   .read_text())
    c.update(name="tiny-mellum", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
             vocab_size=1000, num_hidden_layers=4, sliding_window=8,
             layer_types=c["layer_types"][:4],
             mlp_layer_types=c["mlp_layer_types"][:4], num_experts=4,
             num_experts_per_tok=2, torch_dtype="float32",
             expert_parallel={"chips": 2, "published_num_experts": 8,
                              "first_expert": 4, "held": 4})
    c.update(over)
    return c


def test_config_file_maps_published_keys():
    c = json.loads((BENCH / "configs" / "mellum2-12b-a2.5b-ep4.json")
                   .read_text())
    fam = family.load(c)
    assert fam.__name__ == "families.mellum"
    cfg = fam.model_config(c)
    assert [k.window for k in cfg.attn_pattern] == [1024, 1024, 1024, None]
    assert cfg.attn_pattern[3].yarn.factor == 16
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.top_k) == (64, 16, 8)
    assert cfg.n_layers == 28 and cfg.vocab == 98304
    dims = fam.Dims.from_config(c)
    assert dims.windows.count(None) == 7 and dims.held == 16


@pytest.fixture(scope="module")
def setup():
    c = tiny_mellum()
    fam = family.load(c)
    cfg = fam.model_config(c)
    return fam, cfg, fam.Dims.from_config(c), fam.make_params(c, SEED, cfg)


def test_control_moves_the_logits(setup):
    """The float8 control differs from the reference by far more than the
    program does (it must fail the comparison on the chip)."""
    fam, cfg, dims, params = setup
    toks = np.random.default_rng(4).integers(1, dims.vocab, size=24)
    ref, _ = fam.reference.logits(dims, SEED, toks, np.arange(24))
    low, _ = fam.reference.logits(dims, SEED, toks, np.arange(24),
                                  precision="fp8")
    assert np.abs(ref - low).max() > 50 * TOL


def test_gaps_of_reference_tokens_are_zero(setup):
    fam, cfg, dims, params = setup
    prompt = np.random.default_rng(2).integers(1, dims.vocab, size=20)
    seq, out = prompt, []
    for _ in range(4):                   # greedy by the reference itself
        lg, _ = fam.reference.logits(dims, SEED, seq, [len(seq) - 1])
        out.append(int(lg[0].argmax()))
        seq = np.append(seq, out[-1])
    (g,) = fam.served_gaps(dims, SEED, [(prompt, out)])
    assert g.shape == (4,) and (g == 0).all()
