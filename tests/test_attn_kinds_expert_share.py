"""Per-layer attention kinds (sliding window / full, default RoPE / YaRN)
and the no-drop expert share, on the paged serving path.

A Mellum-shaped model at toy size, made from the benchmark's Mellum2
configuration file shrunk to test widths: four layers, three attending
through a window of 8 with default RoPE and one in full with YaRN; every
MLP sparse, 8 experts routed top-2, this chip holding experts 4-7.  Its
logits are compared with the benchmark family's plain float32 reference
(``chipbench/families/mellum/reference.py``: ``jax.numpy`` from the
published definitions, nothing of the program's attention, RoPE or MoE
code used), on the family's seeded weights.
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import Yarn
from repro.models import init_params
from repro.models.layers import yarn_bounds, yarn_freqs
from repro.models.moe import grouped_matmul, moe_share
from repro.models.transformer import (
    decode_step, init_paged_caches, prefill, prefix_prefill)
from repro.serving import ServingConfig
from repro.serving.batcher import ContinuousBatcher, Request

BENCH = Path(__file__).resolve().parents[1] / "chipbench"
sys.path.append(str(BENCH))
import family  # noqa: E402  (the benchmark's families, found by path)

SEED = 2**33 + 9
# the program runs in float32 on the reference's weight values (bfloat16
# numbers): the two differ by summation order only, ~1e-6 relative; logits
# are O(1), so 2e-4 leaves two orders of room and still fails a wrong mask,
# rotation or routing weight (each moves logits by >= 1e-2)
LOGIT_TOL = 2e-4


def _config() -> dict:
    """The Mellum2 configuration file at test widths: one period of three
    windowed layers (window 8) and a full YaRN one, 8 published experts of
    which 4-7 are held, top-2, float32."""
    c = json.loads((BENCH / "configs" / "mellum2-12b-a2.5b-ep4.json")
                   .read_text())
    c.update(name="mellum-toy", hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
             vocab_size=1000, num_hidden_layers=4, sliding_window=8,
             layer_types=c["layer_types"][:4],
             mlp_layer_types=c["mlp_layer_types"][:4], num_experts=4,
             num_experts_per_tok=2, torch_dtype="float32",
             expert_parallel={"chips": 2, "published_num_experts": 8,
                              "first_expert": 4, "held": 4})
    return c


FAM = family.load(_config())
CFG = FAM.model_config(_config())
DIMS = FAM.Dims.from_config(_config())


def _params():
    return FAM.make_params(_config(), SEED, CFG)


def ref_logits(tokens):
    """The family reference's logits (S, vocab) at every position."""
    return FAM.reference.logits(DIMS, SEED, tokens, np.arange(len(tokens)))[0]


# ---------------------------------------------------------------------------
# YaRN at the published numbers
# ---------------------------------------------------------------------------


def test_yarn_published_numbers():
    """Mellum2's full layers: dim 128, base 500,000, factor 16 over 8,192
    positions, beta 32 / 1: the ramp runs from index 18 to 35 and cos/sin
    are scaled by 0.1 ln 16 + 1."""
    y = Yarn(factor=16.0, original_max_position=8192, beta_fast=32,
             beta_slow=1)
    assert yarn_bounds(128, 500_000.0, y) == (18, 35)
    inv, scale = yarn_freqs(128, 500_000.0, y)
    assert scale == pytest.approx(1.2772588722239782, abs=1e-12)
    assert scale == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    extrap = 500_000.0 ** (-2 * np.arange(64) / 128)
    np.testing.assert_allclose(inv[:19], extrap[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], extrap[35:] / 16, rtol=1e-12)
    e = 1 - (26 - 18) / (35 - 18)                 # inside the ramp
    assert inv[26] == pytest.approx(extrap[26] * (e + (1 - e) / 16),
                                    rel=1e-12)


# ---------------------------------------------------------------------------
# the expert share
# ---------------------------------------------------------------------------


def _share(params, first, held):
    cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, held=held, first_expert=first))
    return cfg, dict(params, wi=params["wi"][first:first + held],
                     wo=params["wo"][first:first + held])


@pytest.mark.parametrize("held", [1, 2, 4])
def test_expert_shares_add_up(held):
    """The held shares' partial outputs add up to the uncut layer's, and
    their counts to all assignments."""
    whole_cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, held=0, first_expert=0))
    lp = init_params(whole_cfg, jax.random.PRNGKey(3))["blocks"][0]
    lp = jax.tree.map(lambda a: a[0], lp)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, CFG.d_model))
    whole, stats = moe_share(lp, x, whole_cfg)
    assert stats.tolist()[:2] == [3 * 5 * 2, 3 * 5 * 2]
    total, held_sum = 0.0, 0
    for first in range(0, 8, held):
        cfg, p = _share(lp, first, held)
        out, st = moe_share(p, x, cfg)
        total = total + out
        held_sum += int(st[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert held_sum == 3 * 5 * 2


def test_one_expert_takes_every_token_and_drops_none():
    """Every token routed to expert 6, the third held (its router column
    dominates): the share computes all of them, as a per-token dense
    product over the held experts does."""
    lp = jax.tree.map(lambda a: a[0], _params()["blocks"][0])["moe"]
    lp = dict(lp, router=lp["router"].at[:, 6].set(50.0))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (4, 6, CFG.d_model)))
    out, st = moe_share(lp, x, CFG)
    first = CFG.moe.first_expert
    with jax.default_matmul_precision("highest"):
        h = x.reshape(-1, CFG.d_model)
        probs = jax.nn.softmax(h @ lp["router"], -1)
        top_p, top_i = jax.lax.top_k(probs, 2)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        assert bool((top_i[:, 0] == 6).all())
        want = 0.0
        for j in range(CFG.moe.n_held):
            w = jnp.where(top_i == first + j, top_p, 0).sum(-1)
            gate, up = jnp.split(h @ lp["wi"][j], 2, -1)
            want = want + w[:, None] * ((jax.nn.silu(gate) * up) @ lp["wo"][j])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, CFG.d_model),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    assert int(st[0]) >= 24 and int(st[2]) >= 1


# ---------------------------------------------------------------------------
# windowed kernels against their references (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 5, 9])
@pytest.mark.parametrize("n_q", [1, 3])
def test_paged_kernel_window_walk(window, n_q):
    """The paged kernel equals ``ref.py`` with and without the window's page
    walk (window 9, page 4: a walk of 4 or 5 of the slot's 12 pages)."""
    from repro.kernels.paged_attention import ops, ref

    B, H, Hkv, dh, ps, n_pages, maxp = 3, 4, 2, 32, 4, 40, 12
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (B, n_q, H, dh))
    kp = jax.random.normal(ks[1], (n_pages + 1, ps, Hkv, dh))
    vp = jax.random.normal(ks[2], (n_pages + 1, ps, Hkv, dh))
    table = jax.random.permutation(ks[3], n_pages)[:B * maxp].reshape(B, maxp)
    cur = jnp.array([2, 21, 44 - n_q], jnp.int32)
    table = jnp.where(jnp.arange(maxp)[None] * ps <= cur[:, None] + n_q - 1,
                      table, -1)
    got = ops.paged_verify_attention(q, kp, vp, table, cur, window=window,
                                     interpret=True)
    want = ref.paged_verify_attention_ref(q, kp, vp, table, cur,
                                          window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 6, 40])
def test_prefix_kernel_window(window):
    """Cached-prefix admission under a window equals masked attention over
    ``[prefix; suffix]``; blocks behind the window are skipped."""
    from repro.kernels.prefix_attention.ops import prefix_flash_attention
    from repro.models.attention import naive_attention

    B, Lp, S, H, Hkv, dh = 2, 48, 16, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    pk, pv = (jax.random.normal(k_, (B, Lp, Hkv, dh)) for k_ in ks[1:3])
    k, v = (jax.random.normal(k_, (B, S, Hkv, dh)) for k_ in ks[3:5])
    got = prefix_flash_attention(q, pk, pv, k, v, window=window, block_q=8,
                                 block_k=16, interpret=True)
    want = naive_attention(q, jnp.concatenate([pk, k], 1),
                           jnp.concatenate([pv, v], 1), causal=True,
                           window=window, q_offset=Lp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the model against the reference: cold prefill, paged decode, prefix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_logits_match_reference(impl):
    """Token by token through the paged decode step past the window and
    across 4-token pages (a shuffled page table), the cold prefill's last
    row, and a cached-prefix admission of the last 8 tokens, against the
    reference."""
    params = _params()
    S, ps, V = 24, 4, CFG.vocab
    toks = np.random.default_rng(3).integers(1, V, size=S)
    want = ref_logits(toks)
    with jax.default_matmul_precision("highest"):
        lg, _ = prefill(params, jnp.asarray(toks[None]), CFG, max_len=S,
                        impl=impl)
        np.testing.assert_allclose(np.asarray(lg[0, :V]), want[-1],
                                   atol=LOGIT_TOL, rtol=0)
        caches = init_paged_caches(CFG, 1, 8, ps)
        table = jnp.asarray([[5, 2, 7, 0, 3, 1, -1, -1]], jnp.int32)
        step = jax.jit(lambda c, t, p: decode_step(
            params, t, c, p, CFG, impl=impl, page_table=table))
        for p in range(S):
            lg, caches = step(caches, jnp.asarray(toks[p:p + 1]),
                              jnp.asarray([p], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg[0, :V]), want[p],
                                       atol=LOGIT_TOL, rtol=0)
        # pages 5, 2, 7, 0 (positions 0-15) as the prefix context
        pids = table[:, :4]
        prefix = {k: tuple(a[:, pids].reshape(a.shape[0], 1, 16, *a.shape[3:])
                           for a in (v.k, v.v))
                  for k, v in caches.kv.items()}
        lg, _ = prefix_prefill(params, jnp.asarray(toks[None, 16:]), prefix,
                               CFG, prefix_len=16, impl=impl)
        np.testing.assert_allclose(np.asarray(lg[0, :V]), want[-1],
                                   atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_batcher_serves_reference_tokens(impl):
    """Through ``ContinuousBatcher`` (paged, prefix cache): 24-token
    prompts over one shared 16-token document, decoded 20 tokens past the
    window of 8 and across 4-token pages.  Every served token is the
    reference's best within ``LOGIT_TOL`` (greedy serving; a gap wider than
    that means the program's logits left the reference's), judged by the
    family's own comparison, and the expert-share counters come back
    through the chunk and admission syncs."""
    params = _params()
    rng = np.random.default_rng(1)
    doc = rng.integers(1, CFG.vocab, size=16)
    scfg = ServingConfig(slots=2, prompt_len=24, max_len=48, paged=True,
                         page_size=4, prefix_cache=True, attn_impl=impl,
                         chunk=4)
    b = ContinuousBatcher(params, CFG, scfg)
    reqs = []
    for wave in range(2):
        for _ in range(2):
            prompt = np.concatenate([doc, rng.integers(1, CFG.vocab, 8)])
            r = Request(rid=len(reqs), prompt=prompt.astype(np.int32),
                        max_new=20, namespace="docs")
            reqs.append(r)
            b.submit(r)
        while b.queue or any(s is not None for s in b.slot_req):
            b.step()
    assert b.stats.prefix_hits >= 1
    assert all(len(r.out) == 20 for r in reqs)
    gaps = FAM.served_gaps(DIMS, SEED, [(r.prompt, list(r.out))
                                        for r in reqs])
    for r, g in zip(reqs, gaps):
        assert g.max() <= LOGIT_TOL, (r.rid, g.max())
    st = b.stats
    # decode: 4 requests x 19 decoded tokens x 4 layers x top-2
    assert st.moe_assignments == 4 * 19 * 4 * 2
    assert 0 < st.moe_held_assignments < st.moe_assignments
    assert 0 < st.moe_held_experts_hit <= st.steps * 4 * 4
    assert st.admit_moe_assignments > 0


@pytest.mark.parametrize("m", [8, 130])
def test_grouped_matmul_kernel_on_a_stack(m):
    """``grouped_matmul`` (megablox's kernel, interpret mode off the TPU)
    equals XLA's ``ragged_dot`` over a stack of 3 layers x 4 experts in
    which only the middle layer's groups have rows."""
    ks = jax.random.split(jax.random.PRNGKey(13), 2)
    lhs = jax.random.normal(ks[0], (m, 64)).astype(jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (12, 64, 96)).astype(jnp.bfloat16)
    held = np.array([m // 3, 0, m // 4, m // 5])
    sizes = jnp.asarray(np.concatenate([np.zeros(4), held, np.zeros(4)]),
                        jnp.int32)
    got = grouped_matmul(lhs, rhs, sizes)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    n = int(held.sum())
    # bfloat16 outputs, accumulated in float32 in another order: one ulp of
    # bfloat16 at these magnitudes (|x| ~ 8) is 0.06, inside rtol + atol
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=2e-2, atol=2e-2)
