"""The configuration file as the serving program takes it.

``model_config`` maps a Mellum2 ``config.json`` (as kept under
``chipbench/configs/``, with its ``expert_parallel`` share) onto the
program's ``ModelConfig``: the repeating period of ``layer_types`` becomes
``attn_pattern`` (a window and default RoPE for ``sliding_attention``, full
attention with YaRN for ``full_attention``), every MLP the expert share.
``make_params`` packs the weights of ``weights.py`` into the program's
parameter tree, on the device, in one jitted call.  This is the only module
of the family that knows the program's parameter layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from families.qwen3.weights import embedding, final_norm, lm_head, root_key

from .weights import Dims, held_experts, layer_weights


def _period(kinds):
    """The shortest prefix whose repetition is ``kinds``."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]


def model_config(c: dict):
    from repro.configs.base import AttnKind, ModelConfig, MoEConfig, Yarn

    if c.get("hidden_act", "silu") != "silu" or c.get("attention_bias"):
        raise ValueError("only SwiGLU, bias-free Mellum blocks are mapped")
    if not c.get("norm_topk_prob", False):
        raise ValueError("the router's top-k weights are renormalised")
    if set(c["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("only all-sparse MLP layers are mapped")
    dims = Dims.from_config(c)
    kinds = []
    for window, (theta, yarn) in zip(dims.windows, dims.ropes):
        kinds.append(AttnKind(
            window=window, rope_theta=theta,
            yarn=None if yarn is None else Yarn(*yarn)))
    return ModelConfig(
        name=c["name"], family="moe", n_layers=dims.n_layers, d_model=dims.d,
        n_heads=dims.n_heads, n_kv_heads=dims.n_kv,
        d_ff=c["intermediate_size"], vocab=dims.vocab, d_head=dims.d_head,
        rope_theta=dims.ropes[0][0], attn_pattern=tuple(_period(kinds)),
        tie_embeddings=dims.tied, norm_eps=dims.eps, dtype=c["torch_dtype"],
        moe=MoEConfig(n_experts=dims.n_experts, top_k=dims.top_k,
                      expert_d_ff=dims.d_expert, held=dims.held,
                      first_expert=dims.first_expert))


def _pad_rows(w, rows):
    return jnp.pad(w, ((0, rows - w.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _make(key, dims: Dims, vocab_padded: int, period: int,
          dtype: str = "bfloat16"):
    def one(layer):
        w = layer_weights(key, layer, dims)
        e = held_experts(key, layer, dims)
        return {
            "ln1": {"scale": w["ln1"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "ln2": {"scale": w["ln2"]},
            "moe": {"router": w["router"].astype(jnp.float32),
                    "wi": jnp.concatenate([e["gate"], e["up"]], axis=-1),
                    "wo": e["down"]},
        }

    def cast(path, a):   # the router stays float32: the program routes in it
        return a if path[-1].key == "router" else a.astype(dtype)

    params = {
        "embed": {"w": _pad_rows(embedding(key, dims), vocab_padded)},
        "final_norm": {"scale": final_norm(key, dims)},
        # period position p holds layers p, p + period, ... (the scan's
        # blocks in order)
        "blocks": [jax.lax.map(one, jnp.arange(p, dims.n_layers, period))
                   for p in range(period)],
        "lm_head": {"w": _pad_rows(lm_head(key, dims), vocab_padded)},
    }
    return jax.tree_util.tree_map_with_path(cast, params)


def make_params(c: dict, seed: int, cfg):
    """The program's parameter tree for config ``c`` from ``seed``: one
    jitted call, on the default device, in the served type."""
    return _make(root_key(seed), Dims.from_config(c), cfg.vocab_padded,
                 len(cfg.attn_pattern), cfg.dtype)
