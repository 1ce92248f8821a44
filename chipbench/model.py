"""The configuration file as the serving program takes it.

``model_config`` maps a Hugging Face ``config.json`` (as kept under
``chipbench/configs/``) onto the program's ``ModelConfig``; ``make_params``
packs the weights of ``chipbench/weights.py`` into the program's parameter
tree, on the device, in one jitted call.  This is the only module of the
benchmark that knows the program's parameter layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import Dims, embedding, final_norm, layer_weights, lm_head, \
    root_key

#: model types this adapter can map; a new family adds its mapping here
SUPPORTED = ("qwen3",)


def model_config(c: dict):
    from repro.configs.base import ModelConfig

    if c["model_type"] not in SUPPORTED:
        raise ValueError(f"no mapping for model_type {c['model_type']!r}")
    if c.get("hidden_act", "silu") != "silu" or c.get("attention_bias"):
        raise ValueError("only SwiGLU, bias-free Qwen3 blocks are mapped")
    if c.get("use_sliding_window") or c.get("rope_scaling"):
        raise ValueError("sliding windows and rope scaling are not mapped")
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], d_head=c["head_dim"], qk_norm=True,
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


def _pad_rows(w, rows):
    return jnp.pad(w, ((0, rows - w.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, dims: Dims, vocab_padded: int, dtype: str = "bfloat16"):
    def one(layer):
        w = layer_weights(key, layer, dims)
        return {
            "ln1": {"scale": w["ln1"]},
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"], "q_norm": {"scale": w["q_norm"]},
                     "k_norm": {"scale": w["k_norm"]}},
            "ln2": {"scale": w["ln2"]},
            "mlp": {"wi": jnp.concatenate([w["gate"], w["up"]], axis=-1),
                    "wo": w["down"]},
        }

    params = {
        "embed": {"w": _pad_rows(embedding(key, dims), vocab_padded)},
        "final_norm": {"scale": final_norm(key, dims)},
        "blocks": [jax.lax.map(one, jnp.arange(dims.n_layers))],
    }
    if not dims.tied:
        params["lm_head"] = {"w": _pad_rows(lm_head(key, dims), vocab_padded)}
    # the numbers are bfloat16 whatever the served type (the reference
    # makes the same ones); a float32 configuration serves them widened
    return jax.tree.map(lambda a: a.astype(dtype), params)


def make_params(c: dict, seed: int, cfg):
    """The program's parameter tree for config ``c`` from ``seed``: one
    jitted call, on the default device, in the served type."""
    return _make(root_key(seed), Dims.from_config(c), cfg.vocab_padded,
                 cfg.dtype)
