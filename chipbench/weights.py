"""Random weights of a Qwen3-style decoder, made from a seed.

The benchmark's own generator: the harness packs what it makes into the
layout the serving program takes (``chipbench/model.py``), and the plain
reference (``chipbench/reference.py``) makes the same numbers again, layer by
layer, without touching the program.  Every tensor is drawn from its own key,
``fold_in`` of the seed's key with a fixed (group, layer, tensor) path, as
float32 normals scaled and rounded to the served dtype, bfloat16.

Scales: projections 1/sqrt(fan_in); embedding and LM head 0.02 (the
configs' ``initializer_range``); RMSNorm scales 1 + 0.1 N(0, 1), so that a
norm whose scale is dropped or misplaced changes the logits.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

_LAYER, _EMBED, _FINAL_NORM, _HEAD = 1, 2, 3, 4
#: tensor ids inside one layer; the order is part of the seed's meaning
LAYER_TENSORS = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                 "gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference and the generator need, from a config file."""

    d: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    n_layers: int
    tied: bool
    eps: float
    theta: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        return cls(d=c["hidden_size"], n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], d_head=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   n_layers=c["num_hidden_layers"],
                   tied=bool(c["tie_word_embeddings"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]))

    def layer_shapes(self):
        d, q, kv = self.d, self.n_heads * self.d_head, self.n_kv * self.d_head
        return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "wo": (q, d), "q_norm": (self.d_head,),
                "k_norm": (self.d_head,), "ln2": (d,),
                "gate": (d, self.d_ff), "up": (d, self.d_ff),
                "down": (self.d_ff, d)}


def root_key(seed: int):
    """The key of a seed of up to 64 bits (the low word seeds, the high word
    is folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm_scale(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def layer_weights(key, layer, dims: Dims, dtype=jnp.bfloat16):
    """Layer ``layer``'s tensors (a dict by :data:`LAYER_TENSORS`);
    ``layer`` may be traced."""
    lkey = jax.random.fold_in(jax.random.fold_in(key, _LAYER), layer)
    out = {}
    for i, (name, shape) in enumerate(dims.layer_shapes().items()):
        k = jax.random.fold_in(lkey, i)
        if len(shape) == 1:
            out[name] = _norm_scale(k, shape, dtype)
        else:
            out[name] = _normal(k, shape, shape[0] ** -0.5, dtype)
    return out


def embedding(key, dims: Dims, dtype=jnp.bfloat16):
    """(vocab, d) input embedding; also the LM head when tied."""
    return _normal(jax.random.fold_in(key, _EMBED), (dims.vocab, dims.d),
                   0.02, dtype)


def lm_head(key, dims: Dims, dtype=jnp.bfloat16):
    """(vocab, d) output projection (logits = h @ head.T)."""
    if dims.tied:
        return embedding(key, dims, dtype)
    return _normal(jax.random.fold_in(key, _HEAD), (dims.vocab, dims.d),
                   0.02, dtype)


def final_norm(key, dims: Dims, dtype=jnp.bfloat16):
    return _norm_scale(jax.random.fold_in(key, _FINAL_NORM), (dims.d,), dtype)
