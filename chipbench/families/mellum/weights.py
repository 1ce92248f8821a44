"""Random weights of a Mellum2 decoder's expert share, made from a seed.

The sizes come from the configuration file (``Dims.from_config``): the
published widths, the layer pattern (``layer_types``, each layer's window
and RoPE from ``rope_parameters``), and the expert share this chip holds,
``expert_parallel`` (experts ``[first_expert, first_expert + held)`` of the
router's ``published_num_experts``; ``num_experts`` is the held count).

Every tensor is drawn as the Qwen3 family's are (``families.qwen3.weights``:
the seed's :func:`root_key`, float32 normals rounded to bfloat16, norm
scales ``1 + 0.1 N(0, 1)``), from a ``fold_in`` path of its own.  An
expert's tensors fold in the expert's published index, so a share holds the
same numbers whichever experts sit beside it.  Embedding, final norm and
LM head are the Qwen3 family's own (``embedding``, ``final_norm``,
``lm_head``), so its reference's ``embed`` and ``head`` serve here too.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from families.qwen3.weights import norm_scale, normal

_LAYER = 1              # the Qwen3 family's layer group
#: tensor ids inside one layer; the order is part of the seed's meaning
LAYER_TENSORS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router")
EXPERT_TENSORS = ("gate", "up", "down")
_EXPERTS = 100          # fold_in id of the experts under a layer's key


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference, the generator and the counts need."""

    d: int
    n_heads: int
    n_kv: int
    d_head: int
    vocab: int
    n_layers: int
    eps: float
    windows: Tuple[Optional[int], ...]     # per layer; None = full
    #: per layer: (theta, yarn) with yarn None or (factor, original
    #: positions, beta_fast, beta_slow, attention_factor)
    ropes: Tuple[tuple, ...]
    n_experts: int                          # the router's outputs
    top_k: int
    d_expert: int
    first_expert: int
    held: int
    tied: bool = False

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        rp = c["rope_parameters"]
        ropes, windows = [], []
        for kind in c["layer_types"]:
            r = rp[kind]
            yarn = None
            if r.get("rope_type", "default") == "yarn":
                yarn = (float(r["factor"]),
                        int(r["original_max_position_embeddings"]),
                        float(r["beta_fast"]), float(r["beta_slow"]),
                        float(r["attention_factor"]))
            ropes.append((float(r["rope_theta"]), yarn))
            windows.append(int(c["sliding_window"])
                           if kind == "sliding_attention" else None)
        ep = c["expert_parallel"]
        return cls(d=c["hidden_size"], n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"], d_head=c["head_dim"],
                   vocab=c["vocab_size"], n_layers=c["num_hidden_layers"],
                   eps=float(c["rms_norm_eps"]), windows=tuple(windows),
                   ropes=tuple(ropes),
                   n_experts=int(ep["published_num_experts"]),
                   top_k=c["num_experts_per_tok"],
                   d_expert=c["moe_intermediate_size"],
                   first_expert=int(ep["first_expert"]),
                   held=int(c["num_experts"]),
                   tied=bool(c["tie_word_embeddings"]))

    def layer_shapes(self):
        d, q, kv = self.d, self.n_heads * self.d_head, self.n_kv * self.d_head
        return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
                "wo": (q, d), "ln2": (d,), "router": (d, self.n_experts)}

    def expert_shapes(self):
        d, f = self.d, self.d_expert
        return {"gate": (d, f), "up": (d, f), "down": (f, d)}


def _layer_key(key, layer):
    return jax.random.fold_in(jax.random.fold_in(key, _LAYER), layer)


def layer_weights(key, layer, dims: Dims, dtype=jnp.bfloat16):
    """Layer ``layer``'s attention, norms and router (a dict by
    :data:`LAYER_TENSORS`); ``layer`` may be traced."""
    lkey = _layer_key(key, layer)
    out = {}
    for i, (name, shape) in enumerate(dims.layer_shapes().items()):
        k = jax.random.fold_in(lkey, i)
        if len(shape) == 1:
            out[name] = norm_scale(k, shape, dtype)
        else:
            out[name] = normal(k, shape, shape[0] ** -0.5, dtype)
    return out


def expert_weights(key, layer, expert, dims: Dims, dtype=jnp.bfloat16):
    """Published expert ``expert`` of layer ``layer``: gate, up, down (a
    dict by :data:`EXPERT_TENSORS`); both may be traced."""
    ekey = jax.random.fold_in(
        jax.random.fold_in(_layer_key(key, layer), _EXPERTS), expert)
    return {name: normal(jax.random.fold_in(ekey, i), shape,
                         shape[0] ** -0.5, dtype)
            for i, (name, shape) in enumerate(dims.expert_shapes().items())}


def held_experts(key, layer, dims: Dims, dtype=jnp.bfloat16):
    """The held experts of layer ``layer``, each tensor stacked
    (held, ...) in the order of their published indices."""
    ids = dims.first_expert + jnp.arange(dims.held)
    return jax.vmap(lambda e: expert_weights(key, layer, e, dims, dtype))(ids)
