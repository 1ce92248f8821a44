"""Operation and byte counts against hand counts for one layer of
``qwen3-0.6b``, and of Qwen3-32B's wider, untied shape."""

import json

import pytest
from conftest import BENCH

from families.qwen3 import flops
from families.qwen3.weights import Dims


#: Qwen3-32B's widths (huggingface.co/Qwen/Qwen3-32B config.json), over
#: the qwen3-0.6b file: an untied head and a wide MLP
QWEN3_32B = {"hidden_size": 5120, "num_attention_heads": 64,
             "num_key_value_heads": 8, "head_dim": 128,
             "intermediate_size": 25600, "tie_word_embeddings": False}


def dims(name, **over):
    c = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    if name == "qwen3-32b":
        c.update(QWEN3_32B)
    c.update(over)
    return Dims.from_config(c)


@pytest.mark.parametrize("name, per_layer", [
    # q 1024x2048, k and v 1024x1024, o 2048x1024, gate/up 1024x3072,
    # down 3072x1024
    ("qwen3-0.6b", 2097152 + 2 * 1048576 + 2097152 + 3 * 3145728),
    # q 5120x8192, k and v 5120x1024, o 8192x5120, gate/up/down 5120x25600
    ("qwen3-32b", 41943040 + 2 * 5242880 + 41943040 + 3 * 131072000),
])
def test_layer_params_by_hand(name, per_layer):
    assert flops.layer_matmul_params(dims(name)) == per_layer


def test_one_layer_decode_token_by_hand():
    d = dims("qwen3-0.6b", num_hidden_layers=1)
    ctx = 4097
    attn = 2 * ctx * 128 * 16 * 2            # scores and values, 16 heads
    head = 2 * 151936 * 1024
    assert flops.token_flops(d, ctx, logits=True) == \
        2 * flops.layer_matmul_params(d) + attn + head


def test_one_layer_prefill_by_hand():
    d = dims("qwen3-32b", num_hidden_layers=1)
    # positions 2..4 of a prompt whose first two came from the cache:
    # contexts 3, 4, 5
    want = (3 * 2 * flops.layer_matmul_params(d)
            + sum(4 * c * 64 * 128 for c in (3, 4, 5))
            + 2 * 151936 * 5120)
    assert flops.prefill_flops(d, 2, 5) == want


def test_decode_attention_work_by_hand():
    d = dims("qwen3-0.6b", num_hidden_layers=1)
    ops, nbytes = flops.decode_attn_work(d, 33, 16)
    assert ops == 4 * 33 * 16 * 128
    # three 16-token pages of K and V, 8 kv heads x 128, bf16; q and out
    assert nbytes == 2 * 3 * 16 * 8 * 128 * 2 + 2 * 16 * 128 * 2
