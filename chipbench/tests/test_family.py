"""A configuration's ``model_type`` picks its family
(``chipbench/family.py``), and the qwen3 family makes the same numbers it
made before it moved under ``chipbench/families/``.

The golden values were recorded from ``chipbench/model.py`` and
``chipbench/reference.py`` as they stood before the move, on the CPU, at a
2-layer, d 128 shape and seed 7.  The weights are bfloat16 numbers summed in
float64 (a redraw, a swapped tensor or a reordered leaf moves them by a
percent; the tolerance admits a last-place difference of the CPU's normal
sampler in a few elements); the logits are float32.
"""

import json

import numpy as np
import pytest
from conftest import BENCH, tiny_config

import family

SEED = 7

#: leaf -> (shape, sum of squares, sum of |x| weighted by 1 + (i mod 17))
GOLDEN_PARAMS = {
    "['blocks'][0]['attn']['k_norm']['scale']":
        ((2, 32), 62.52388000488281, 545.82421875),
    "['blocks'][0]['attn']['q_norm']['scale']":
        ((2, 32), 65.935546875, 553.94140625),
    "['blocks'][0]['attn']['wk']":
        ((2, 128, 64), 128.29892786793255, 10398.280565232038),
    "['blocks'][0]['attn']['wo']":
        ((2, 128, 128), 253.78164247645068, 20670.01745697856),
    "['blocks'][0]['attn']['wq']":
        ((2, 128, 128), 254.45256141030808, 20722.85138091445),
    "['blocks'][0]['attn']['wv']":
        ((2, 128, 64), 127.86673512616007, 10477.509939730167),
    "['blocks'][0]['ln1']['scale']":
        ((2, 128), 262.6312561035156, 2302.48046875),
    "['blocks'][0]['ln2']['scale']":
        ((2, 128), 257.9290771484375, 2295.66015625),
    "['blocks'][0]['mlp']['wi']":
        ((2, 128, 512), 1030.8671654323311, 83421.13384214044),
    "['blocks'][0]['mlp']['wo']":
        ((2, 256, 128), 254.0596895782499, 29325.082498557866),
    "['embed']['w']": ((1024, 128), 51.33152979343062, 18390.800502477214),
    "['final_norm']['scale']": ((128,), 128.23046875, 1103.64453125),
}
GOLDEN_TOKENS = [944, 625, 684, 897, 578, 775, 833, 225, 56, 300, 285, 873]
GOLDEN_ROWS = [0, 5, 11]
GOLDEN_LOGITS = {
    "argmax": [944, 863, 863],
    "max": [0.7725541591644287, 0.6588266491889954, 0.857931911945343],
    "sumsq": [50.855958625831576, 49.40645111229853, 47.75389559279098],
    "first8": [
        [-0.1904403567314148, 0.06591180711984634, 0.12104543298482895,
         0.3296612799167633, -0.012466268613934517, 0.06539272516965866,
         -0.06008287891745567, -0.10543644428253174],
        [-0.05072999745607376, 0.08450297266244888, -0.09570802003145218,
         0.1280924677848816, -0.02053746022284031, 0.145851731300354,
         0.0155904246494174, -0.018977448344230652],
        [-0.07011491805315018, -0.15254107117652893, -0.1402498334646225,
         0.17313314974308014, -0.036361679434776306, 0.22780963778495789,
         0.02051737532019615, 0.06551900506019592]],
}

#: a family that exists only in a test's directory: sizes with ``vocab``,
#: stubs, and a helper it takes from the qwen3 family by import
TOY = '''
import dataclasses

import numpy as np

from families.qwen3 import reference as q3

from . import flops


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int

    @classmethod
    def from_config(cls, c):
        return cls(vocab=c["vocab_size"])


def model_config(c):
    return c["name"]


def make_params(c, seed, cfg):
    return {"seed": seed}


def served_gaps(dims, seed, pairs):
    return [q3.token_gaps(np.zeros((len(o), dims.vocab)), o)
            for _, o in pairs]


control_gaps = served_gaps
'''
TOY_FLOPS = '''
def token_flops(dims, ctx, *, logits):
    return 1.0


def decode_flops(dims, ctxs):
    return float(len(ctxs))


def prefill_flops(dims, start, end):
    return float(end - start)


def decode_attn_work(dims, ctx, page_size):
    return 1.0, 1.0
'''


def qwen3_config():
    return json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())


def test_model_type_picks_the_qwen3_family():
    import families.qwen3

    fam = family.load(qwen3_config())
    assert fam is families.qwen3
    assert fam.Dims.from_config(qwen3_config()).vocab == 151936
    for name in ("model_config", "make_params", "served_gaps",
                 "control_gaps"):
        assert callable(getattr(fam, name))
    for name in ("decode_flops", "prefill_flops", "decode_attn_work",
                 "token_flops"):
        assert callable(getattr(fam.flops, name))


def test_unknown_model_type_lists_the_families():
    with pytest.raises(SystemExit, match=r"'nosuch'.*present: .*qwen3"):
        family.load(dict(qwen3_config(), model_type="nosuch"))


def test_new_family_is_found_as_new_files(tmp_path):
    (tmp_path / "toy").mkdir()
    (tmp_path / "toy" / "__init__.py").write_text(TOY)
    (tmp_path / "toy" / "flops.py").write_text(TOY_FLOPS)
    config = {"name": "toy-1", "model_type": "toy", "vocab_size": 7}
    fam = family.load(config, root=tmp_path)
    dims = fam.Dims.from_config(config)
    assert dims.vocab == 7
    assert fam.make_params(config, 3, fam.model_config(config)) == {
        "seed": 3}
    (g,) = fam.served_gaps(dims, 3, [([1, 2], [4, 5])])
    assert (g == 0).all()
    assert fam.flops.decode_flops(dims, [10, 11]) == 2.0
    with pytest.raises(SystemExit, match=r"present: toy$"):
        family.load(dict(config, model_type="qwen3"), root=tmp_path)


def test_weights_match_the_golden_values():
    import jax

    c = tiny_config(hidden_size=128, intermediate_size=256)
    fam = family.load(c)
    params = fam.make_params(c, SEED, fam.model_config(c))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert sorted(jax.tree_util.keystr(p) for p, _ in leaves) == \
        sorted(GOLDEN_PARAMS)
    for path, leaf in leaves:
        shape, sumsq, weighted = GOLDEN_PARAMS[jax.tree_util.keystr(path)]
        x = np.asarray(leaf, np.float64).ravel()
        assert str(leaf.dtype) == "bfloat16" and leaf.shape == shape
        np.testing.assert_allclose(
            [(x * x).sum(), np.abs(x) @ (np.arange(x.size) % 17 + 1)],
            [sumsq, weighted], rtol=1e-6, err_msg=jax.tree_util.keystr(path))


def test_reference_logits_match_the_golden_values():
    from families.qwen3 import reference

    c = tiny_config(hidden_size=128, intermediate_size=256)
    dims = family.load(c).Dims.from_config(c)
    lg = reference.logits(dims, SEED, GOLDEN_TOKENS, GOLDEN_ROWS)
    assert lg.argmax(-1).tolist() == GOLDEN_LOGITS["argmax"]
    np.testing.assert_allclose(lg.max(-1), GOLDEN_LOGITS["max"], rtol=1e-5)
    np.testing.assert_allclose((lg.astype(np.float64) ** 2).sum(-1),
                               GOLDEN_LOGITS["sumsq"], rtol=1e-5)
    np.testing.assert_allclose(lg[:, :8], GOLDEN_LOGITS["first8"],
                               rtol=1e-5, atol=1e-6)
