"""Witness, on the chip, of the fault that keeps the chat cells out of
``BENCHMARK.json``: the batcher left-pads a prompt shorter than
``prompt_len`` with token 0 and attends to the padding.

    python chipbench/witness_padding.py --seeds 1,2,3

At the size of the issue's ``qwen3-0.6b.chat-flood`` cell (the whole
Qwen3-0.6B in bfloat16, 32 slots, ``prompt_len`` 512, ``max_len`` 768, paged,
Pallas), one process serves, per seed, 32 greedy requests at once: 30 with
prompt lengths drawn as that cell drew them (lognormal, median 128, sigma
0.7, 32 to 512) and 2 of exactly 512 tokens.  For a sample of them the
plain reference (the qwen3 family's) reads the widest gap of the served
tokens twice: on the prompt as sent, and on the row as the batcher
padded it.  One JSON line per seed: the widest gap of the short prompts
against each, and of the full-length ones.  A sound batcher reads alike on
both for every prompt; this one departs on the prompt as sent only where it
padded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

PROMPT_LEN, MAX_NEW, SHORT, FULL, COMPARED = 512, 16, 30, 2, 6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("witness_padding.py: no TPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(run.ROOT / "src"))
    import family

    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher, Request

    run.use_compile_cache()
    config = json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())
    fam = family.load(config)
    cfg, dims = fam.model_config(config), fam.Dims.from_config(config)
    scfg = ServingConfig(slots=SHORT + FULL, prompt_len=PROMPT_LEN,
                         max_len=768, attn_impl="pallas", paged=True,
                         page_size=16)
    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        lens = np.clip(np.rint(rng.lognormal(np.log(128), 0.7, SHORT)),
                       32, PROMPT_LEN - 1).astype(int)
        prompts = [rng.integers(1, dims.vocab, size=n, dtype=np.int32)
                   for n in lens] + \
                  [rng.integers(1, dims.vocab, size=PROMPT_LEN,
                                dtype=np.int32) for _ in range(FULL)]
        params = fam.make_params(config, seed, cfg)
        b = ContinuousBatcher(params, cfg, scfg)
        reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW)
                for i, p in enumerate(prompts)]
        for r in reqs:
            b.submit(r)
        b.run()
        outs = [list(r.out) for r in reqs]
        del b, params
        picked = list(range(COMPARED)) + list(range(SHORT, SHORT + FULL))
        sent = fam.served_gaps(
            dims, seed, [(prompts[i], outs[i]) for i in picked])
        rows = [np.concatenate([np.zeros(PROMPT_LEN - len(prompts[i]),
                                         np.int32), prompts[i]])
                for i in picked]
        padded = fam.served_gaps(
            dims, seed, [(rows[j], outs[i]) for j, i in enumerate(picked)])
        short, full = slice(0, COMPARED), slice(COMPARED, None)
        print(json.dumps({
            "seed": seed,
            "short_lens": [int(lens[i]) for i in range(COMPARED)],
            "short_vs_sent": float(max(g.max() for g in sent[short])),
            "short_vs_padded_row": float(max(g.max() for g in padded[short])),
            "full_vs_sent": float(max(g.max() for g in sent[full]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
