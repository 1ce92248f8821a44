"""The one traffic generator: a seeded open-loop schedule from a mix file.

A mix (``chipbench/traffic/<name>.json``) holds only parameters:

* ``arrivals``: ``{"process": "poisson", "knee_per_s": r, "load": x}`` --
  requests arrive as a Poisson process at ``x * r`` per second;
* ``prompt``: either ``{"documents": n, "doc_tokens": t, "zipf_s": s,
  "question_tokens": q}`` (every prompt is one of ``n`` shared documents,
  picked by Zipf(s) over their ranks, followed by a ``q``-token question of
  its own) or a length distribution (below);
* ``output``: a length distribution, the number of tokens each request
  decodes;
* ``namespace``: the prefix-cache namespace of every request, or null;
* ``base_seed``, ``horizon_s``: see below.

A length distribution is ``{"dist": "fixed", "tokens": n}`` or
``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
(rounded, clipped to [a, b]).

Sizes and arrivals are the same for every seed.  The request sizes (prompt
and output lengths, document ranks) and the arrival times are drawn once,
from the mix's ``base_seed``, for ``horizon_s`` seconds of arrivals at the
offered rate; ``--seed`` then shuffles the order of the sizes within
consecutive blocks of ``shuffle_block`` requests, assigns the documents to
ranks, and draws every token.  So two seeds offer the same work at the same
times, in another order block by block, and a window holds nearly the same
work whatever the seed: a burst of arrivals, which sets the latency tail,
comes at the same time for every seed.  One seed always offers the same
requests.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""

    index: int
    arrival_s: float            # offset from the start of the traffic
    prompt: np.ndarray          # (S,) int32
    max_new: int
    namespace: Optional[str]
    doc: int                    # document id, or -1


def rate_per_s(mix: dict) -> float:
    a = mix["arrivals"]
    if a["process"] != "poisson":
        raise ValueError(f"unknown arrival process {a['process']!r}")
    return float(a["knee_per_s"]) * float(a["load"])


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["tokens"]), dtype=np.int64)
    if dist["dist"] == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], size=n)
        return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def zipf_ranks(n_docs: int, s: float, n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` document ranks in [0, n_docs), P(rank k) proportional to
    (k + 1) ** -s."""
    p = np.arange(1, n_docs + 1, dtype=np.float64) ** -float(s)
    return rng.choice(n_docs, size=n, p=p / p.sum())


def block_permutation(n: int, block: int,
                      rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each index only within its
    block of ``block`` consecutive indices."""
    return np.concatenate([i + rng.permutation(min(block, n - i))
                           for i in range(0, n, block)])


def n_requests(mix: dict, rate: Optional[float] = None) -> int:
    rate = rate_per_s(mix) if rate is None else rate
    return int(np.ceil(rate * float(mix["horizon_s"]))) + 1


def schedule(mix: dict, seed: int, vocab: int,
             rate: Optional[float] = None) -> List[Planned]:
    """The mix's requests for ``seed``, in arrival order.  ``rate``
    overrides the offered rate (the knee sweep)."""
    rate = rate_per_s(mix) if rate is None else float(rate)
    n = n_requests(mix, rate)
    base = np.random.default_rng(int(mix["base_seed"]))
    gaps = base.exponential(1.0 / rate, size=n)
    outs = draw_lengths(mix["output"], n, base)
    p = mix["prompt"]
    docs = "documents" in p
    if docs:
        ranks = zipf_ranks(p["documents"], p["zipf_s"], n, base)
        lens = np.full(n, p["doc_tokens"] + p["question_tokens"])
    else:
        ranks = np.full(n, -1)
        lens = draw_lengths(p, n, base)

    rng = np.random.default_rng(int(seed))
    order = block_permutation(n, int(mix["shuffle_block"]), rng)
    outs, ranks, lens = outs[order], ranks[order], lens[order]
    doc_of_rank = rng.permutation(p["documents"]) if docs else None
    documents = (rng.integers(1, vocab, size=(p["documents"], p["doc_tokens"]),
                              dtype=np.int32) if docs else None)
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(n):
        if docs:
            doc = int(doc_of_rank[ranks[i]])
            question = rng.integers(1, vocab, size=p["question_tokens"],
                                    dtype=np.int32)
            prompt = np.concatenate([documents[doc], question])
        else:
            doc = -1
            prompt = rng.integers(1, vocab, size=int(lens[i]), dtype=np.int32)
        out.append(Planned(index=i, arrival_s=float(arrivals[i]),
                           prompt=prompt, max_new=int(outs[i]),
                           namespace=mix.get("namespace"), doc=doc))
    return out
