"""Smoke test of the serving path on a TPU chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host: tp=4 and re-mesh

One chip, two phases:

* **serve** — ``qwen3-0.6b`` at its published widths (28 layers, d_model
  1024, 16 query / 8 KV heads x 128, vocab 151,936) in bf16, weights from
  ``init_params`` and ``--seed``, served through
  ``ContinuousBatcher(ServingConfig(paged=True, prefix_cache=True,
  attn_impl="pallas"))``: 16 requests over 8 slots, prompts of 256-1024
  tokens, half of them sharing a 768-token page-aligned prefix (the second
  wave of those is admitted through the prefix kernel), 33 new tokens each.
  Checks every request completes with its full budget, the prefix cache
  hit, and the compiled decode chunk holds a Mosaic kernel
  (``tpu_custom_call``).
* **identity** — a short shared-prefix trace served twice in float32, with
  ``attn_impl="xla"`` and ``"pallas"``; the greedy tokens must be identical.

``--four-chips`` runs only the tensor-parallel phase: the same model in
float32 at ``ServingConfig(paged=True, tp=4)`` over a lease of four
distinct chips against ``tp=1`` on the same requests, plus one live
``remesh`` 1 -> 4 in the middle of a stream; all three must emit identical
greedy tokens.

Exits non-zero, printing no result, when JAX finds no TPU (a silent CPU
fallback included) or any check fails.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ARCH = "qwen3-0.6b"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds XLA spent compiling (persistent-cache reads included), and
    the number of compiles, read off JAX's own monitoring events.  Tracing
    and lowering are left out: their events nest (an inner jit is traced
    inside the outer trace), so summing them would count time twice."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark) -> str:
        s, c, h = mark
        return (f"backend_compile_s={self.seconds - s:.1f} "
                f"compiles={self.compiles - c} cache_hits={self.cache_hits - h}")


def make_requests(vocab, seed, *, n, prompt_len, shared, max_new):
    """``n`` requests alternating between a shared-prefix group (full
    ``prompt_len`` prompts whose first ``shared`` tokens agree, namespace
    ``"shared"``) and unshared prompts of random length."""
    import numpy as np

    from repro.serving.batcher import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, size=shared)
    out = []
    for i in range(n):
        if i % 2 == 0:
            tail = rng.integers(1, vocab, size=prompt_len - shared)
            prompt, ns = np.concatenate([prefix, tail]), "shared"
        else:
            size = int(rng.integers(prompt_len // 4, prompt_len + 1))
            prompt, ns = rng.integers(1, vocab, size=size), None
        out.append(Request(rid=i, prompt=prompt.astype(np.int32),
                           max_new=max_new, namespace=ns))
    return out


def serve(params, cfg, config, requests, *, mesh=None, before_run=None):
    """Serve ``requests`` to completion; return (batcher, tokens per request)."""
    from repro.serving.batcher import ContinuousBatcher

    b = ContinuousBatcher(params, cfg, config, mesh=mesh)
    for r in requests:
        b.submit(r)
    if before_run is not None:
        before_run(b)
    b.run()
    for r in requests:
        if not r.done or len(r.out) != r.max_new:
            raise SystemExit(
                f"chip_smoke: request {r.rid} ended with {len(r.out)} of "
                f"{r.max_new} tokens (done={r.done})")
    return b, [list(map(int, r.out)) for r in requests]


def phase_serve(cfg, seed, clock) -> None:
    import jax

    from repro.models import init_params
    from repro.serving import ServingConfig
    from repro.serving.engine import paged_decode_chunk_program

    mark = clock.mark()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    config = ServingConfig(slots=8, prompt_len=1024, max_len=1088, chunk=8,
                           attn_impl="pallas", paged=True, page_size=16,
                           prefix_cache=True)
    requests = make_requests(cfg.vocab, seed, n=16, prompt_len=1024,
                             shared=768, max_new=33)
    t0 = time.perf_counter()
    b, toks = serve(params, cfg, config, requests)
    wall = time.perf_counter() - t0
    st = b.stats
    if st.completed != len(requests) or st.poisoned_slots:
        raise SystemExit(f"chip_smoke: serve phase stats {st}")
    if not st.prefix_hits:
        raise SystemExit("chip_smoke: no prefix-cache hit: the prefix "
                         "admission kernel never ran")
    if any(not 0 <= t < cfg.vocab for row in toks for t in row):
        raise SystemExit("chip_smoke: a token outside the vocabulary")
    log(f"serve: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.d_head} "
        f"vocab={cfg.vocab} dtype={cfg.dtype} slots={config.slots} "
        f"page_size={config.page_size} attn_impl={config.attn_impl}")
    log(f"serve: requests_completed={st.completed} "
        f"tokens_emitted={st.tokens} prefix_hits={st.prefix_hits} "
        f"prefill_tokens_skipped={st.prefill_tokens_skipped} "
        f"chunks={st.chunks} {clock.since(mark)} "
        f"wall_s_incl_compile={wall:.1f}")
    chunk = paged_decode_chunk_program(cfg, b.scfg, b.chunk, b.page_size)
    hlo = chunk.lower(b.params, b.caches, b.state, b.pages,
                      jax.random.PRNGKey(0)).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"serve: decode chunk HLO contains tpu_custom_call: {has_kernel}")
    if not has_kernel:
        raise SystemExit("chip_smoke: the decode chunk has no Pallas kernel")


def phase_identity(cfg32, seed, clock) -> None:
    import jax

    from repro.models import init_params
    from repro.serving import ServingConfig

    mark = clock.mark()
    params = init_params(cfg32, jax.random.PRNGKey(seed))
    out = {}
    for impl in ("xla", "pallas"):
        config = ServingConfig(slots=4, prompt_len=256, max_len=288, chunk=8,
                               attn_impl=impl, paged=True, page_size=16,
                               prefix_cache=True)
        requests = make_requests(cfg32.vocab, seed, n=8, prompt_len=256,
                                 shared=128, max_new=17)
        b, out[impl] = serve(params, cfg32, config, requests)
        if not b.stats.prefix_hits:
            raise SystemExit(f"chip_smoke: identity phase ({impl}) had no "
                             "prefix-cache hit")
    same = out["xla"] == out["pallas"]
    n_tok = sum(map(len, out["pallas"]))
    log(f"identity: float32 greedy tokens xla == pallas: {same} "
        f"({len(out['pallas'])} requests, {n_tok} tokens) {clock.since(mark)}")
    if not same:
        for i, (a, p) in enumerate(zip(out["xla"], out["pallas"])):
            if a != p:
                log(f"identity: request {i} xla={a} pallas={p}")
        raise SystemExit("chip_smoke: xla and pallas tokens differ")


def phase_four_chips(cfg32, seed, clock) -> None:
    import jax

    from repro.models import init_params
    from repro.serving import ServingConfig
    from repro.serving.tenancy import VirtualAcceleratorPool

    mark = clock.mark()
    pool = VirtualAcceleratorPool(devices=jax.devices()[:4],
                                  devices_per_core=1)
    mesh = pool.tp_mesh_for(pool.lease("wide", 4))
    ids = sorted(int(d.id) for d in mesh.devices.flat)
    log(f"four-chips: tp sub-mesh devices {ids}")
    if len(set(ids)) != 4:
        raise SystemExit(f"chip_smoke: the tp=4 sub-mesh spans devices {ids}")
    params = init_params(cfg32, jax.random.PRNGKey(seed))

    def config(tp):
        return ServingConfig(slots=4, prompt_len=256, max_len=320, chunk=8,
                             paged=True, page_size=16, tp=tp)

    def reqs():
        return make_requests(cfg32.vocab, seed, n=8, prompt_len=256,
                             shared=128, max_new=33)

    _, ref = serve(params, cfg32, config(1), reqs())
    b4, tp4 = serve(params, cfg32, config(4), reqs(), mesh=mesh)
    held = {int(d.id) for leaf in jax.tree.leaves(b4.params)
            for d in leaf.devices()}
    log(f"four-chips: tp=4 == tp=1 greedy tokens: {tp4 == ref} "
        f"(params on devices {sorted(held)})")

    def move_mid_stream(b):
        b.step()
        b.step()
        b.remesh(mesh=mesh)

    bm, moved = serve(params, cfg32, config(1), reqs(),
                      before_run=move_mid_stream)
    log(f"four-chips: live remesh 1->4 tokens identical: {moved == ref} "
        f"(tp={bm.tp}, remeshes={bm.stats.remeshes}) {clock.since(mark)}")
    if held != set(ids):
        raise SystemExit(f"chip_smoke: tp=4 params live on {sorted(held)}")
    if tp4 != ref or moved != ref:
        raise SystemExit("chip_smoke: tp=4 or re-meshed tokens differ "
                         "from tp=1")
    if bm.tp != 4 or bm.stats.remeshes != 1:
        raise SystemExit("chip_smoke: the live remesh did not happen")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 / live re-mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    need = 4 if args.four_chips else 1

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU", file=sys.stderr)
        return 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config
    from repro.kernels.common import default_interpret
    from repro.launch.runtime import use_compile_cache

    if default_interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode on "
              "the TPU", file=sys.stderr)
        return 1
    log(f"device_kind: {dev.device_kind} platform={dev.platform} "
        f"count={len(devices)}")
    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    # the float32 comparisons run at full f32 matmul precision: the TPU's
    # default rounds f32 operands to bf16 on the MXU, and that rounding,
    # not a fault, would then decide near-ties between the two sides
    f32_precision = jax.default_matmul_precision("highest")
    if args.four_chips:
        with f32_precision:
            phase_four_chips(cfg32, args.seed, clock)
    else:
        phase_serve(cfg, args.seed, clock)
        log(f"peak_bytes_in_use: "
            f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        with f32_precision:
            phase_identity(cfg32, args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
