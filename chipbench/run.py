"""Chip benchmark of the serving path: one cell, one seed, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs[].file``) and
a traffic mix (``chipbench/traffic/<traffic>.json``); the configuration's
``model_type`` names its family (``chipbench/families/<model_type>/``), and
its per-layer metrics are read by ``chipbench/metrics/<name>.py``.  Nothing
here is specific to a cell or a model.

One run, in one process:

1. finds the chips the cell asks for, or exits 1 with no result;
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``
   (or ``$JAX_COMPILATION_CACHE_DIR``);
3. makes the weights on the device from ``--seed`` in one jitted call;
4. warms up every program shape the cell's traffic can use, on a batcher
   of its own: each decode chunk length, each admission batch bucket, cold
   and at the documents' cached depth, and the page-push widths;
5. on a fresh batcher, fills the prefix cache with the mix's documents,
   then offers the traffic open-loop from ``t = 0``; after ``preroll_s``
   seconds the measured window opens.  Everything until then is
   ``setup_s``.  With ``--trace 1`` the window is the first ``trace_s``
   seconds, under the profiler;
6. reads the peak device memory, frees the program's state, and compares
   a sample of the served requests with the plain reference of the
   configuration's family (``chipbench/family.py``);
7. prints the compared numbers with their limits on standard error, and
   the result as the last line of standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
import zlib
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

#: host spans around the calls into the program (they label idle gaps)
SPANS = ("submit", "step", "wait", "admit", "chunk_dispatch", "chunk_sync")


def log(msg: str) -> None:
    print(msg, flush=True)


def read_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def verdict(compared: dict, n_compared: int, failed: int) -> bool:
    """The test that decides ``correct``: requests were compared, none
    failed, and the widest gap lies within its limit (``NaN`` does not)."""
    return n_compared > 0 and failed == 0 and \
        compared["value"] <= compared["limit"]


def load_cell(name: str, bench: dict) -> dict:
    """Everything a run of cell ``name`` needs, from ``BENCHMARK.json`` and
    the files it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(metric: str):
    """The reader of per-layer metric ``metric``:
    ``chipbench/metrics/<metric>.py``, function ``read``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def use_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however quick to compile, so that only the
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


def pow2_upto(n: int) -> List[int]:
    out, k = [], 1
    while k <= n:
        out.append(k)
        k *= 2
    return out


def _drain(b) -> None:
    while b.queue or any(r is not None for r in b.slot_req):
        b.step()


def warm_shapes(params, cfg, scfg, mix: dict, vocab: int) -> None:
    """Run every program shape the mix's traffic can reach once, on a
    batcher that is then thrown away: decode chunks of each power-of-two
    length up to ``chunk``; admissions of the batch buckets the mix lists
    under ``warm`` (``cold_batches``, and for document mixes
    ``cached_batches`` at the documents' cached depth); page pushes of each
    power-of-two width up to the pool."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.batcher import ContinuousBatcher, Request
    from repro.serving.engine import page_push_program

    rng = np.random.default_rng(0)
    P = scfg.prompt_len
    rid = iter(range(1 << 30))

    def req(prompt, max_new, ns=None):
        return Request(rid=next(rid), prompt=prompt.astype(np.int32),
                       max_new=max_new, namespace=ns)

    def batch(make, nb):
        for _ in range(nb):
            b.submit(make())
        _drain(b)

    b = ContinuousBatcher(params, cfg, scfg)
    # chunk lengths T = chunk, chunk/2, ..., 1 from one request's budget
    b.submit(req(rng.integers(1, vocab, size=P),
                 1 + sum(pow2_upto(scfg.chunk))))
    _drain(b)
    for nb in mix["warm"]["cold_batches"]:
        batch(lambda: req(rng.integers(1, vocab, size=P), 2), nb)
    p = mix["prompt"]
    if "documents" in p and scfg.prefix_cache:
        doc = rng.integers(1, vocab, size=p["doc_tokens"])

        def asking():
            q = rng.integers(1, vocab, size=P - len(doc))
            return req(np.concatenate([doc, q]), 2, "warm")

        batch(asking, 2)                # the pair's witness inserts the doc
        for nb in mix["warm"]["cached_batches"]:
            batch(asking, nb)
    if scfg.paged:
        for w in pow2_upto(2 * b.n_pages):
            b.pages = page_push_program()(b.pages,
                                          jnp.full((w,), -1, jnp.int32))
        b.pages.free_top.block_until_ready()
    del b
    gc.collect()


def fill_documents(b, plan, mix: dict) -> None:
    """Put every document of the schedule into the prefix cache: two
    requests per document, the pair being the recurrence the cache needs
    before it inserts."""
    import numpy as np

    from repro.serving.batcher import Request

    seen = {}
    for pr in plan:
        if pr.doc >= 0 and pr.doc not in seen:
            seen[pr.doc] = pr.prompt
    rng = np.random.default_rng(1)
    qlen = mix["prompt"]["question_tokens"]
    rid = -1
    for doc in sorted(seen):
        for _ in range(2):
            prompt = seen[doc].copy()
            prompt[-qlen:] = rng.integers(1, int(prompt.max()) + 1, size=qlen)
            b.submit(Request(rid=rid, prompt=prompt, max_new=2,
                             namespace=mix.get("namespace")))
            rid -= 1
        _drain(b)


# ---------------------------------------------------------------------------
# the open-loop load loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Rec:
    """What the host saw of one request."""

    plan: object
    req: object
    arrival: float              # scheduled, absolute perf_counter time
    submit: float
    first: Optional[float] = None
    last: Optional[float] = None
    done: Optional[float] = None
    n: int = 0


@dataclasses.dataclass
class Window:
    start: float
    end: float
    tokens: int = 0             # tokens delivered inside the window
    steps: int = 0
    decode_ctxs: List[int] = dataclasses.field(default_factory=list)
    prefills: List[tuple] = dataclasses.field(default_factory=list)
    counters0: Dict[str, int] = dataclasses.field(default_factory=dict)
    counters1: Dict[str, int] = dataclasses.field(default_factory=dict)
    compiles: int = 0
    max_admitted: int = 0       # most requests one step admitted, any time


def _annotate(b, span):
    """Wrap the batcher's admission and chunk calls in host spans."""
    for attr, name in (("_admit", "admit"), ("_dispatch_chunk",
                                             "chunk_dispatch"),
                       ("_finish_chunk", "chunk_sync")):
        fn = getattr(b, attr, None)
        if fn is None:
            continue

        def wrapped(*a, __fn=fn, __name=name, **kw):
            with span(__name):
                return __fn(*a, **kw)
        setattr(b, attr, wrapped)


def drive(b, plan, t0: float, win: Window, *, span=None,
          on_open=None) -> List[Rec]:
    """Offer ``plan`` open-loop from ``t0`` until ``win.end``; account every
    request and the window's work.  ``on_open`` runs once when the window
    opens (counter snapshots, the profiler)."""
    import contextlib

    from repro.serving.batcher import Request

    span = span or (lambda name: contextlib.nullcontext())
    recs: List[Rec] = []
    live: List[Rec] = []
    i, opened = 0, False
    while True:
        now = time.perf_counter()
        if not opened and now >= win.start:
            opened = True
            if on_open is not None:
                on_open()
            now = time.perf_counter()
        if now >= win.end:
            break
        with span("submit"):
            while i < len(plan) and t0 + plan[i].arrival_s <= now:
                pr = plan[i]
                req = Request(rid=pr.index, prompt=pr.prompt,
                              max_new=pr.max_new, namespace=pr.namespace)
                b.submit(req)
                rec = Rec(plan=pr, req=req, arrival=t0 + pr.arrival_s,
                          submit=now)
                recs.append(rec)
                live.append(rec)
                i += 1
        if b.queue or any(r is not None for r in b.slot_req):
            c0 = b.stats.prefill_tokens_skipped, b.stats.prefix_hits
            with span("step"):
                b.step()
            t = time.perf_counter()
            closing = t >= win.end
            if closing:
                # a step begun before the close ends the window: its work
                # and time both count, and nothing is cut in two
                win.end = t
            in_win = opened
            if in_win:
                win.steps += 1
            skipped = b.stats.prefill_tokens_skipped - c0[0]
            hits = b.stats.prefix_hits - c0[1]
            still, admitted = [], 0
            for rec in live:
                n = len(rec.req.out)
                new = n - rec.n
                if new > 0:
                    if rec.n == 0:
                        rec.first = t
                        admitted += 1
                        if in_win:
                            cached = skipped // hits if hits > 0 else 0
                            hits -= 1
                            skipped -= cached
                            win.prefills.append((cached, len(rec.plan.prompt)))
                    rec.last = t
                    if in_win:
                        win.tokens += new
                        ell = len(rec.plan.prompt)
                        win.decode_ctxs.extend(
                            ell + m for m in range(max(rec.n, 1), n))
                    rec.n = n
                if rec.req.done:
                    rec.done = t
                else:
                    still.append(rec)
            live = still
            win.max_admitted = max(win.max_admitted, admitted)
            if closing:
                break
        else:
            nxt = t0 + plan[i].arrival_s if i < len(plan) else win.end
            with span("wait"):
                time.sleep(max(0.0, min(nxt, win.end) - time.perf_counter()))
    return recs


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def latencies(recs: List[Rec], win: Window):
    """TTFT and TPOT (seconds) of the requests that arrived in the window.
    A request with no first token at the close counts with its age then; a
    request with two tokens or more counts with its tokens so far."""
    pop = [r for r in recs if win.start <= r.arrival <= win.end]
    ttft = [(r.first if r.first is not None and r.first <= win.end
             else win.end) - r.arrival for r in pop]
    tpot = [(r.last - r.first) / (r.n - 1) for r in pop
            if r.first is not None and r.n >= 2]
    return pop, ttft, tpot


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler's trace under chipbench_out/")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload, read_benchmark())
    need = int(spec["cell"]["chips"])

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"run.py: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    result, compared = run_cell(spec, args.seed, args.seconds,
                                bool(args.trace), t_start,
                                keep_trace=args.keep_trace)
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float, *, keep_trace: bool = False,
             control: bool = False):
    """One run of a cell; returns (result line, compared numbers).  With
    ``control`` the float8 control is also read on the same requests and
    judged by the same test, as ``control_correct``
    (``chipbench/control.py``; the benchmark's own runs never do)."""
    import jax
    import numpy as np

    import family
    import traffic
    from clock import CompileClock, percentile

    from repro.serving import ServingConfig
    from repro.serving.batcher import ContinuousBatcher

    config, mix, cell = spec["config"], spec["mix"], spec["cell"]
    dev = jax.devices()[0]
    log(f"cell {cell['name']}: device_kind={dev.device_kind} "
        f"platform={dev.platform} count={len(jax.devices())}")
    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    fam = family.load(config)
    cfg = fam.model_config(config)
    dims = fam.Dims.from_config(config)
    scfg = ServingConfig(**mix["serving"])
    params = fam.make_params(config, seed, cfg)
    jax.block_until_ready(params)
    log(f"weights made: {time.perf_counter() - t_start:.3f} s")
    plan = traffic.schedule(mix, seed, dims.vocab)

    warm_shapes(params, cfg, scfg, mix, dims.vocab)
    log(f"shapes warm: {time.perf_counter() - t_start:.3f} s "
        f"compiles={clock.compiles} cache_hits={clock.cache_hits}")
    b = ContinuousBatcher(params, cfg, scfg)
    if "documents" in mix["prompt"] and scfg.prefix_cache:
        fill_documents(b, plan, mix)
    window_s = min(seconds, mix["trace_s"]) if trace else seconds

    span = None
    prof_dir = None
    if trace:
        span = jax.profiler.TraceAnnotation
        _annotate(b, span)
        prof_dir = str(ROOT / "chipbench_out" / "trace" /
                       f"{cell['name']}.{seed}")
    t0 = time.perf_counter()
    win = Window(start=t0 + mix["preroll_s"],
                 end=t0 + mix["preroll_s"] + window_s)
    state = {}

    def on_open():
        state["setup_s"] = time.perf_counter() - t_start
        state["compiles"] = clock.compiles
        win.counters0 = b.stats.as_dict()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
            state["window_span"] = span("window")
            state["window_span"].__enter__()
            # the profiler's start took time: the window opens now
            now = time.perf_counter()
            win.end = now + window_s
            win.start = now

    recs = drive(b, plan, t0, win, span=span, on_open=on_open)
    window_s = win.end - win.start
    win.counters1 = b.stats.as_dict()
    win.compiles = clock.compiles - state["compiles"]
    reduced = None
    if trace:
        state["window_span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"window: {window_s:.3f} s, steps={win.steps}, "
        f"compiles_in_window={win.compiles}")

    pop, ttft, tpot = latencies(recs, win)
    late = [r.submit - r.arrival for r in recs
            if win.start <= r.arrival <= win.end]
    metrics_e2e = {
        "tokens_per_s": win.tokens / window_s,
        "ttft_p90_ms": percentile(ttft, 0.9) * 1e3,
        "tpot_p90_ms": percentile(tpot, 0.9) * 1e3,
        "setup_s": state["setup_s"],
    }
    log(f"requests: arrived_in_window={len(pop)} ttft_samples={len(ttft)} "
        f"tpot_samples={len(tpot)} "
        f"finished_in_window={sum(1 for r in recs if r.done is not None and win.start <= r.done <= win.end)} "
        f"queue_at_close={len(b.queue)} "
        f"most_admitted_in_one_step={win.max_admitted}")
    log(f"ttft_ms: p50={percentile(ttft, 0.5) * 1e3:.3f} "
        f"p90={metrics_e2e['ttft_p90_ms']:.3f}; tpot_ms: "
        f"p50={percentile(tpot, 0.5) * 1e3:.3f} "
        f"p90={metrics_e2e['tpot_p90_ms']:.3f}")
    log("ttft_samples_ms: " + " ".join(f"{x * 1e3:.1f}"
                                        for x in sorted(ttft)))
    log(f"generator lateness_ms: p50={percentile(late, 0.5) * 1e3:.3f} "
        f"max={max(late, default=float('nan')) * 1e3:.3f}")
    failed = sum(1 for r in pop if getattr(r.req, "dropped", False))
    log(f"attempted={len(pop)} failed={failed} tokens={win.tokens} "
        f"setup_s={state['setup_s']:.3f} peak_bytes_in_use={peak}")

    # the requests to compare: finished in the window, the longest first,
    # then others drawn from the seed
    done = [r for r in recs if r.done is not None
            and win.start <= r.done <= win.end]
    chk = mix["check"]
    sample = []
    if done:
        done.sort(key=lambda r: (-r.n, r.plan.index))
        sample = [done[0]]
        rest = done[1:]
        order = np.random.default_rng(seed).permutation(len(rest))
        for j in order[:chk["requests"] - 1]:
            sample.append(rest[j])
    pairs = [(r.plan.prompt, list(r.req.out)) for r in sample]

    if trace:
        import trace_reduce

        path = sorted(glob.glob(
            f"{prof_dir}/plugins/profile/*/*.xplane.pb"))[-1]
        reduced = trace_reduce.reduce(trace_reduce.read_xplane(path), SPANS)
        if not keep_trace:
            shutil.rmtree(prof_dir, ignore_errors=True)

    # free the program's state before the reference runs on the chip
    del b, params, on_open
    gc.collect()
    t_ref = time.perf_counter()
    gaps = fam.served_gaps(dims, seed, pairs)
    widest = float(max((g.max() for g in gaps), default=float("nan")))
    n_cmp = sum(len(g) for g in gaps)
    log(f"reference: {len(pairs)} requests, {n_cmp} served tokens compared "
        f"in {time.perf_counter() - t_ref:.3f} s")
    log("compared (rid:tokens:crc32): " + " ".join(
        f"{r.plan.index}:{len(o)}:"
        f"{zlib.crc32(np.asarray(o, np.int32).tobytes()):08x}"
        for r, (_, o) in zip(sample, pairs)))
    compared = {"widest_gap": {"value": widest, "limit": chk["limit"]}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": verdict(compared["widest_gap"], len(pairs), failed),
              "attempted": len(pop), "failed": failed}
    if control:
        low = fam.control_gaps(dims, seed, pairs)
        compared["control_widest_gap"] = {
            "value": float(max((g.max() for g in low), default=float("nan"))),
            "limit": chk["limit"]}
        # the control in the program's place, judged by the same test
        result["control_correct"] = verdict(compared["control_widest_gap"],
                                            len(pairs), failed)
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        peaks = json.loads((HERE / "peaks.json").read_text())
        if dev.device_kind not in peaks:
            raise SystemExit(f"run.py: no peaks for {dev.device_kind!r} in "
                             "chipbench/peaks.json")
        c0, c1 = win.counters0, win.counters1
        m = Measured(dims=dims, page_size=scfg.page_size,
                     peak=peaks[dev.device_kind], trace=reduced, window=win,
                     counters={k: c1[k] - c0[k] for k in c1},
                     flops=fam.flops, ttft_s=ttft)
        out = {}
        for spec_m in spec["per_layer"]:
            v = reader(spec_m["name"])(m)
            if v is not None:
                out[spec_m["name"]] = {"value": float(v),
                                       "unit": spec_m["unit"]}
        result["metrics"] = out
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.idle_by_label(10)}
    else:
        result["metrics"] = {
            spec_m["name"]: {"value": float(metrics_e2e[spec_m["name"]]),
                             "unit": spec_m["unit"]}
            for spec_m in spec["end_to_end"]}
        result["device"] = device
    result["check"] = compared
    return result, compared


@dataclasses.dataclass
class Measured:
    """What a per-layer reader may read: the traced window's reduction, the
    batcher's counters (differences over the window), the host's record of
    the decoded and prefilled tokens and of each request's TTFT, the peaks
    of the chip, and the family's operation counts (``flops``) of its
    sizes (``dims``)."""

    dims: object
    page_size: int
    peak: dict
    trace: object
    window: Window
    counters: Dict[str, int]
    flops: object
    ttft_s: List[float]         # TTFT of the requests arrived in the window


if __name__ == "__main__":
    raise SystemExit(main())
