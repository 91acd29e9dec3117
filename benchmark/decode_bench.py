#!/usr/bin/env python3
"""Paged-KV decode benchmark (ISSUE 15): concurrent ragged-batch
generation through the DecodeSession vs the serving engine's
solo-execution fallback — the throughput claim as a number.

What it runs
------------
The bundled NMT demo network (demos/seq2seq) with seed-initialized
parameters — identical weights for both paths, so both decode identical
tokens and the comparison is pure scheduling:

- **solo**  — the PR-13 serving shape for ragged workloads: W worker
  threads, each a dense ``SequenceGenerator`` (one sequence per step
  dispatch, encoder re-run every step), draining one request queue.
  This is exactly what the bucketer's ragged fallback does per request.
- **paged** — ``GenerationEngine``: one prefill per admission writes
  the encoder states into KV pages; every decode step advances ALL
  active slots through one fixed-shape compiled program (continuous
  batching at token granularity).

Both paths serve the same burst of ragged-length requests; we record
generated tokens/s, per-request p50/p99 latency, and the executor
compile-cache hit rate over the measured window (after warmup the paged
path must be 1.0 — batch churn never re-traces).

Artifact
--------
``--out`` (default decode_bench.json) gets a
``paddle_tpu.decode_bench.v1`` document.  The acceptance row (>= 3x
tokens/s at equal or lower p99, cache hit rate 1.0) is a CPU
control-flow check, not a device measurement.

Sharing modes (ISSUE 18)
------------------------
``--mode=prefix`` serves a prefix-heavy burst (N requests drawn from a
handful of long shared prompt prefixes) through a ``TinyDecoderLM``
engine twice — prefix cache off, then on — and records tokens/s and
**peak page-pool occupancy** for both.  The cached run must decode
token-identical ids; the win is skipped prefill work plus aliased
(copy-on-write) prefix pages.  ``--mode=spec`` decodes the same burst
greedily and speculatively (n-gram prompt-lookup draft, one ragged
verify step per chunk) and hard-fails unless the speculative ids are
token-identical; the acceptance ratio comes from the
``decode_spec_*`` counters.  ``--mode=sharing`` runs both and writes
one ``paddle_tpu.decode_bench.v2`` artifact
(benchmark/DECODE_BENCH_r02.json is such a run).

Usage
-----
    python benchmark/decode_bench.py [--mode=compare|prefix|spec|sharing]
        [--requests=64] [--slots=8]
        [--solo-workers=2] [--max-new-tokens=16] [--pages=96]
        [--page-size=8] [--pages-per-seq=8] [--prefix-pages=4]
        [--spec-k=4] [--out=decode_bench.json] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA = "paddle_tpu.decode_bench.v1"
SCHEMA_V2 = "paddle_tpu.decode_bench.v2"


class _Params:
    def __init__(self):
        from paddle_tpu.executor import Scope

        self.scope = Scope()


def make_beam_gen(max_length: int):
    # the ONE shared spec builder — bench, serving config, and parity
    # tests must construct the identical network
    from demos.seq2seq.gen_config import make_beam_gen as _mk

    return _mk(beam_size=1, max_length=max_length)


def make_requests(n: int, seed: int = 7):
    from demos.seq2seq.network import VOCAB

    rng = np.random.RandomState(seed)
    return [list(rng.randint(2, VOCAB, rng.randint(2, 9)))
            for _ in range(n)]


def _cache_counts():
    from paddle_tpu.observability import metrics as M

    snap = M.snapshot()
    out = {}
    for k, name in (("miss", "executor_compile_cache_miss_total"),
                    ("hit", "executor_compile_cache_hit_total")):
        out[k] = sum(r["value"] for r in
                     snap.get(name, {"values": []})["values"])
    return out


def _percentiles(lat_s):
    lat = sorted(lat_s)
    pick = lambda p: lat[min(len(lat) - 1, int(p * len(lat)))]  # noqa: E731
    return {"p50_ms": round(pick(0.50) * 1e3, 3),
            "p99_ms": round(pick(0.99) * 1e3, 3)}


# ---------------------------------------------------------------------------
# solo baseline: the serving engine's ragged fallback, W workers
# ---------------------------------------------------------------------------


def clone_params(params):
    """Deep-copy the parameter scope (the ``pd_machine_clone`` shape the
    serving replicas use): the executor donates state buffers per run,
    so concurrent workers must not share device buffers."""
    p = _Params()
    for name in list(params.scope.keys()):
        p.scope.set(name, np.array(np.asarray(params.scope.get(name))))
    return p


def run_solo(params, requests, max_new, workers: int):
    from paddle_tpu.generation import SequenceGenerator

    gens = [SequenceGenerator(make_beam_gen(max_new), clone_params(params))
            for _ in range(workers)]
    for g in gens:                      # warmup: compile each replica
        g.generate_greedy([requests[0]])
    c0 = _cache_counts()

    work: queue.Queue = queue.Queue()
    results = [None] * len(requests)
    t0 = time.perf_counter()
    for i, r in enumerate(requests):
        work.put((i, r))

    errors = []

    def worker(g):
        while True:
            try:
                i, src = work.get_nowait()
            except queue.Empty:
                return
            try:
                ids = g.generate_greedy([src])
            except BaseException as e:  # surface, don't silently drop
                errors.append(e)
                return
            results[i] = (ids, time.perf_counter() - t0)

    threads = [threading.Thread(target=worker, args=(g,)) for g in gens]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    wall = time.perf_counter() - t0
    c1 = _cache_counts()
    tokens = sum(len(ids) for ids, _ in results)
    lat = [dt for _, dt in results]
    misses = c1["miss"] - c0["miss"]
    hits = c1["hit"] - c0["hit"]
    return {
        "workers": workers,
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        **_percentiles(lat),
        "cache": {"miss": misses, "hit": hits,
                  "hit_rate": round(hits / max(1, hits + misses), 4)},
    }, [ids for ids, _ in results]


# ---------------------------------------------------------------------------
# paged: the decode engine
# ---------------------------------------------------------------------------


def run_paged(params, requests, max_new, slots, pages, page_size):
    from paddle_tpu.decode import GenerationEngine

    engine = GenerationEngine.for_seq2seq(
        make_beam_gen(max_new), clone_params(params), num_pages=pages,
        page_size=page_size, pages_per_seq=2, max_slots=slots,
        max_waiting=len(requests) + 1, max_new_tokens=max_new)
    engine.submit(requests[0]).wait(600)      # warmup: prefill + step
    c0 = _cache_counts()

    t0 = time.perf_counter()
    reqs = [engine.submit(r) for r in requests]
    done_at = []
    for r in reqs:
        r.wait(600)
        done_at.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t0
    c1 = _cache_counts()
    engine.stop()
    tokens = sum(len(r.tokens) for r in reqs)
    misses = c1["miss"] - c0["miss"]
    hits = c1["hit"] - c0["hit"]
    return {
        "slots": slots,
        "pages": pages,
        "page_size": page_size,
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        **_percentiles(done_at),
        "cache": {"miss": misses, "hit": hits,
                  "hit_rate": round(hits / max(1, hits + misses), 4)},
    }, [list(r.tokens) for r in reqs]


# ---------------------------------------------------------------------------
# sharing modes (ISSUE 18): prefix cache + speculative decoding
# ---------------------------------------------------------------------------


class _PeakSampler:
    """Polls ``allocator.pages_in_use`` on a side thread and keeps the
    max — the pool-occupancy number CoW prefix sharing is supposed to
    shrink.  Polling can miss a one-tick spike; at decode-step
    timescales (ms) a 0.5 ms sample period is dense enough."""

    def __init__(self, alloc):
        self.alloc, self.peak = alloc, 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            v = self.alloc.pages_in_use
            if v > self.peak:
                self.peak = v
            time.sleep(0.0005)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *a):
        self._stop.set()
        self._t.join()


def _make_lm(args, seed: int = 11):
    from paddle_tpu.decode.model import TinyDecoderLM

    return TinyDecoderLM(num_pages=args.pages, page_size=args.page_size,
                         pages_per_seq=args.pages_per_seq, seed=seed)


def make_prefix_requests(n: int, page_size: int, prefix_pages: int,
                         n_prefixes: int = 4, seed: int = 13):
    """A prefix-heavy burst: every request is one of ``n_prefixes``
    long shared prefixes (full pages of tokens) plus a short random
    suffix — the workload prefix caching exists for."""
    rng = np.random.RandomState(seed)
    bases = [list(rng.randint(2, 64, prefix_pages * page_size))
             for _ in range(n_prefixes)]
    return [bases[rng.randint(n_prefixes)]
            + list(rng.randint(2, 64, 1 + rng.randint(4)))
            for _ in range(n)]


def make_lm_requests(n: int, seed: int = 17):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(2, 64, rng.randint(4, 13))) for _ in range(n)]


def _run_lm_burst(engine, requests, sample_alloc=None):
    engine.submit(requests[0]).wait(600)      # warmup: compile the step
    peak = 0
    sampler = (_PeakSampler(sample_alloc) if sample_alloc is not None
               else None)
    t0 = time.perf_counter()
    if sampler:
        sampler.__enter__()
    try:
        reqs = [engine.submit(r) for r in requests]
        done_at = []
        for r in reqs:
            r.wait(600)
            done_at.append(time.perf_counter() - t0)
    finally:
        if sampler:
            sampler.__exit__()
            peak = sampler.peak
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in reqs)
    out = {
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(tokens / wall, 2),
        **_percentiles(done_at),
    }
    if sampler:
        out["peak_pages_in_use"] = peak
    return out, [list(r.tokens) for r in reqs]


def run_prefix(cache_on: bool, requests, args):
    from paddle_tpu.decode import GenerationEngine

    lm = _make_lm(args)
    engine = GenerationEngine(lm, max_slots=args.slots,
                              max_waiting=len(requests) + 1,
                              max_new_tokens=args.max_new_tokens,
                              prefix_cache=cache_on)
    try:
        out, ids = _run_lm_burst(engine, requests, sample_alloc=lm.allocator)
        out["prefix_cache"] = bool(cache_on)
        if cache_on:
            out["cache_stats"] = engine.session.prefix_cache.stats()
    finally:
        engine.stop()
    return out, ids


def mode_prefix(args):
    requests = make_prefix_requests(args.requests, args.page_size,
                                    args.prefix_pages)
    print(f"== prefix-heavy load, cache OFF ({args.requests} requests, "
          f"{args.prefix_pages * args.page_size}-token shared prefixes)",
          file=sys.stderr)
    off, off_ids = run_prefix(False, requests, args)
    print(f"   {off['tokens_per_s']} tok/s  "
          f"peak {off['peak_pages_in_use']} pages", file=sys.stderr)
    print("== prefix-heavy load, cache ON", file=sys.stderr)
    on, on_ids = run_prefix(True, requests, args)
    print(f"   {on['tokens_per_s']} tok/s  "
          f"peak {on['peak_pages_in_use']} pages  "
          f"hits {on['cache_stats']['hits']}", file=sys.stderr)
    if on_ids != off_ids:
        raise SystemExit("prefix-cached decode diverged from the uncached "
                         "run — page sharing corrupted the KV")
    return {
        "workload": {
            "requests": args.requests,
            "shared_prefixes": 4,
            "prefix_tokens": args.prefix_pages * args.page_size,
            "max_new_tokens": args.max_new_tokens,
        },
        "cache_off": off,
        "cache_on": on,
        "tokens_identical": True,
        "speedup_tokens_per_s": round(
            on["tokens_per_s"] / max(1e-9, off["tokens_per_s"]), 2),
        "peak_pages_ratio": round(
            on["peak_pages_in_use"] / max(1, off["peak_pages_in_use"]), 3),
    }


def _spec_counts():
    from paddle_tpu.observability import metrics as M

    snap = M.snapshot()
    out = {}
    for key, name in (("proposed", "decode_spec_proposed_total"),
                      ("accepted", "decode_spec_accepted_total")):
        out[key] = sum(r["value"] for r in
                       snap.get(name, {"values": []})["values"])
    return out


def mode_spec(args):
    from paddle_tpu.decode import GenerationEngine
    from paddle_tpu.decode.spec import NgramDraft

    requests = make_lm_requests(args.requests)

    print(f"== greedy baseline ({args.requests} requests)", file=sys.stderr)
    base_engine = GenerationEngine(_make_lm(args), max_slots=args.slots,
                                   max_waiting=len(requests) + 1,
                                   max_new_tokens=args.max_new_tokens)
    try:
        base, base_ids = _run_lm_burst(base_engine, requests)
    finally:
        base_engine.stop()
    print(f"   {base['tokens_per_s']} tok/s", file=sys.stderr)

    print(f"== speculative (ngram draft, k={args.spec_k})", file=sys.stderr)
    spec_engine = GenerationEngine(_make_lm(args), max_slots=args.slots,
                                   max_waiting=len(requests) + 1,
                                   max_new_tokens=args.max_new_tokens,
                                   spec_draft=NgramDraft(),
                                   spec_k=args.spec_k)
    s0 = _spec_counts()
    try:
        spec, spec_ids = _run_lm_burst(spec_engine, requests)
    finally:
        spec_engine.stop()
    s1 = _spec_counts()
    proposed = s1["proposed"] - s0["proposed"]
    accepted = s1["accepted"] - s0["accepted"]
    spec["draft"] = f"ngram(k={args.spec_k})"
    spec["proposed"] = proposed
    spec["accepted"] = accepted
    spec["accept_ratio"] = round(accepted / max(1, proposed), 4)
    print(f"   {spec['tokens_per_s']} tok/s  "
          f"accept {spec['accept_ratio']}", file=sys.stderr)

    if spec_ids != base_ids:
        raise SystemExit("speculative decode is not token-identical to "
                         "greedy — the acceptance rule is broken")
    return {
        "workload": {
            "requests": args.requests,
            "max_new_tokens": args.max_new_tokens,
            "spec_k": args.spec_k,
        },
        "greedy": base,
        "speculative": spec,
        "tokens_identical": True,
        "speedup_tokens_per_s": round(
            spec["tokens_per_s"] / max(1e-9, base["tokens_per_s"]), 2),
    }


def main_sharing(args):
    doc = {
        "schema": SCHEMA_V2,
        "model": "paddle_tpu/decode TinyDecoderLM (seed-initialized)",
        "config": {
            "slots": args.slots,
            "pages": args.pages,
            "page_size": args.page_size,
            "pages_per_seq": args.pages_per_seq,
            "backend": os.environ.get("JAX_PLATFORMS", "default"),
        },
    }
    summary = {}
    if args.mode in ("prefix", "sharing"):
        doc["prefix"] = mode_prefix(args)
        summary["prefix_speedup"] = doc["prefix"]["speedup_tokens_per_s"]
        summary["peak_pages_ratio"] = doc["prefix"]["peak_pages_ratio"]
    if args.mode in ("spec", "sharing"):
        doc["spec"] = mode_spec(args)
        summary["spec_accept_ratio"] = \
            doc["spec"]["speculative"]["accept_ratio"]
        summary["spec_speedup"] = doc["spec"]["speedup_tokens_per_s"]
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps(summary))
    print(f"artifact written to {args.out}", file=sys.stderr)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="compare",
                    choices=("compare", "prefix", "spec", "sharing"))
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--solo-workers", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--pages", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pages-per-seq", type=int, default=8)
    ap.add_argument("--prefix-pages", type=int, default=4,
                    help="shared-prefix length in pages (prefix mode)")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--out", default="decode_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config: exercise the harness, not the claim")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.slots = 6, 3
        args.max_new_tokens, args.solo_workers = 5, 1
        args.pages = 24
        if args.mode != "compare":
            args.requests, args.pages = 8, 48
            args.prefix_pages = 2

    import jax

    # the persistent XLA compile cache must not shape a throughput
    # measurement: compile times are part of what this bench reports
    jax.config.update("jax_enable_compilation_cache", False)

    import paddle_tpu  # noqa: F401  (register ops before anything else)

    if args.mode != "compare":
        return main_sharing(args)

    params = _Params()
    # materialize the parameters once (fixed startup seeds) so every
    # clone serves byte-identical weights
    from paddle_tpu.generation import SequenceGenerator

    SequenceGenerator(make_beam_gen(args.max_new_tokens), params)
    requests = make_requests(args.requests)

    print(f"== solo fallback ({args.solo_workers} workers, "
          f"{args.requests} requests)", file=sys.stderr)
    solo, solo_ids = run_solo(params, requests, args.max_new_tokens,
                              args.solo_workers)
    print(f"   {solo['tokens_per_s']} tok/s  p99 {solo['p99_ms']} ms",
          file=sys.stderr)

    print(f"== paged decode ({args.slots} slots)", file=sys.stderr)
    paged, paged_ids = run_paged(params, requests, args.max_new_tokens,
                                 args.slots, args.pages, args.page_size)
    print(f"   {paged['tokens_per_s']} tok/s  p99 {paged['p99_ms']} ms",
          file=sys.stderr)

    if paged_ids != solo_ids:
        raise SystemExit("paged decode diverged from the solo oracle — "
                         "the speedup would be meaningless")

    doc = {
        "schema": SCHEMA,
        "model": "demos/seq2seq (NMT, seed-initialized)",
        "config": {
            "requests": args.requests,
            "max_new_tokens": args.max_new_tokens,
            "backend": os.environ.get("JAX_PLATFORMS", "default"),
        },
        "solo": solo,
        "paged": paged,
        "speedup_tokens_per_s": round(
            paged["tokens_per_s"] / max(1e-9, solo["tokens_per_s"]), 2),
        "p99_ratio": round(paged["p99_ms"] / max(1e-9, solo["p99_ms"]), 3),
        "tokens_identical": True,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({k: doc[k] for k in
                      ("speedup_tokens_per_s", "p99_ratio")}))
    print(f"artifact written to {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
