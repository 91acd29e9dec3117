#!/usr/bin/env python3
"""Serving load-test harness (ISSUE 13): measure the continuous-batching
replica-pool engine against the single-lock-equivalent baseline, so the
throughput claim is a number, not an adjective.

What it runs
------------
A bundled MLP inference model (fc stack, --depth x --hidden) is exported
once; then for each engine config:

- **baseline**  — replicas=1, max_batch=1: every request dispatches
  alone at its exact shape, one worker.  Functionally identical to the
  pre-ISSUE-13 server (one executor behind a lock).
- **batched**   — --replicas N, --max_batch B: bucketed coalescing
  across a replica pool.

two load loops are driven over plain HTTP (keep-alive connections):

- **closed loop** — C clients issue requests back-to-back for D
  seconds: sustained RPS + p50/p99 service latency.
- **open loop**   — requests arrive on a fixed schedule at a target
  rate (sweeping fractions of the closed-loop RPS): the saturation
  curve.  Latency is measured from the *scheduled* arrival, so
  coordinated omission cannot hide queueing.

Compile-cache behavior is scraped from /metrics before and after each
measured window: after warmup the miss delta must be 0 (one compiled
XLA program per bucket, hit rate ~1.0).

Artifact
--------
``--out`` (default serving_bench.json) gets a
``paddle_tpu.serving_bench.v1`` document (a CPU control-flow check when
run under JAX_PLATFORMS=cpu, not a device measurement).

Usage
-----
    python benchmark/serving_bench.py [--replicas=4] [--max_batch=16]
        [--clients=16] [--duration=10] [--depth=4] [--hidden=256]
        [--open-points=0.5,0.75,1.0,1.25] [--out=serving_bench.json]
        [--model_dir=DIR] [--smoke]
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA = "paddle_tpu.serving_bench.v1"


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


def build_model(dirname: str, depth: int, hidden: int, in_dim: int,
                classes: int) -> str:
    import paddle_tpu as fluid

    fluid.framework.reset_default_programs()
    x = fluid.layers.data(name="x", shape=[in_dim], dtype="float32")
    h = x
    for _ in range(depth):
        h = fluid.layers.fc(input=h, size=hidden, act="relu")
    pred = fluid.layers.fc(input=h, size=classes, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(dirname, ["x"], [pred], exe)
    return dirname


# ---------------------------------------------------------------------------
# HTTP client (keep-alive; one connection per worker thread)
# ---------------------------------------------------------------------------


class Client:
    def __init__(self, address: str):
        host, port = address.rsplit(":", 1)
        self.conn = http.client.HTTPConnection(host, int(port), timeout=60)
        self.headers = {"Content-Type": "application/json"}

    def predict(self, body: bytes) -> int:
        self.conn.request("POST", "/predict", body=body,
                          headers=self.headers)
        resp = self.conn.getresponse()
        resp.read()
        return resp.status

    def get(self, path: str) -> str:
        self.conn.request("GET", path)
        resp = self.conn.getresponse()
        return resp.read().decode()

    def close(self):
        self.conn.close()


def _percentile(sorted_ms, q):
    if not sorted_ms:
        return float("nan")
    i = min(len(sorted_ms) - 1, int(round(q * (len(sorted_ms) - 1))))
    return sorted_ms[i]


def _cache_counts(address: str):
    text = Client(address).get("/metrics")
    hits = misses = 0.0
    for line in text.splitlines():
        if line.startswith("executor_compile_cache_hit_total"):
            hits += float(line.rsplit(" ", 1)[1])
        elif line.startswith("executor_compile_cache_miss_total"):
            misses += float(line.rsplit(" ", 1)[1])
    return hits, misses


# ---------------------------------------------------------------------------
# load loops
# ---------------------------------------------------------------------------


def closed_loop(address: str, body: bytes, clients: int, duration: float):
    """C clients, back-to-back requests: sustained RPS + service latency."""
    latencies: list = []
    errors = [0]
    lock = threading.Lock()
    stop_at = time.perf_counter() + duration
    start_gate = threading.Barrier(clients + 1)

    def worker():
        c = Client(address)
        # connect before the gate: accepting a connection needs the
        # server's (GIL-scheduled) accept loop, and under full load an
        # unlucky client can sit in the backlog for the whole window —
        # that would measure the accept loop, not the engine
        c.conn.connect()
        mine, bad = [], 0
        start_gate.wait()
        while True:
            t0 = time.perf_counter()
            if t0 >= stop_at:
                break
            try:
                code = c.predict(body)
                if code != 200:
                    bad += 1
                    continue
            except OSError:
                bad += 1
                continue
            mine.append((time.perf_counter() - t0) * 1e3)
        c.close()
        with lock:
            latencies.extend(mine)
            errors[0] += bad

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    start_gate.wait()
    t_start = time.perf_counter()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t_start
    latencies.sort()
    return {
        "loop": "closed", "clients": clients,
        "duration_s": round(elapsed, 3),
        "requests": len(latencies), "errors": errors[0],
        "achieved_rps": round(len(latencies) / elapsed, 1),
        "p50_ms": round(_percentile(latencies, 0.50), 3),
        "p99_ms": round(_percentile(latencies, 0.99), 3),
        "max_ms": round(latencies[-1], 3) if latencies else float("nan"),
    }


def open_loop(address: str, body: bytes, rate: float, duration: float,
              senders: int):
    """Fixed-rate arrivals; latency measured from the *scheduled*
    arrival time (coordinated-omission-proof)."""
    n = max(1, int(rate * duration))
    next_idx = [0]
    latencies: list = []
    errors = [0]
    lock = threading.Lock()
    start_gate = threading.Barrier(senders + 1)
    t0_box = [0.0]

    def worker():
        c = Client(address)
        c.conn.connect()   # see closed_loop: keep accept out of the window
        mine, bad = [], 0
        start_gate.wait()
        t0 = t0_box[0]
        while True:
            with lock:
                i = next_idx[0]
                if i >= n:
                    break
                next_idx[0] += 1
            sched = t0 + i / rate
            now = time.perf_counter()
            if sched > now:
                time.sleep(sched - now)
            try:
                code = c.predict(body)
                if code != 200:
                    bad += 1
                    continue
            except OSError:
                bad += 1
                continue
            mine.append((time.perf_counter() - sched) * 1e3)
        c.close()
        with lock:
            latencies.extend(mine)
            errors[0] += bad

    threads = [threading.Thread(target=worker) for _ in range(senders)]
    for t in threads:
        t.start()
    t0_box[0] = time.perf_counter() + 0.05
    start_gate.wait()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0_box[0]
    latencies.sort()
    return {
        "loop": "open", "offered_rps": round(rate, 1),
        "duration_s": round(elapsed, 3),
        "requests": len(latencies), "errors": errors[0],
        "achieved_rps": round(len(latencies) / max(elapsed, 1e-9), 1),
        "p50_ms": round(_percentile(latencies, 0.50), 3),
        "p99_ms": round(_percentile(latencies, 0.99), 3),
        "max_ms": round(latencies[-1], 3) if latencies else float("nan"),
    }


# ---------------------------------------------------------------------------
# one engine config = server + warmup + closed + open sweep
# ---------------------------------------------------------------------------


def _request_body(srv) -> bytes:
    """One single-row request synthesized from the served model's own
    BatchSpec (feed names, row shapes, dtypes) — so --model_dir exports
    bench the same way the bundled MLP does instead of 400ing on a
    hardcoded feed name."""
    from paddle_tpu.serving.batching import BatchSpec

    spec = srv._spec
    if not spec.batchable:
        # a no-coalescing config (baseline max_batch=1) disables the
        # spec; rebuild it just to synthesize feeds
        spec = BatchSpec.from_program(srv._bundle.program,
                                      srv._bundle.feed_names,
                                      srv._bundle.fetch_names)
    if not spec.batchable:
        raise SystemExit(
            f"cannot synthesize load for this export: {spec.reason}; "
            "serving_bench needs a batch-major model (ragged/LoD models "
            "serve, but the harness cannot invent their feeds)")
    rng = np.random.RandomState(0)
    payload = {}
    for name in spec.feed_names:
        shape = (1,) + spec.row_shapes[name]
        dt = np.dtype(spec.dtypes[name])
        if dt.kind == "f":
            payload[name] = rng.standard_normal(shape).astype(dt).tolist()
        else:
            payload[name] = np.zeros(shape, dt).tolist()
    return json.dumps(payload).encode()


def bench_config(model_dir: str, *, mode: str, replicas: int, max_batch: int,
                 batch_timeout_ms: float, clients: int, duration: float,
                 open_points, senders: int):
    from paddle_tpu.serving import InferenceServer

    srv = InferenceServer(model_dir, replicas=replicas, max_batch=max_batch,
                          batch_timeout_ms=batch_timeout_ms, warmup=True)
    body = _request_body(srv)
    try:
        # traffic warmup: exercise the HTTP path + any solo shapes
        closed_loop(srv.address, body, clients=min(4, clients),
                    duration=min(1.0, duration / 4))
        h0, m0 = _cache_counts(srv.address)
        closed = closed_loop(srv.address, body, clients, duration)
        h1, m1 = _cache_counts(srv.address)
        closed["cache"] = {
            "hits": h1 - h0, "misses": m1 - m0,
            "hit_rate": round((h1 - h0) / max(1.0, (h1 - h0) + (m1 - m0)), 6),
        }
        runs = [closed]
        for frac in open_points:
            rate = max(1.0, closed["achieved_rps"] * frac)
            runs.append(open_loop(srv.address, body, rate, duration,
                                  senders))
        info = srv.batching_info()
    finally:
        srv.stop()
    return {"mode": mode, "replicas": replicas, "max_batch": max_batch,
            "batch_timeout_ms": batch_timeout_ms, "batching": info,
            "runs": runs}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model_dir", help="serve an existing export instead "
                    "of building the bundled MLP")
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--in_dim", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--max_batch", type=int, default=16)
    ap.add_argument("--batch_timeout_ms", type=float, default=0.0)
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--senders", type=int, default=64,
                    help="open-loop sender pool size")
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--open-points", default="0.5,0.75,1.0,1.25",
                    help="open-loop rates as fractions of closed-loop RPS"
                    " ('' to skip)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the single-lock baseline config")
    ap.add_argument("--out", default="serving_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sanity run (lint_self.sh)")
    ap.add_argument("--multi-thread-eigen", action="store_true",
                    help="let XLA CPU's eigen pool use every core per op. "
                    "Off by default: the spinning pool starves the Python "
                    "HTTP/client threads (seconds-long GIL convoys, wild "
                    "run-to-run variance) and no serving deployment gives "
                    "one request every core anyway — per-replica "
                    "single-thread steps measure the engine, not the "
                    "scheduler fight")
    args = ap.parse_args(argv)

    if not args.multi_thread_eigen:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_cpu_multi_thread_eigen=false").strip()

    if args.smoke:
        args.depth, args.hidden, args.in_dim, args.classes = 1, 32, 8, 4
        args.replicas, args.max_batch = 2, 4
        args.clients, args.senders, args.duration = 4, 8, 0.5
        args.open_points = "1.0"

    model_dir = args.model_dir
    tmp = None
    if not model_dir:
        tmp = tempfile.TemporaryDirectory(prefix="serving_bench_")
        model_dir = build_model(os.path.join(tmp.name, "model"), args.depth,
                                args.hidden, args.in_dim, args.classes)
    open_points = [float(p) for p in args.open_points.split(",") if p]

    configs = []
    if not args.no_baseline:
        configs.append(dict(mode="baseline", replicas=1, max_batch=1,
                            batch_timeout_ms=0.0))
    configs.append(dict(mode="batched", replicas=args.replicas,
                        max_batch=args.max_batch,
                        batch_timeout_ms=args.batch_timeout_ms))

    results = []
    for cfg in configs:
        print(f"== {cfg['mode']}: replicas={cfg['replicas']} "
              f"max_batch={cfg['max_batch']}", flush=True)
        r = bench_config(model_dir, clients=args.clients,
                         duration=args.duration, open_points=open_points,
                         senders=args.senders, **cfg)
        for run in r["runs"]:
            print("  ", json.dumps(run), flush=True)
        results.append(r)

    doc = {
        "schema": SCHEMA,
        "host": {"cpus": os.cpu_count(),
                 "jax_platforms": os.environ.get("JAX_PLATFORMS", "")},
        "model": ({"model_dir": args.model_dir} if args.model_dir else
                  {"depth": args.depth, "hidden": args.hidden,
                   "in_dim": args.in_dim, "classes": args.classes}),
        "load": {"clients": args.clients, "duration_s": args.duration,
                 "senders": args.senders, "open_points": open_points},
        "configs": results,
    }
    if len(results) == 2:
        base = results[0]["runs"][0]
        batt = results[1]["runs"][0]
        doc["headline"] = {
            "baseline_rps": base["achieved_rps"],
            "batched_rps": batt["achieved_rps"],
            "speedup": round(batt["achieved_rps"]
                             / max(base["achieved_rps"], 1e-9), 2),
            "baseline_p99_ms": base["p99_ms"],
            "batched_p99_ms": batt["p99_ms"],
            "batched_cache_hit_rate": batt["cache"]["hit_rate"],
        }
        print("headline:", json.dumps(doc["headline"]))
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"artifact written to {args.out}")
    if tmp:
        tmp.cleanup()
    return 0


if __name__ == "__main__":
    sys.exit(main())
