#!/usr/bin/env python3
"""Cold-start benchmark (ISSUE 20): artifact-booted serving vs cold JIT.

What it measures
----------------
A bundled MLP export (--depth x --hidden, buckets up to --max_batch) is
compiled once with ``paddle compile``; then two fresh server processes
are booted via ``paddle serve --warmup``:

- **jit boot** — no artifacts: every bucket-ladder program is
  traced + compiled before the listening line prints;
- **aot boot** — ``--artifacts=DIR``: every program is deserialized
  from the artifact store (donation restored).

The reported number is **time-to-first-successful-response**: process
spawn -> first 200 from POST /predict, the interval a rolling restart
actually spends dark.  Both boots answer the same request body and the
response bytes must be identical (the artifact path is a cache, never
an approximation).  The aot boot's /health must report a pure
``boot=aot`` store with zero rejected lookups.

A separate in-process probe asserts donation is ACTIVE on the AOT
path: a stateful two-op program is exported, re-loaded from the store
in a fresh executor, stepped twice, and the step-2 donated input
buffer must come back deleted (donated to XLA), not merely unused.

Artifact
--------
``--out`` (default COLDSTART_r01.json) gets a
``paddle_tpu.coldstart_bench.v1`` document; the acceptance row is aot
boot >= --min-speedup x faster (default 3.0).

Usage
-----
    python benchmark/coldstart_bench.py [--depth=64] [--hidden=128]
        [--max_batch=64] [--reps=1] [--min-speedup=3.0]
        [--out=COLDSTART_r01.json] [--smoke]

The default model is deep and narrow on purpose: cold-start pain is
compile time, so the bench wants many XLA programs (7 buckets) each
with a long op chain (64 fc layers), while keeping the parameter set
small enough that loading params — paid identically by both boots —
does not drown the compile-time difference being measured.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA = "paddle_tpu.coldstart_bench.v1"


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
# subprocess boot: spawn `paddle serve --warmup`, time to first 200
# ---------------------------------------------------------------------------


def boot_once(model_dir: str, max_batch: int, body: bytes,
              artifacts: str = None, timeout: float = 900.0) -> dict:
    """One cold boot in a fresh process.  Returns wall times (spawn ->
    listening, spawn -> first 200), the /predict response bytes, and
    the server's /health aot block."""
    cmd = [sys.executable, "-m", "paddle_tpu.cli", "serve",
           f"--model_dir={model_dir}", "--port=0",
           f"--max_batch={max_batch}", "--warmup"]
    if artifacts:
        cmd.append(f"--artifacts={artifacts}")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env,
                            cwd=REPO)
    address = None
    try:
        deadline = t0 + timeout
        for line in proc.stdout:
            if "listening on" in line:
                address = line.rsplit("listening on", 1)[1].strip()
                break
            if time.perf_counter() > deadline:
                raise SystemExit("boot timed out before listening line")
        if address is None:
            raise SystemExit(
                f"server exited before listening (rc={proc.wait()})")
        listening_s = time.perf_counter() - t0
        base = f"http://{address}"
        req = urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json"})
        while True:
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    resp = r.read()
                break
            except (urllib.error.URLError, ConnectionError):
                if time.perf_counter() > deadline:
                    raise SystemExit("no 200 before boot timeout")
                time.sleep(0.02)
        first_response_s = time.perf_counter() - t0
        with urllib.request.urlopen(base + "/health", timeout=30) as r:
            health = json.loads(r.read())
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    return {"listening_s": round(listening_s, 3),
            "first_response_s": round(first_response_s, 3),
            "response": resp, "aot": health.get("aot")}


# ---------------------------------------------------------------------------
# donation probe: AOT-loaded executables must still alias state buffers
# ---------------------------------------------------------------------------


def donation_probe(tmp: str) -> dict:
    """Export a stateful program, reload it from the store in a fresh
    executor, step twice: step 2's donated input (step 1's own output)
    must come back deleted — donation active, asserted not assumed."""
    import jax.numpy as jnp

    from paddle_tpu import aot, framework
    from paddle_tpu.aot.artifact import ArtifactStore, ArtifactWriter
    from paddle_tpu.executor import Executor, Scope

    def _program():
        prog = framework.Program()
        block = prog.global_block()
        block.create_var(name="W", shape=(8, 8), dtype="float32",
                         persistable=True)
        block.create_var(name="Y", shape=(8, 8), dtype="float32")
        block.append_op(type="scale", inputs={"X": ["W"]},
                        outputs={"Out": ["Y"]}, attrs={"scale": 2.0})
        block.append_op(type="scale", inputs={"X": ["W"]},
                        outputs={"Out": ["W"]}, attrs={"scale": 1.5})
        return prog

    art = os.path.join(tmp, "donation_artifacts")
    w0 = np.arange(64, dtype=np.float32).reshape(8, 8)
    exe = Executor()
    scope = Scope()
    scope.set("W", jnp.asarray(w0))
    writer = ArtifactWriter(art)
    with aot.capture(writer):
        (y_ref,) = exe.run(_program(), feed={}, fetch_list=["Y"],
                           scope=scope)
    writer.finish()

    exe2 = Executor()
    exe2.aot_store = ArtifactStore(art)
    scope2 = Scope()
    scope2.set("W", jnp.asarray(w0))
    prog2 = _program()
    (y_aot,) = exe2.run(prog2, feed={}, fetch_list=["Y"], scope=scope2)
    w_step1 = scope2.get("W")
    exe2.run(prog2, feed={}, fetch_list=["Y"], scope=scope2)
    return {
        "loaded_from_store": exe2.aot_store.results.get("loaded", 0) > 0,
        "bit_identical": bool(np.array_equal(np.asarray(y_ref),
                                             np.asarray(y_aot))),
        "donation_active": bool(w_step1.is_deleted()),
    }


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--depth", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--in-dim", type=int, default=64)
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--max_batch", type=int, default=64)
    ap.add_argument("--reps", type=int, default=1,
                    help="boots per mode; the best (min) time is scored")
    ap.add_argument("--min-speedup", type=float, default=3.0)
    ap.add_argument("--out", default="COLDSTART_r01.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model, no speedup gate (CI wiring check)")
    args = ap.parse_args()
    if args.smoke:
        args.depth, args.hidden, args.max_batch = 2, 16, 2
        args.min_speedup = 0.0

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.aot.export import export_model

    with tempfile.TemporaryDirectory(prefix="paddle_coldstart_") as tmp:
        model_dir = build_model(os.path.join(tmp, "model"), args.depth,
                                args.hidden, args.in_dim, args.classes)
        art_dir = os.path.join(tmp, "artifacts")
        t0 = time.perf_counter()
        writer = export_model(model_dir, art_dir, max_batch=args.max_batch)
        export_s = time.perf_counter() - t0
        print(f"paddle compile: {len(writer.entries)} executables "
              f"in {export_s:.1f}s", flush=True)

        rng = np.random.RandomState(0)
        body = json.dumps({
            "x": rng.randn(2, args.in_dim).astype("float32").tolist()
        }).encode()

        boots = {"jit": [], "aot": []}
        for rep in range(max(1, args.reps)):
            for mode in ("jit", "aot"):
                b = boot_once(model_dir, args.max_batch, body,
                              artifacts=art_dir if mode == "aot" else None)
                boots[mode].append(b)
                print(f"{mode} boot #{rep}: listening "
                      f"{b['listening_s']}s, first response "
                      f"{b['first_response_s']}s", flush=True)

        parity = all(b["response"] == boots["jit"][0]["response"]
                     for m in boots for b in boots[m])
        aot_health = boots["aot"][-1]["aot"] or {}
        rejected = {k: v for k, v in
                    (aot_health.get("results") or {}).items()
                    if k != "loaded"}
        probe = donation_probe(tmp)

    jit_s = min(b["first_response_s"] for b in boots["jit"])
    aot_s = min(b["first_response_s"] for b in boots["aot"])
    speedup = jit_s / aot_s if aot_s else float("inf")
    doc = {
        "schema": SCHEMA,
        "config": {"depth": args.depth, "hidden": args.hidden,
                   "in_dim": args.in_dim, "classes": args.classes,
                   "max_batch": args.max_batch, "reps": args.reps,
                   "smoke": args.smoke},
        "export": {"executables": len(writer.entries),
                   "bytes": sum(e["nbytes"]
                                for e in writer.entries.values()),
                   "seconds": round(export_s, 3)},
        "boots": {m: [{k: b[k] for k in
                       ("listening_s", "first_response_s")}
                      for b in boots[m]] for m in boots},
        "jit_first_response_s": jit_s,
        "aot_first_response_s": aot_s,
        "speedup": round(speedup, 2),
        "parity_bit_identical": parity,
        "aot_boot": aot_health.get("boot"),
        "aot_store_results": aot_health.get("results"),
        "donation": probe,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"jit {jit_s:.2f}s -> aot {aot_s:.2f}s "
          f"({speedup:.1f}x); parity={parity} "
          f"donation_active={probe['donation_active']} -> {args.out}")

    ok = (parity and probe["donation_active"] and probe["bit_identical"]
          and probe["loaded_from_store"] and not rejected
          and aot_health.get("boot") == "aot"
          and speedup >= args.min_speedup)
    if not ok:
        print(f"FAIL: speedup={speedup:.2f} (need >= "
              f"{args.min_speedup}), parity={parity}, "
              f"aot_boot={aot_health.get('boot')!r}, "
              f"rejected={rejected}, donation={probe}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
