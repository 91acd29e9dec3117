"""Benchmark: ResNet-50 ImageNet training throughput, single TPU chip.

North-star metric (BASELINE.json): samples/sec/chip, ResNet-50, BS=256.
Baseline (BASELINE.md): the reference's best published ResNet-50
training number is 84.08 img/s (BS=256, 2x Xeon 6148 + MKL-DNN,
benchmark/IntelOptimizedPaddle.md:38-45).  ``vs_baseline`` is the ratio
of our samples/sec to that.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "mfu",
"device"}.  A measurement path: it exits non-zero when jax finds no
TPU, when the device kind has no entry in the peaks table, or when any
step fails — nothing is retried at another size.
"""

import json
import os
import sys
import time

import numpy as np


def build(batch, image, class_dim, dtype="float32", learning_rate=0.1):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet_imagenet

    fluid.framework.reset_default_programs()
    img = fluid.layers.data(name="img", shape=list(image), dtype=dtype)
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    pred = resnet_imagenet(img, class_dim=class_dim)
    loss = fluid.layers.mean(fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=learning_rate,
                             momentum=0.9).minimize(loss)
    return fluid, loss


def require_tpu():
    """The device record every result carries; exits unless jax runs on
    a TPU (TPUPlace itself only means "the default backend")."""
    from paddle_tpu.framework import device_record

    device = device_record()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench: jax found no TPU (platform="
                         f"{device['platform']!r}); a CPU run is not a "
                         "measurement")
    return device


def run(batch=256, image=(3, 224, 224), class_dim=1000, steps=20, warmup=3):
    import jax
    from paddle_tpu import amp

    if os.environ.get("BENCH_AMP", "1") == "1":
        amp.enable()  # bf16 matmul/conv with fp32 master weights
    fluid, loss = build(batch, image, class_dim)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    xs = rng.randn(batch, *image).astype("float32")
    ys = rng.randint(0, class_dim, (batch, 1)).astype("int64")
    import jax.numpy as jnp

    pipeline = os.environ.get("BENCH_PIPELINE", "0") == "1"
    if os.environ.get("BENCH_CHAIN", "1") == "1" and not pipeline:
        # jitted training loop: lax.scan over K steps in ONE program,
        # the standard JAX shape for a training loop — the scanned loop
        # measures the device step itself, without per-step dispatch
        # (BENCH_CHAIN=0 times exe.run per step instead).
        from jax import lax

        fn, state, feeds, _ = exe.build_callable(
            fluid.default_main_program(), {"img": xs, "label": ys},
            [loss.name])
        K = 10

        def multi(state, feeds):
            def body(s, _):
                fetches, s2 = fn(s, feeds)
                return s2, fetches[0]

            s, losses = lax.scan(body, state, None, length=K)
            return losses[-1], s

        jm = jax.jit(multi, donate_argnums=(0,))
        dev_feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        # one warm call compiles and runs K steps — `warmup` and
        # `steps` are interpreted in units of K-step chains here
        # (timed steps round up to >= 2 chains)
        out, state = jm(state, dev_feeds)
        float(np.asarray(out))
        for _ in range(max(warmup // K - 1, 0)):
            out, state = jm(state, dev_feeds)
        float(np.asarray(out))
        reps = max(steps // K, 2)
        # chains dispatch asynchronously inside a block; the best of 5
        # blocks drops inter-block jitter without putting a host sync
        # inside the pipeline
        best, loss_val = float("inf"), 0.0
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(reps):
                out, state = jm(state, dev_feeds)
            loss_val = float(np.asarray(out))  # sync once per block
            best = min(best, time.perf_counter() - t0)
        return batch * reps * K / best, loss_val

    if pipeline:
        # double-buffered host feed: decode-free here (synthetic), but
        # every step pays a fresh host->device transfer that the next
        # step's dispatch overlaps — the trainer's prefetch=True shape
        feeds = [{"img": xs + np.float32(i % 2),
                  "label": ys} for i in range(2)]
        staged = {k: jax.device_put(v) for k, v in feeds[0].items()}
        for _ in range(warmup):
            (l,) = exe.run(feed=staged, fetch_list=[loss],
                           return_numpy=False)
        np.asarray(l)
        t0 = time.perf_counter()
        for i in range(steps):
            (l,) = exe.run(feed=staged, fetch_list=[loss],
                           return_numpy=False)
            staged = {k: jax.device_put(v)
                      for k, v in feeds[(i + 1) % 2].items()}
        loss_val = float(np.asarray(l))
        dt = time.perf_counter() - t0
        return batch * steps / dt, loss_val

    # Device-resident feed: one pre-staged batch measures the training
    # step itself, not the input pipeline (BENCH_PIPELINE=1 measures
    # the double-buffered loader shape).
    feed = {"img": jnp.asarray(xs), "label": jnp.asarray(ys)}

    for _ in range(warmup):
        (l,) = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    np.asarray(l)  # sync

    # async dispatch: materialize the loss once at the end (a real loop
    # logs every N steps, not every step)
    t0 = time.perf_counter()
    for _ in range(steps):
        (l,) = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    loss_val = float(np.asarray(l))  # sync
    dt = time.perf_counter() - t0
    return batch * steps / dt, loss_val


# Published bf16 peak TFLOP/s, keyed by the EXACT ``device_kind`` jax
# reports.  A device that is not in the table is an error, not a
# default: add it with its source.
_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197,
}

_RESNET50_TRAIN_GFLOP_PER_IMG = 12.3  # ~3x the 4.1 GFLOP fwd at 224x224


def _mfu(ips: float) -> float:
    """Model-FLOPs utilization vs the published peak."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_TFLOPS:
        raise SystemExit(f"bench: no published peak for device_kind "
                         f"{kind!r}; add it to _PEAK_TFLOPS with its source")
    peak = _PEAK_TFLOPS[kind]
    if os.environ.get("BENCH_AMP", "1") != "1":
        peak /= 2  # f32 run: the MXU's f32 rate is half the bf16 peak
    return ips * _RESNET50_TRAIN_GFLOP_PER_IMG * 1e9 / (peak * 1e12)


def write_telemetry_artifact(path, headline):
    """Per-run telemetry artifact (schema paddle_tpu.bench_telemetry.v1):
    the headline record plus the observability registry snapshot
    (compile/step/feed/fetch metrics the run accumulated), the span
    ring's events (empty unless the run was made under
    ``observability.recording()``), and a measured per-step telemetry
    overhead with its fraction of the mean cached step.
    """
    from paddle_tpu import observability as obs
    from paddle_tpu.framework import device_record

    snap = obs.snapshot()
    overhead = obs.measure_step_overhead()
    art = {
        "schema": "paddle_tpu.bench_telemetry.v1",
        "headline": headline,
        "device": device_record(),
        "telemetry_overhead_sec_per_step": overhead,
        "metrics": snap,
        "events": obs.GLOBAL_EVENTS.to_chrome_trace(),
    }
    # overhead as a fraction of the mean cached (hot-path) step, when
    # the run produced one — the <=2% budget, measured per run
    step = snap.get("executor_step_seconds", {}).get("values", [])
    hot = [v for v in step
           if v["labels"].get("cached") == "hit" and v["count"]]
    if hot:
        mean = sum(v["sum"] for v in hot) / sum(v["count"] for v in hot)
        if mean > 0:
            art["telemetry_overhead_fraction_of_step"] = overhead / mean
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return path


def main():
    from paddle_tpu import compile_cache

    compile_cache.configure()  # before first backend use
    device = require_tpu()
    baseline = 84.08  # img/s, reference ResNet-50 BS=256 train (see header)
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    ips, loss_val = run(batch=batch, steps=steps)
    if not np.isfinite(loss_val):
        raise SystemExit(f"bench: non-finite loss {loss_val}")
    headline = {
        "metric": f"resnet50_train_samples_per_sec_per_chip_bs{batch}",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / baseline, 2),
        "mfu": round(_mfu(ips), 4),
        "device": device,
    }
    print(json.dumps(headline))
    telemetry_path = os.environ.get("BENCH_TELEMETRY",
                                    "bench_telemetry.json")
    if telemetry_path not in ("", "0", "off"):
        write_telemetry_artifact(telemetry_path, headline)
        print(f"bench: telemetry artifact -> {telemetry_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
