"""Per-shape XLA conv emitter probe at the ResNet-50 BS=256 hot shapes.

Methodology: every measurement value-chains R=64 applications inside
one jit and reads a single scalar, so per-program dispatch overhead is
amortized out of the per-application time:

- square stride-1 convs (Cin == Cout) chain directly: y = conv(y, w);
- expand/reduce 1x1 pairs chain as alternating pairs (C -> 4C -> C),
  reporting the pair average.

The stride-2 downsample/stem shapes are not probed here (no
shape-preserving chain exists for them); they stay on the XLA emitter
unconditionally.

The earlier revision of this file dep-chained with R=8 and read
5-16 TF/s for every shape; those numbers were fixed-overhead
artifacts, not emitter efficiency (PERF.md "Round-4 conv kernel
verdict").

Usage: python benchmark/conv_probe.py [--steps N] [--only c2,c4]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

R = 64

# (name, N, H, W, Cin, Cout, k, stride)
SQUARE = [
    ("c2.3x3", 256, 56, 56, 64, 64, 3, 1),
    ("c3.3x3", 256, 28, 28, 128, 128, 3, 1),
    ("c4.3x3", 256, 14, 14, 256, 256, 3, 1),
    ("c5.3x3", 256, 7, 7, 512, 512, 3, 1),
]
PAIRS = [  # 1x1 expand/reduce bottleneck pairs
    ("c2.1x1", 256, 56, 56, 64, 256),
    ("c3.1x1", 256, 28, 28, 128, 512),
    ("c4.1x1", 256, 14, 14, 256, 1024),
]


def conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def timed(jf, arg, steps, napps):
    out = float(jf(arg))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jf(arg)
    float(out)
    return (time.perf_counter() - t0) / steps / napps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--only", type=str, default="")
    args = ap.parse_args()
    only = [t for t in args.only.split(",") if t]
    rng = np.random.RandomState(0)
    print(f"{'shape':10} {'ms':>8} {'TF/s':>7}", flush=True)

    for name, n, h, w, ci, co, k, s in SQUARE:
        if only and not any(t in name for t in only):
            continue
        x = jnp.asarray(rng.randn(n, h, w, ci), jnp.bfloat16)
        wt = jnp.asarray(rng.randn(k, k, ci, co) * 0.03, jnp.bfloat16)
        flops = 2 * n * h * w * ci * co * k * k

        def run(x0, wt=wt, s=s):
            def body(_, y):
                return conv(y, wt, s)

            return jnp.sum(lax.fori_loop(0, R, body, x0).astype(
                jnp.float32))

        dt = timed(jax.jit(run), x, args.steps, R)
        print(f"{name:10} {dt*1e3:8.3f} {flops/dt/1e12:7.1f}", flush=True)

    for name, n, h, w, ci, co in PAIRS:
        if only and not any(t in name for t in only):
            continue
        x = jnp.asarray(rng.randn(n, h, w, ci), jnp.bfloat16)
        w1 = jnp.asarray(rng.randn(1, 1, ci, co) * 0.05, jnp.bfloat16)
        w2 = jnp.asarray(rng.randn(1, 1, co, ci) * 0.05, jnp.bfloat16)
        flops = 2 * n * h * w * ci * co  # per application (avg of pair)

        def run(x0, w1=w1, w2=w2):
            def body(_, y):
                return conv(conv(y, w1), w2)

            return jnp.sum(lax.fori_loop(0, R // 2, body, x0).astype(
                jnp.float32))

        dt = timed(jax.jit(run), x, args.steps, R)
        print(f"{name:10} {dt*1e3:8.3f} {flops/dt/1e12:7.1f}", flush=True)


if __name__ == "__main__":
    main()
