"""Pallas implicit-GEMM conv vs the XLA conv emitter, per ResNet-50
hot shape and direction.

Methodology (supersedes the first conv_probe harness): every
measurement value-chains R=64 applications inside one jit and reads
one scalar at the end, so per-program dispatch overhead is amortized
out.  fwd and bwd-input chain directly (Cin == Cout
at the 3x3 shapes); bwd-filter uses a data-dependent perturbation
chain whose per-iteration cost (~one sum pass) is identical for both
implementations.

Usage: python benchmark/pallas_conv_bench.py [--only c2,c4] [--dirs fwd]
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.pallas.conv import _conv_dw_impl, _conv_fwd_impl

SHAPES = [
    ("c2.3x3", 256, 56, 56, 64, 3),
    ("c3.3x3", 256, 28, 28, 128, 3),
    ("c4.3x3", 256, 14, 14, 256, 3),
    ("c5.3x3", 256, 7, 7, 512, 3),
]

R = 64


def xla_conv(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def timed(jf, arg, steps=3):
    out = float(jf(arg))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jf(arg)
    float(out)
    return (time.perf_counter() - t0) / steps / R


def value_chain(fn):
    def run(x0):
        def body(_, y):
            return fn(y)

        y = lax.fori_loop(0, R, body, x0)
        return jnp.sum(y.astype(jnp.float32))

    return jax.jit(run)


def dep_chain(fn):
    def run(x0):
        def body(_, carry):
            x_c, acc = carry
            s = jnp.sum(fn(x_c).astype(jnp.float32))
            dep = jnp.where(jnp.isnan(s), s, 0.0).astype(x0.dtype)
            return x0 + dep, acc + s

        _, acc = lax.fori_loop(0, R, body, (x0, jnp.float32(0)))
        return acc

    return jax.jit(run)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--dirs", type=str, default="fwd,bwd_x,bwd_w")
    args = ap.parse_args()
    only = [t for t in args.only.split(",") if t]
    dirs = args.dirs.split(",")
    rng = np.random.RandomState(0)
    print(f"{'shape':8} {'dir':6} {'xla ms':>8} {'pallas ms':>9} "
          f"{'xla TF':>7} {'pallas TF':>9} {'speedup':>8}", flush=True)
    for name, n, h, w, c, k in SHAPES:
        if only and not any(t in name for t in only):
            continue
        x = jnp.asarray(rng.randn(n, h, w, c), jnp.bfloat16)
        wt = jnp.asarray(rng.randn(k, k, c, c) * 0.03, jnp.bfloat16)
        g = jnp.asarray(rng.randn(n, h, w, c) * 0.03, jnp.bfloat16)
        flops = 2 * n * h * w * c * c * k * k
        w_flip = jnp.flip(wt, (0, 1)).swapaxes(2, 3)

        cases = {}
        if "fwd" in dirs:
            cases["fwd"] = (
                value_chain(lambda v: xla_conv(v, wt).astype(v.dtype)),
                value_chain(lambda v: _conv_fwd_impl(v, wt, k // 2)), x)
        if "bwd_x" in dirs:
            # backward-input == forward conv with flipped/transposed w
            cases["bwd_x"] = (
                value_chain(lambda v: lax.conv_general_dilated(
                    v, w_flip, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(
                        v.dtype)),
                value_chain(lambda v: _conv_fwd_impl(v, w_flip, k // 2)), g)
        if "bwd_w" in dirs:
            def xla_dw(v):
                return jax.grad(
                    lambda ww: jnp.sum(xla_conv(v, ww).astype(jnp.float32)
                                       * g.astype(jnp.float32)))(wt)

            cases["bwd_w"] = (
                dep_chain(xla_dw),
                dep_chain(lambda v: _conv_dw_impl(v, g, k, k // 2)), x)

        for tag, (jx, jp, arg) in cases.items():
            tx = timed(jx, arg)
            tp = timed(jp, arg)
            print(f"{name:8} {tag:6} {tx*1e3:8.3f} {tp*1e3:9.3f} "
                  f"{flops/tx/1e12:7.1f} {flops/tp/1e12:9.1f} "
                  f"{tx/tp:8.2f}x", flush=True)


if __name__ == "__main__":
    main()
