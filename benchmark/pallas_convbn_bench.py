"""Epilogue-fused conv+BN-stats kernel vs XLA's in-model fusion
(the one unexplored ResNet-MFU lever).

Compares, at the ResNet c4/c5 shapes where the plain Pallas conv came
closest (0.83-0.96x), the COMPOSITE forward op the model actually runs:
conv -> batch-statistics (mean/var over N,H,W).  The XLA side is the
jit-fused conv + stats reduction (what the in-model step executes);
the Pallas side accumulates the statistics in the conv's flush epilogue
while the f32 output block is still in VMEM, saving the stats pass's
full-tensor HBM read.

Methodology: R=64 value-chains inside one jit (benchmark/conv_probe.py
— short chains measure per-program dispatch, not the chip); a chained
iteration feeds the conv
output back as input (Cin == Cout at these shapes) and folds mean/var
into the carried value so neither side can dead-code the statistics.

Prints one JSON line per (shape, variant).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.pallas.conv import conv2d_bn_stats_nhwc

SHAPES = [
    # (tag, N, H, W, C==O, K)
    ("c4.3x3", 256, 14, 14, 256, 3),
    ("c5.3x3", 256, 7, 7, 512, 3),
]
R = 64


def xla_conv_bn(x, w):
    out = lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    mean = jnp.mean(out, axis=(0, 1, 2))
    var = jnp.mean(out * out, axis=(0, 1, 2)) - mean * mean
    return out.astype(x.dtype), mean, var


def pallas_conv_bn(x, w, k):
    return conv2d_bn_stats_nhwc(x, w, k // 2)


def chain(fn):
    """Feed conv output back as input; fold the stats into the carry so
    they cannot be dead-coded."""

    def run(x0):
        def body(_, y):
            out, mean, var = fn(y)
            # rank-1 correction keeps stats live at negligible cost
            return out + (mean * 0 + var * 0).astype(out.dtype)

        y = lax.fori_loop(0, R, body, x0)
        return jnp.sum(y.astype(jnp.float32))

    return jax.jit(run)


def timed(jf, arg, steps=3):
    # same discipline as benchmark/pallas_conv_bench.py::timed (R-chain
    # amortization; kept in step with that file's methodology)
    out = float(jf(arg))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jf(arg)
    float(out)
    return (time.perf_counter() - t0) / steps / R


def main():
    rows = []
    for tag, n, h, w, c, k in SHAPES:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, h, w, c).astype(np.float32),
                        dtype=jnp.bfloat16)
        wt = jnp.asarray(rng.randn(k, k, c, c).astype(np.float32) * 0.05,
                        dtype=jnp.bfloat16)
        flops = 2.0 * n * h * w * c * c * k * k

        t_xla = timed(chain(lambda v: xla_conv_bn(v, wt)), x)
        t_pal = timed(chain(lambda v: pallas_conv_bn(v, wt, k)), x)
        row = {
            "shape": tag, "n": n, "hw": h, "c": c, "k": k,
            "xla_fused_ms": round(t_xla * 1e3, 3),
            "pallas_fused_ms": round(t_pal * 1e3, 3),
            "xla_tf_s": round(flops / t_xla / 1e12, 1),
            "pallas_tf_s": round(flops / t_pal / 1e12, 1),
            "pallas_speedup_vs_xla": round(t_xla / t_pal, 3),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    main()
