"""Raw-JAX ResNet-50 training-step ceiling probe.

Hand-rolled NHWC bf16 ResNet-50 (no framework) to measure the best
throughput XLA gives this chip; the framework bench is then tuned
toward this number.  Variants toggled by env:

  CEIL_LAYOUT=NHWC|NCHW   conv data layout (default NHWC)
  CEIL_DTYPE=bf16|f32     activation/param compute dtype (default bf16)
  CEIL_BN=f32|compute     batch-norm statistics dtype (default f32)

Prints one JSON line per run with img/s and MFU.
"""

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LAYOUT = os.environ.get("CEIL_LAYOUT", "NHWC")
DTYPE = jnp.bfloat16 if os.environ.get("CEIL_DTYPE", "bf16") == "bf16" else jnp.float32
BN_F32 = os.environ.get("CEIL_BN", "f32") == "f32"

DN = (("NHWC", "HWIO", "NHWC") if LAYOUT == "NHWC" else ("NCHW", "OIHW", "NCHW"))
C_AXIS = 3 if LAYOUT == "NHWC" else 1


DOT1X1 = os.environ.get("CEIL_DOT1X1", "0") == "1"


def conv(x, w, stride, pad):
    if (DOT1X1 and LAYOUT == "NHWC" and w.shape[0] == 1 and w.shape[1] == 1
            and pad == 0):
        # 1x1 conv as an explicit matmul: XLA's dot emitter sustains a
        # higher fraction of the MXU roofline than the conv emitter at
        # these shapes (measured).  stride-2 = subsample then dot.
        if stride != 1:
            x = x[:, ::stride, ::stride, :]
        return jnp.dot(x, w[0, 0])
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=[(pad, pad), (pad, pad)],
        dimension_numbers=DN)


NO_BN = os.environ.get("CEIL_NOBN", "0") == "1"


def bn(x, scale, bias, eps=1e-5):
    shp = [1, 1, 1, 1]
    shp[C_AXIS] = x.shape[C_AXIS]
    if NO_BN:  # scale+shift only: isolates the cost of the statistics
        return x * scale.reshape(shp) + bias.reshape(shp)
    red = tuple(i for i in range(4) if i != C_AXIS)
    if os.environ.get("CEIL_BN") == "mixed":
        # f32-accumulated stats (fused convert+reduce), bf16 normalize
        m = jnp.mean(x, axis=red, keepdims=True, dtype=jnp.float32)
        v = (jnp.mean(jnp.square(x.astype(jnp.float32)), axis=red,
                      keepdims=True) - jnp.square(m))
        inv = lax.rsqrt(v + eps).astype(x.dtype)
        y = (x - m.astype(x.dtype)) * inv
        return y * scale.reshape(shp) + bias.reshape(shp)
    xf = x.astype(jnp.float32) if BN_F32 else x
    m = jnp.mean(xf, axis=red, keepdims=True)
    v = jnp.mean(jnp.square(xf), axis=red, keepdims=True) - jnp.square(m)
    y = (xf - m) * lax.rsqrt(v + eps)
    return (y * scale.reshape(shp) + bias.reshape(shp)).astype(x.dtype)


def make_params(rng):
    params = []

    def add_conv(cin, cout, k):
        nonlocal rng
        rng, sub = jax.random.split(rng)
        fan = k * k * cin
        shape = (k, k, cin, cout) if LAYOUT == "NHWC" else (cout, cin, k, k)
        w = (jax.random.normal(sub, shape, DTYPE) / float(np.sqrt(fan))).astype(DTYPE)
        params.append(w)
        params.append(jnp.ones((cout,), DTYPE))   # bn scale
        params.append(jnp.zeros((cout,), DTYPE))  # bn bias
        return len(params) - 3

    cfg = {50: (3, 4, 6, 3)}[50]
    idx = {}
    idx["stem"] = add_conv(3, 64, 7)
    cin = 64
    for gi, (count, cmid) in enumerate(zip(cfg, (64, 128, 256, 512))):
        for bi in range(count):
            stride = 2 if (bi == 0 and gi > 0) else 1
            if bi == 0:
                idx[f"g{gi}b{bi}s"] = add_conv(cin, cmid * 4, 1)
            idx[f"g{gi}b{bi}c1"] = add_conv(cin, cmid, 1)
            idx[f"g{gi}b{bi}c2"] = add_conv(cmid, cmid, 3)
            idx[f"g{gi}b{bi}c3"] = add_conv(cmid, cmid * 4, 1)
            cin = cmid * 4
    rng, sub = jax.random.split(rng)
    params.append(jax.random.normal(sub, (2048, 1000), DTYPE) * 0.01)
    params.append(jnp.zeros((1000,), DTYPE))
    idx["fc"] = len(params) - 2
    return params, idx, cfg


def forward(params, idx, cfg, x):
    def cbr(tag, x, stride, pad, relu=True):
        i = idx[tag]
        y = bn(conv(x, params[i], stride, pad), params[i + 1], params[i + 2])
        return jax.nn.relu(y) if relu else y

    x = cbr("stem", x, 2, 3)
    window = [1, 3, 3, 1] if LAYOUT == "NHWC" else [1, 1, 3, 3]
    strides = [1, 2, 2, 1] if LAYOUT == "NHWC" else [1, 1, 2, 2]
    pads = [(0, 0), (1, 1), (1, 1), (0, 0)] if LAYOUT == "NHWC" else [(0, 0), (0, 0), (1, 1), (1, 1)]
    x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max, window,
                          strides, pads)
    for gi, count in enumerate(cfg):
        for bi in range(count):
            stride = 2 if (bi == 0 and gi > 0) else 1
            short = cbr(f"g{gi}b{bi}s", x, stride, 0, relu=False) if f"g{gi}b{bi}s" in idx else x
            y = cbr(f"g{gi}b{bi}c1", x, stride, 0)
            y = cbr(f"g{gi}b{bi}c2", y, 1, 1)
            y = cbr(f"g{gi}b{bi}c3", y, 1, 0, relu=False)
            x = jax.nn.relu(short + y)
    x = jnp.mean(x, axis=(1, 2) if LAYOUT == "NHWC" else (2, 3))
    i = idx["fc"]
    return x.astype(jnp.float32) @ params[i].astype(jnp.float32) + params[i + 1].astype(jnp.float32)


def main():
    batch = int(os.environ.get("CEIL_BATCH", "256"))
    steps = int(os.environ.get("CEIL_STEPS", "20"))
    rng = jax.random.key(0)
    params, idx, cfg = make_params(rng)

    shape = (batch, 224, 224, 3) if LAYOUT == "NHWC" else (batch, 3, 224, 224)
    x = jax.random.normal(jax.random.key(1), shape, DTYPE)
    labels = jax.random.randint(jax.random.key(2), (batch,), 0, 1000)

    def loss_fn(params):
        logits = forward(params, idx, cfg, x)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - ll)

    flat_update = os.environ.get("CEIL_FLATOPT", "1") == "1"
    if flat_update:
        # One fused SGD-momentum kernel over a single flat master buffer:
        # 157 per-tensor updates cost ~140us each in dispatch/fixup alone
        # (measured); one flat kernel is pure bandwidth.
        sizes = [int(np.prod(p.shape)) for p in params]
        offs = np.cumsum([0] + sizes)
        master = jnp.concatenate([p.astype(jnp.float32).ravel() for p in params])
        mom_flat = jnp.zeros_like(master)

        def unflatten(flat):
            return [lax.dynamic_slice(flat, (int(offs[i]),), (sizes[i],))
                    .reshape(params[i].shape).astype(params[i].dtype)
                    for i in range(len(params))]

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(master, mom_flat):
            ps = unflatten(master)
            loss, grads = jax.value_and_grad(loss_fn)(ps)
            gflat = jnp.concatenate(
                [g.astype(jnp.float32).ravel() for g in grads])
            mom_flat = 0.9 * mom_flat + gflat
            master = master - 0.1 * mom_flat
            return loss, master, mom_flat

        for _ in range(3):
            loss, master, mom_flat = step(master, mom_flat)
        float(np.asarray(loss))
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, master, mom_flat = step(master, mom_flat)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
        ips = batch * steps / dt
        tflops = ips * 12.3e9 / 1e12
        print(json.dumps({
            "layout": LAYOUT, "dtype": str(DTYPE.__name__), "bn_f32": BN_F32,
            "flat_opt": True, "img_per_sec": round(ips, 1),
            "est_tflops": round(tflops, 1),
            "mfu_vs_197tflops": round(tflops / 197, 3), "loss": float(loss),
        }))
        return

    moms = [jnp.zeros_like(p, dtype=jnp.float32) for p in params]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, moms):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_p, new_m = [], []
        for p, m, g in zip(params, moms, grads):
            m = 0.9 * m + g.astype(jnp.float32)
            new_m.append(m)
            new_p.append((p.astype(jnp.float32) - 0.1 * m).astype(p.dtype))
        return loss, new_p, new_m

    mode = os.environ.get("CEIL_MODE", "step")
    if mode == "fwd":
        fwd = jax.jit(lambda p: jnp.sum(forward(p, idx, cfg, x)))
        for _ in range(3):
            out = fwd(params)
        float(np.asarray(out))  # host read = sync
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fwd(params)
        float(np.asarray(out))
        dt = time.perf_counter() - t0
        loss = out
    else:
        for _ in range(3):
            loss, params, moms = step(params, moms)
        float(np.asarray(loss))
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, params, moms = step(params, moms)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
    ips = batch * steps / dt
    tflops = ips * 12.3e9 / 1e12  # ~3x fwd FLOPs, 4.1 GFLOP/img fwd
    print(json.dumps({
        "layout": LAYOUT, "dtype": str(DTYPE.__name__), "bn_f32": BN_F32,
        "img_per_sec": round(ips, 1), "est_tflops": round(tflops, 1),
        "mfu_vs_197tflops": round(tflops / 197, 3), "loss": float(loss),
    }))


if __name__ == "__main__":
    main()
