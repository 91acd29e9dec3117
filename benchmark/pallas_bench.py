"""Pallas-vs-XLA microbenchmarks on the real TPU.

Each kernel in paddle_tpu/pallas must earn its place: this prints a per-kernel table of Pallas time vs the XLA lowering it
shadows.  The defaults in paddle_tpu/pallas/__init__.py follow the
winners of an earlier setup's run; nothing is re-measured on the
locally attached v5e yet.

All timings end in a host read of the result.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# repo root importable without PYTHONPATH
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


CHAIN = 8  # sequential in-jit applications: amortizes per-program
           # dispatch that would otherwise make the loop host-bound

# --tuned: let kernel dispatch consult the checked-in tuning database
# (paddle_tpu/pallas/tuning).  Without it the DB is disabled so the
# pallas column measures the hard-coded defaults — run both to get the
# tuned-vs-default A/B rows.
TUNED = False


def timeit(fn, *args, reps=10, warmup=2):
    """fn must be a jitted callable that runs its op CHAIN times with a
    data dependency; returns seconds per single application."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / (reps * CHAIN)


def _sync(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def row(name, xla_ms, pal_ms):
    speedup = xla_ms / pal_ms
    verdict = "pallas" if speedup > 1.05 else ("tie" if speedup > 0.95 else "xla")
    print(json.dumps({"bench": name, "xla_ms": round(xla_ms, 3),
                      "pallas_ms": round(pal_ms, 3),
                      "speedup": round(speedup, 2), "winner": verdict}))


def bench_matmul():
    from paddle_tpu.pallas.matmul import matmul

    for n in (1024, 2048, 4096):
        x = jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16)
        y = jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16)

        def chain(mm):
            def run(a, b):
                for _ in range(CHAIN):
                    a = mm(a, b) * jnp.bfloat16(1e-3)
                return a
            return jax.jit(run)

        xla = chain(lambda a, b: jnp.dot(a, b))
        # unset blocks resolve via the tuning DB (disabled = defaults)
        pal = chain(lambda a, b: matmul(a, b))
        row(f"matmul_{n}x{n}_bf16", timeit(xla, x, y) * 1e3,
            timeit(pal, x, y) * 1e3)


def bench_softmax():
    from paddle_tpu.pallas.softmax import softmax

    for rows, cols in ((8192, 512), (16384, 128), (4096, 1024)):
        x = jax.random.normal(jax.random.key(0), (rows, cols), jnp.float32)

        def chain(sm):
            def run(a):
                for _ in range(CHAIN):
                    a = sm(a) + a
                return a
            return jax.jit(run)

        xla = chain(lambda a: jax.nn.softmax(a, axis=-1))
        pal = chain(lambda a: softmax(a))
        row(f"softmax_{rows}x{cols}", timeit(xla, x) * 1e3,
            timeit(pal, x) * 1e3)


def bench_gather():
    from paddle_tpu.pallas.embedding import gather_rows

    v, d, n = 50304, 512, 8192
    w = jax.random.normal(jax.random.key(0), (v, d), jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (n,), 0, v, jnp.int32)

    def chain(g):
        def run(w, ids):
            acc = jnp.zeros((), jnp.int32)
            for _ in range(CHAIN):
                out = g(w, (ids + acc) % v)
                acc = out[0, 0].astype(jnp.int32) % 2
            return acc
        return jax.jit(run)

    xla = chain(lambda w, i: jnp.take(w, i, axis=0))
    pal = chain(lambda w, i: gather_rows(w, i))
    row(f"gather_{v}x{d}_n{n}", timeit(xla, w, ids) * 1e3,
        timeit(pal, w, ids) * 1e3)


def _lstm_ref(xp, w, b, h0, c0):
    from jax import lax

    def step(carry, xt):
        h, c = carry
        gates = xt + jnp.dot(h, w, preferred_element_type=jnp.float32
                             ).astype(xt.dtype) + b
        i, f, g, o = jnp.split(gates, 4, -1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), (h, c)

    _, (hs, cs) = lax.scan(step, (h0, c0), xp)
    return hs, cs


def bench_lstm():
    from paddle_tpu.pallas.lstm import lstm_seq

    for t, b, h in ((100, 64, 256), (128, 32, 512), (256, 128, 128)):
        dt = jnp.float32
        xp = jax.random.normal(jax.random.key(0), (t, b, 4 * h), dt) * 0.1
        w = jax.random.normal(jax.random.key(1), (h, 4 * h), dt) * 0.05
        bias = jnp.zeros((4 * h,), dt)
        h0 = jnp.zeros((b, h), dt)
        c0 = jnp.zeros((b, h), dt)

        def chain_f(f):
            def run(xp, w, bias, h0, c0):
                for _ in range(CHAIN):
                    hs = f(xp, w, bias, h0, c0)[0]
                    h0 = hs[-1]
                return h0
            return jax.jit(run)

        row(f"lstm_fwd_T{t}_B{b}_H{h}",
            timeit(chain_f(_lstm_ref), xp, w, bias, h0, c0) * 1e3,
            timeit(chain_f(lstm_seq), xp, w, bias, h0, c0) * 1e3)

        def chain_g(f):
            def loss(xp, w, bias, h0, c0):
                hs, _ = f(xp, w, bias, h0, c0)
                return jnp.sum(hs ** 2)

            g = jax.grad(loss, argnums=(0, 4))

            def run(xp, w, bias, h0, c0):
                for _ in range(CHAIN):
                    dxp, dh0 = g(xp, w, bias, h0, c0)
                    h0 = h0 + dh0 * 1e-6
                return h0
            return jax.jit(run)

        row(f"lstm_grad_T{t}_B{b}_H{h}",
            timeit(chain_g(_lstm_ref), xp, w, bias, h0, c0) * 1e3,
            timeit(chain_g(lstm_seq), xp, w, bias, h0, c0) * 1e3)


def bench_batch_norm():
    """ResNet-50 BS=256 BN shapes, channel-minor (R=N*H*W, C) view."""
    from paddle_tpu.pallas.batch_norm import batch_norm_train, _bn_fwd_impl
    from jax import lax

    eps = 1e-5

    def xla_bn(x, g, b):
        m = jnp.mean(x, 0, dtype=jnp.float32)
        v = jnp.mean(jnp.square(x.astype(jnp.float32)), 0) - m * m
        inv = lax.rsqrt(v + eps)
        a = g.astype(jnp.float32) * inv
        bb = b.astype(jnp.float32) - m * a
        return (x * a.astype(x.dtype)[None] + bb.astype(x.dtype)[None],
                m, v)

    for R, C in ((256 * 56 * 56, 256), (256 * 28 * 28, 512),
                 (256 * 14 * 14, 1024)):
        x = jax.random.normal(jax.random.key(0), (R, C), jnp.bfloat16)
        g = jnp.ones((C,), jnp.float32)
        b = jnp.zeros((C,), jnp.float32)

        def chain_f(bn):
            def run(x, g, b):
                for _ in range(CHAIN):
                    y, m, v = bn(x, g, b)
                    x = y + jnp.asarray(1e-6, y.dtype)
                return x
            return jax.jit(run)

        row(f"batch_norm_fwd_R{R}_C{C}",
            timeit(chain_f(xla_bn), x, g, b) * 1e3,
            timeit(chain_f(lambda x, g, b: _bn_fwd_impl(x, g, b, eps)),
                   x, g, b) * 1e3)

        def chain_t(bn):
            def loss(x, g, b):
                acc = x
                for _ in range(CHAIN):
                    y, m, v = bn(acc, g, b)
                    acc = y + jnp.asarray(1e-6, y.dtype)
                return jnp.sum(acc.astype(jnp.float32))

            def run(x, g, b):
                return jax.grad(loss)(x, g, b)
            return jax.jit(run)

        row(f"batch_norm_train_R{R}_C{C}",
            timeit(chain_t(xla_bn), x, g, b) * 1e3,
            timeit(chain_t(batch_norm_train), x, g, b) * 1e3)


def bench_flash_attention():
    """Transformer-flagship shapes (B=8 H=16 D=128) + long-context."""
    from paddle_tpu.pallas.flash_attention import flash_attention
    from paddle_tpu.parallel.ring_attention import local_attention

    for BH, S, D in ((128, 1024, 128), (128, 2048, 128), (16, 8192, 128)):
        q, k, v = (jax.random.normal(jax.random.key(i), (BH, S, D),
                                     jnp.bfloat16) for i in range(3))

        def jnp_attn(q, k, v):
            o = local_attention(q[:, None], k[:, None], v[:, None],
                                causal=True)
            return o[:, 0]

        def fl_attn(q, k, v):
            return flash_attention(q, k, v, True)

        def chain_f(f):
            def run(q, k, v):
                for _ in range(CHAIN):
                    o = f(q, k, v)
                    q = o + jnp.asarray(1e-3, o.dtype)
                return o
            return jax.jit(run)

        row(f"flash_attn_fwd_BH{BH}_S{S}_D{D}",
            timeit(chain_f(jnp_attn), q, k, v) * 1e3,
            timeit(chain_f(fl_attn), q, k, v) * 1e3)

        def chain_t(f):
            def loss(q, k, v):
                acc = q
                for _ in range(CHAIN):
                    acc = f(acc, k, v) + jnp.asarray(1e-3, q.dtype)
                return jnp.sum(acc.astype(jnp.float32))

            def run(q, k, v):
                return jax.grad(loss)(q, k, v)
            return jax.jit(run)

        row(f"flash_attn_train_BH{BH}_S{S}_D{D}",
            timeit(chain_t(jnp_attn), q, k, v) * 1e3,
            timeit(chain_t(fl_attn), q, k, v) * 1e3)


if __name__ == "__main__":
    import sys

    from paddle_tpu.pallas import tuning

    args = [a for a in sys.argv[1:] if a != "--tuned"]
    TUNED = len(args) != len(sys.argv) - 1
    if not TUNED:
        tuning.disable()
    which = args[0] if args else "all"
    if which in ("all", "matmul"):
        bench_matmul()
    if which in ("all", "softmax"):
        bench_softmax()
    if which in ("all", "gather"):
        bench_gather()
    if which in ("all", "lstm"):
        bench_lstm()
    if which in ("all", "batch_norm"):
        bench_batch_norm()
    if which in ("all", "flash"):
        bench_flash_attention()
