"""Transformer-LM training throughput + MFU on one chip.

The matmul-dominated counterpart to the ResNet headline bench: shows
the framework sustaining high MXU utilization where the model shape
allows it (PERF.md documents why ResNet-50's convs+BN cannot).  Runs
the framework's own transformer (models/transformer.py) through the
compiling Executor under bf16 AMP.

Prints one JSON line: tokens/sec, step ms, model TFLOP/step and MFU vs
the published peak.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NOMINAL_PEAK = {"TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v4": 275e12,
                "TPU v5p": 459e12, "TPU v3": 123e12}


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import amp
    from paddle_tpu.models import transformer_lm_loss

    B = int(os.environ.get("TB_BATCH", "8"))
    S = int(os.environ.get("TB_SEQ", "1024"))
    D = int(os.environ.get("TB_DMODEL", "2048"))
    L = int(os.environ.get("TB_LAYERS", "4"))
    V = int(os.environ.get("TB_VOCAB", "32768"))
    steps = int(os.environ.get("TB_STEPS", "10"))
    recompute = os.environ.get("TB_RECOMPUTE", "0") == "1"
    if os.environ.get("BENCH_AMP", "1") == "1":
        amp.enable()

    fluid.framework.reset_default_programs()
    tokens = fluid.layers.data(name="tokens", shape=[S, 1], dtype="int64")
    labels = fluid.layers.data(name="labels", shape=[S, 1], dtype="int64")
    loss = transformer_lm_loss(tokens, labels=labels, vocab_size=V,
                               d_model=D, num_heads=D // 128, num_layers=L,
                               recompute=recompute)
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())

    rng = np.random.RandomState(0)
    feed = {"tokens": jnp.asarray(rng.randint(0, V, (B, S, 1)).astype(np.int64)),
            "labels": jnp.asarray(rng.randint(0, V, (B, S, 1)).astype(np.int64))}
    if os.environ.get("BENCH_CHAIN", "1") == "1":
        # scanned K-step training loop in one jitted program — the
        # same methodology as bench.py: per-step dispatch is not device
        # time.  BENCH_CHAIN=0 restores per-dispatch timing.
        from jax import lax

        fn, state, feeds, uses_rng = exe.build_callable(
            fluid.default_main_program(),
            {k: np.asarray(v) for k, v in feed.items()}, [loss.name])
        K = 5

        def multi(state, feeds, base_seed):
            def body(s, i):
                fetches, s2 = (fn(s, feeds, base_seed + i) if uses_rng
                               else fn(s, feeds))
                return s2, fetches[0]

            s, losses = lax.scan(body, state, jnp.arange(K))
            return losses[-1], s

        jm = jax.jit(multi, donate_argnums=(0,))
        dev_feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
        # base_seed advances per macro-step so random ops never replay
        # the same mask across reps
        out, state = jm(state, dev_feeds, jnp.int32(0))
        float(np.asarray(out))
        reps = max(steps // K, 2)
        t0 = time.perf_counter()
        for r in range(reps):
            out, state = jm(state, dev_feeds, jnp.int32((r + 1) * K))
        lv = float(np.asarray(out))
        dt = (time.perf_counter() - t0) / (reps * K)
    else:
        for _ in range(3):
            (l,) = exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
        float(np.asarray(l))  # host-read sync
        t0 = time.perf_counter()
        for _ in range(steps):
            (l,) = exe.run(feed=feed, fetch_list=[loss],
                           return_numpy=False)
        lv = float(np.asarray(l))
        dt = (time.perf_counter() - t0) / steps

    # model FLOPs per step: 6 * non-embedding params * tokens for the
    # blocks, + 6 * D * V * tokens for the logits matmul
    block_params = L * 12 * D * D
    tokens_per_step = B * S
    flops = 6 * block_params * tokens_per_step \
        + 6 * D * V * tokens_per_step
    kind = jax.devices()[0].device_kind
    peak = next((v for k, v in NOMINAL_PEAK.items() if kind.startswith(k)),
                197e12)
    print(json.dumps({
        "metric": f"transformer_lm_train_B{B}_S{S}_D{D}_L{L}"
                  + ("_remat" if recompute else ""),
        "tokens_per_sec": round(tokens_per_step / dt, 1),
        "ms_per_step": round(dt * 1e3, 2),
        "model_tflop_per_step": round(flops / 1e12, 2),
        "mfu_vs_nominal": round(flops / dt / peak, 3),
        "loss": round(lv, 3),
    }))


if __name__ == "__main__":
    main()
