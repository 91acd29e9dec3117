"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three paths users of this system run, through their normal
entry points, in ONE process on one TPU v5e:

- train   ResNet-50 (ImageNet shape, BS=256, bf16 AMP, Momentum) stepped
          through ``fluid.Executor.run``, then one small-batch step on
          TPUPlace vs CPUPlace from one captured startup state;
- kernels the three Pallas kernels ``auto`` mode dispatches on a TPU
          (flash attention fwd+bwd in the transformer LM, the fused LSTM,
          the narrow-row softmax), each checked present in the step's
          program and compared with the same step under
          ``pallas.enable(False)``; and a hybrid's decode step (three
          Gated-DeltaNet layers and a full one at the published head
          shapes), whose state entries the ``conv_step`` and
          ``gated_delta_step`` kernels advance, against the same step's
          loop over the slots, and its 4,096-row prefill, whose linear
          layers run ``gated_delta_chunked``, against the XLA form;
- serve   the HTTP server exactly as ``paddle serve`` builds it:
          ``/health``, ``/predict`` on a ResNet-50 inference export, and
          ``/generate`` over the paged-KV decode engine vs the same
          requests with the jnp reference attention.

``--chips 4`` runs ONLY the multi-chip phase (ResNet-50 dp=4 and the
transformer tp=2 x sp=2 with ring attention, each against the same
program on a one-device mesh).

It fails — exits non-zero, prints no result line — when jax finds no
TPU.  Every phase that fails raises; nothing is retried or skipped.
Weights and data are random, made from ``--seed``.  The LAST line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import contextlib
import json
import os
import runpy
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

# What a chip run must see.  tests/test_chip_smoke.py swaps these for
# the CPU's (platform "cpu", interpreted kernels, no custom-call marker)
# to run the phase functions at toy size; the script has no such option.
EXPECT = {"platform": "tpu", "kernel_path": "compiled",
          "marker": "tpu_custom_call", "memory_stats": True}

SIZES = {
    # ResNet-50 at BS=256 on 3x224x224 over 1,000 classes: the
    # resnet50-train-bs256 cell's program (perf/configs/resnet50.json)
    "train": dict(batch=256, image=(3, 224, 224), classes=1000, steps=6,
                  small_batch=8),
    # a 4-layer d=2048 LM block at S=1024, where flash attention starts
    "transformer": dict(B=8, S=1024, D=2048, L=4, V=32768, steps=3),
    # an embedding of 512 into one LSTM of 256 hidden units over 100
    # steps: inside the LSTM kernel's hidden-size threshold
    "lstm": dict(B=64, T=100, emb=512, hidden=256, steps=3),
    "softmax": dict(rows=4096, cols=256, steps=3),
    # one period of Olmo-Hybrid at the published head shapes (30 linear
    # heads of d_k 96, d_v 192; full heads of 128), the widths shrunk
    "hybrid": dict(slots=8, steps=3, model=dict(
        vocab=4096, d_model=1024, num_heads=8, head_dim=128,
        intermediate_size=2048, max_len=512, num_pages=40, page_size=32,
        pages_per_seq=4, state_entries=9),
        # the same period's prefill over the cell's longest bucket
        prefill=dict(rows=4096, max_len=4608, num_pages=40, page_size=128,
                     pages_per_seq=36)),
    "serve": dict(image=(3, 224, 224), classes=1000, batches=(1, 3, 8),
                  gen_requests=6, gen_slots=4, gen_tokens=16),
    # global batch 256 over dp=4; the hybrid runs S=2048 so each sp=2
    # shard holds S_local=1024 and the ring's per-shard blocks take the
    # flash kernel under the unchanged auto rule (S_local >= 1024)
    "multichip": dict(resnet=dict(batch=256, image=(3, 224, 224),
                                  classes=1000, steps=3),
                      transformer=dict(B=4, S=2048, D=2048, L=2, V=32768,
                                       steps=3)),
}

GEN_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "demos", "decoder_lm", "gen_config.py")


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def require_device(chips):
    """The device line; raises unless jax runs on EXPECT's platform with
    exactly ``chips`` devices."""
    from paddle_tpu.framework import device_record

    device = device_record()
    if device["platform"] != EXPECT["platform"]:
        raise SystemExit(
            f"chip_smoke: jax found no {EXPECT['platform']} "
            f"(platform={device['platform']!r}); this script measures "
            "nothing on another backend")
    if device["count"] != chips:
        raise SystemExit(f"chip_smoke: need {chips} device(s), jax sees "
                         f"{device['count']}")
    return device


# -- helpers -------------------------------------------------------------


def _counter(name, **labels):
    """Sum a registry counter over the label sets matching ``labels``."""
    from paddle_tpu.observability import metrics

    fam = metrics.snapshot().get(name, {"values": []})
    return sum(v["value"] for v in fam["values"]
               if all(v["labels"].get(k) == w for k, w in labels.items()))


def _last_step(exe, scope, feed):
    """(compiled entry, lowered step) of the executor's newest compile —
    the same reach-in tests/test_recompute.py uses."""
    comp = list(exe._cache.values())[-1]
    state = {n: scope.values[n] for n in comp.state_names}
    rest = (np.int64(0),) if comp.uses_rng else ()
    return comp, comp.fn.lower(state, feed, *rest)


def _assert_kernel(kernel, text, before):
    """The kernel was dispatched on EXPECT's path since ``before`` and,
    on a chip, its custom call is in the program text."""
    ran = _counter("pallas_dispatch_total", kernel=kernel,
                   path=EXPECT["kernel_path"]) - before
    assert ran > 0, (f"{kernel}: no {EXPECT['kernel_path']} dispatch "
                     "counted — the reference lowering ran instead")
    found = ""
    if EXPECT["marker"]:
        n = text.count(EXPECT["marker"])
        assert n > 0, f"{kernel}: no {EXPECT['marker']} in the step program"
        found = f", {EXPECT['marker']} x{n} in the step program"
    say(f"  kernel {kernel}: {int(ran)} {EXPECT['kernel_path']} "
        f"dispatch(es){found}")


def _run_steps(exe, scope, program, startup, feed, loss, steps, watch=None):
    """startup + ``steps`` steps through exe.run; returns (losses,
    first-step seconds incl. compile, median later-step seconds, and —
    with ``watch`` naming a parameter — that parameter's first update:
    its value after step 0 minus its startup value)."""
    secs, losses, update = [], [], None
    exe.run(startup, scope=scope)
    for i in range(steps):
        if watch and i == 0:
            update = -np.asarray(scope.values[watch], np.float32)
        t0 = time.perf_counter()
        (l,) = exe.run(program, feed=feed, fetch_list=[loss], scope=scope)
        secs.append(time.perf_counter() - t0)
        losses.append(float(l))
        if watch and i == 0:
            update += np.asarray(scope.values[watch], np.float32)
    later = sorted(secs[1:])
    return (losses, secs[0],
            later[len(later) // 2] if later else float("nan"), update)


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-8)


# -- phase: train --------------------------------------------------------


def _resnet50(batch, image, classes):
    """The resnet50 cells' training program (ResNet-50, mean
    cross-entropy, Momentum 0.9) at the smoke's sizes, at the cells'
    learning rate: on one repeated random batch 0.1 without warm-up
    overshoots for the first dozen steps, and phase_train asserts the
    loss falls."""
    from perf.programs import resnet_imagenet

    cfg = {"image": image, "class_dim": classes, "depth": 50,
           "optimizer": {"learning_rate": 0.01, "momentum": 0.9}}
    return resnet_imagenet.build(cfg, {"batch": batch})


def phase_train(size, seed):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import amp, executor as em

    amp.enable()  # bf16 matmul/conv, fp32 master weights
    batch, image, classes = size["batch"], size["image"], size["classes"]
    built = _resnet50(batch, image, classes)
    loss, main, startup = built["loss"], built["main"], built["startup"]
    rng = np.random.RandomState(seed)
    xs = rng.randn(batch, *image).astype("float32")
    ys = rng.randint(0, classes, (batch, 1)).astype("int64")
    feed = {"img": jnp.asarray(xs), "label": jnp.asarray(ys)}  # on device

    exe, scope = fluid.Executor(fluid.TPUPlace()), em.Scope()
    losses, first, later, _ = _run_steps(exe, scope, main, startup, feed,
                                         loss, size["steps"])
    comp, _ = _last_step(exe, scope, feed)
    say(f"  resnet50 bs={batch} bf16: compile+first step {first:.1f}s, "
        f"later steps median {later * 1e3:.1f} ms (host clock around "
        f"exe.run incl. loss fetch), losses {[round(l, 4) for l in losses]}")
    assert comp.donated_names, "donation: OFF — the train step's mask is empty"
    say(f"  donation: on — {len(comp.donated_names)} of "
        f"{len(comp.state_names)} state buffers donated")
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    where = {d.platform for v in scope.values.values()
             if hasattr(v, "devices") for d in v.devices()}
    assert where == {EXPECT["platform"]}, f"parameters live on {where}"
    say(f"  parameters on {sorted(where)}; memory_stats peak_bytes_in_use "
        f"{_peak_gb(jax.devices()[0])}")

    # does block_until_ready block?  Dispatch steps without fetching,
    # block, then read the loss: if the block waited for the device the
    # host read after it returns at once.
    t0 = time.perf_counter()
    for _ in range(3):
        (l,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                       return_numpy=False)
    t1 = time.perf_counter()
    jax.block_until_ready(l)
    t2 = time.perf_counter()
    float(np.asarray(l))
    t3 = time.perf_counter()
    say(f"  3 steps dispatched without a fetch in {(t1 - t0) * 1e3:.1f} ms; "
        f"block_until_ready then waited {(t2 - t1) * 1e3:.1f} ms; the host "
        f"read after it took {(t3 - t2) * 1e3:.2f} ms")
    assert t3 - t2 < 0.25 * later, (
        "block_until_ready returned before the device was done: the "
        "host read after it still waited")

    # the same program at a small batch: one step on TPUPlace and on
    # CPUPlace from ONE captured startup state
    sb = size["small_batch"]
    small = {"img": xs[:sb], "label": ys[:sb]}
    sc0 = em.Scope()
    fluid.Executor(fluid.TPUPlace()).run(startup, scope=sc0)
    state0 = {n: np.asarray(v) for n, v in sc0.values.items()}
    got = {}
    for name, place in (("default", fluid.TPUPlace()),
                        ("cpu", fluid.CPUPlace())):
        sc = em.Scope()
        for n, v in state0.items():
            sc.set(n, v)
        t0 = time.perf_counter()
        (l,) = fluid.Executor(place).run(main, feed=small, fetch_list=[loss],
                                         scope=sc)
        got[name] = float(l)
        say(f"  small batch {sb} on {type(place).__name__}: loss "
            f"{got[name]:.5f} ({time.perf_counter() - t0:.1f}s incl. compile)")
    # Both places round the same bf16 operands and accumulate in f32;
    # they differ in accumulation order, which flips a few activations
    # by one bf16 ulp (2^-8 = 3.9e-3 relative).  One step from one
    # state keeps the loss well inside 2e-2; a wrong lowering on either
    # backend moves a ~ln(classes) loss by far more.
    tol = 2e-2
    rel = _rel(got["default"], got["cpu"])
    assert rel <= tol, f"TPUPlace vs CPUPlace loss rel {rel:.2e} > {tol}"
    say(f"  TPUPlace vs CPUPlace: rel {rel:.2e} (tol {tol})")
    amp.enable(False)


def _rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def _peak_gb(dev):
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return f"{peak / 2**30:.2f} GiB" if peak else "not reported"


# -- phase: kernels in a model --------------------------------------------


def _kernel_case(name, kernel, build, feed, steps, tol):
    """Train ``steps`` steps with the kernel dispatched (auto mode) and
    again under ``pallas.enable(False)``, both from the same seeded
    init; the kernel must be in the first program and the losses agree
    within ``tol`` (relative, per step)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import executor as em, pallas as pk

    runs = {}
    for mode in ("auto", "off"):
        pk.enable(mode)
        jax.clear_caches()  # dispatch is decided at trace time
        fluid.framework.reset_default_programs()
        loss = build()
        main, startup = (fluid.default_main_program(),
                         fluid.default_startup_program())
        before = _counter("pallas_dispatch_total", kernel=kernel,
                          path=EXPECT["kernel_path"])
        exe, scope = fluid.Executor(fluid.TPUPlace()), em.Scope()
        losses, first, later, _ = _run_steps(exe, scope, main, startup,
                                             feed, loss, steps)
        say(f"  {name} pallas={mode}: compile+first step {first:.1f}s, "
            f"later steps median {later * 1e3:.1f} ms, "
            f"losses {[round(l, 5) for l in losses]}")
        assert all(np.isfinite(losses)), f"{name}: non-finite loss {losses}"
        if mode == "auto":
            _assert_kernel(kernel, _last_step(exe, scope, feed)[1].as_text(),
                           before)
        runs[mode] = losses
    pk.enable("auto")
    worst = max(_rel(a, b) for a, b in zip(runs["auto"], runs["off"]))
    assert worst <= tol, (f"{name}: kernel vs XLA lowering loss rel "
                          f"{worst:.2e} > {tol}")
    say(f"  {name}: kernel vs pallas.enable(False) worst rel {worst:.2e} "
        f"(tol {tol})")


def _hybrid_step_case(size, seed, tol=2e-2, state_tol=1e-5):
    """A hybrid's decode step (three linear layers, then a full one)
    with the conv's and the state's kernels dispatched and again under
    ``pallas.enable(False)``, both from the same seeded weights, pages
    and state entries, the same tokens forced: the dispatch counter
    moved once a linear layer for each kernel; the first layer's
    entries, whose inputs are the forced tokens' alone and so the same
    rows on both sides, agree within ``state_tol`` (relative RMS:
    float32 rounding, only the kernel differs) and the rows its conv
    keeps bit for bit; the logits and the later layers' entries, fed
    through bf16 casts of what came before, within ``tol``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import pallas as pk
    from paddle_tpu.models.olmo_hybrid import FULL, LINEAR, OlmoHybridLM

    S, types = size["slots"], (LINEAR, LINEAR, LINEAR, FULL)
    runs = {}
    for mode in ("auto", "off"):
        pk.enable(mode)
        jax.clear_caches()  # dispatch is decided at trace time
        m = OlmoHybridLM(seed=seed, layer_types=types, **size["model"])
        keys = jax.random.split(jax.random.key(seed), 4)
        m.k_pool, m.v_pool, states, tails = (
            jax.random.normal(k, p.shape, jnp.float32).astype(p.dtype)
            for k, p in zip(keys, m._cache()))
        # entries as a prefill leaves them: zero beyond the key width
        m.extra_pools = (
            states * (jnp.arange(states.shape[-1]) < m.block.d_k), tails)
        # all slots but the last seated, each on a page run and on an
        # entry of its own, out of order
        tables = np.zeros((S, m.pages_per_seq), np.int32)
        entries = 1 + np.random.RandomState(seed).permutation(S - 1)
        for s in range(S - 1):
            tables[s, :m.full_pages] = 1 + s * m.full_pages + np.arange(
                m.full_pages)
            tables[s, m.full_pages] = entries[s]
        lens = np.where(np.arange(S) < S - 1, 40 + 3 * np.arange(S), 0)
        def dispatched():
            return {k: _counter("pallas_dispatch_total", kernel=k,
                                path=EXPECT["kernel_path"])
                    for k in ("conv_step", "gated_delta_step")}

        before = dispatched()
        rows = []
        for step in range(size["steps"]):
            tokens = np.random.RandomState(seed + step).randint(
                2, m.vocab, (S, 1))
            logits, _ = m.decode(tokens, [], tables,
                                 (lens + step).astype(np.int32))
            rows.append(np.asarray(logits)[:S - 1])
        if mode == "auto":
            for kernel, n in dispatched().items():
                ran = n - before[kernel]
                assert ran == types.count(LINEAR), (
                    f"{kernel}: {ran} {EXPECT['kernel_path']} dispatches "
                    "in one traced step, not one a linear layer")
                say(f"  kernel {kernel}: {int(ran)} "
                    f"{EXPECT['kernel_path']} dispatch(es) in the step")
        live = np.unique(tables[:S - 1, m.full_pages])
        runs[mode] = (np.stack(rows), np.asarray(m.state_pool)[:, live],
                      np.asarray(m.conv_pool.astype(jnp.float32))[:, live])
        assert np.isfinite(runs[mode][0]).all(), "hybrid: non-finite logits"
    pk.enable("auto")

    (logits, states, tails), (logits_off, states_off, tails_off) = (
        runs["auto"], runs["off"])
    # the conv's kernel only moves rows: the first layer's, whose
    # inputs are the same on both sides, are the slot loop's bit for bit
    assert (tails[0] == tails_off[0]).all() and tails[0].any(), (
        "hybrid step: conv_step left other rows in the first layer's "
        "entries than the slot loop")
    worst = _rel_rms(logits, logits_off)
    first = _rel_rms(states[0], states_off[0])
    later = _rel_rms(states[1:], states_off[1:])
    assert first <= state_tol and max(worst, later) <= tol, (
        f"hybrid step: kernel vs XLA slot loop: first layer's entries rel "
        f"RMS {first:.2e} (tol {state_tol}), later layers' {later:.2e}, "
        f"logits {worst:.2e} (tol {tol})")
    say(f"  hybrid step: kernel vs pallas.enable(False) first layer's "
        f"entries rel RMS {first:.2e} (tol {state_tol}), later layers' "
        f"{later:.2e}, logits {worst:.2e} (tol {tol})")


def _hybrid_prefill_case(size, seed, tol=2e-2, state_tol=1e-5):
    """The same period's prefill of one bucket of ``rows`` rows with
    the kernels dispatched and again under ``pallas.enable(False)``,
    the same seeded weights and ids: the dispatch counter moved once a
    linear layer for ``gated_delta_chunked``; the first layer's state
    as the prefill leaves it in the entry, whose inputs are the
    embeddings alone and so the same rows on both sides, agrees with
    ``chunked_gated_delta``'s within ``state_tol`` (relative RMS:
    float32 rounding, only the kernel differs); the last row's logits
    and the later layers' states, fed through bf16 casts of what came
    before (and through the flash kernel where it runs), within
    ``tol``."""
    import jax

    from paddle_tpu import pallas as pk
    from paddle_tpu.models.olmo_hybrid import FULL, LINEAR, OlmoHybridLM

    types = (LINEAR, LINEAR, LINEAR, FULL)
    cut = dict(size["prefill"])
    rows = cut.pop("rows")
    ids = np.random.RandomState(seed).randint(
        2, size["model"]["vocab"], rows).tolist()
    runs = {}
    for mode in ("auto", "off"):
        pk.enable(mode)
        jax.clear_caches()  # dispatch is decided at trace time
        m = OlmoHybridLM(seed=seed, layer_types=types,
                         **{**size["model"], **cut})
        before = _counter("pallas_dispatch_total",
                          kernel="gated_delta_chunked",
                          path=EXPECT["kernel_path"])
        pages = m.allocator.alloc(m.context_pages(ids, 0))
        t0 = time.perf_counter()
        _, _, last = m.prefill(ids, pages)
        last = np.asarray(last)
        first = time.perf_counter() - t0
        ran = _counter("pallas_dispatch_total", kernel="gated_delta_chunked",
                       path=EXPECT["kernel_path"]) - before
        assert ran == (types.count(LINEAR) if mode == "auto" else 0), (
            f"gated_delta_chunked: {ran} {EXPECT['kernel_path']} dispatches "
            f"in one traced {rows}-row bucket under pallas={mode}")
        say(f"  hybrid prefill pallas={mode}: {rows} rows, compile + run "
            f"{first:.1f}s, gated_delta_chunked {int(ran)} "
            f"{EXPECT['kernel_path']} dispatch(es)")
        entry = m.allocator.entry_of(pages)
        runs[mode] = (last, np.asarray(m.state_pool[:, entry]))
        assert np.isfinite(last).all(), "hybrid prefill: non-finite logits"
    pk.enable("auto")

    (logits, states), (logits_off, states_off) = runs["auto"], runs["off"]
    first = _rel_rms(states[0], states_off[0])
    later = _rel_rms(states[1:], states_off[1:])
    worst = _rel_rms(logits, logits_off)
    assert first <= state_tol and max(worst, later) <= tol, (
        f"hybrid prefill: kernel vs XLA chunked form: first layer's state "
        f"rel RMS {first:.2e} (tol {state_tol}), later layers' {later:.2e}, "
        f"logits {worst:.2e} (tol {tol})")
    say(f"  hybrid prefill: kernel vs pallas.enable(False) first layer's "
        f"state rel RMS {first:.2e} (tol {state_tol}), later layers' "
        f"{later:.2e}, logits {worst:.2e} (tol {tol})")


def phase_kernels(sizes, seed):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import amp, models

    amp.enable()
    rng = np.random.RandomState(seed)

    t = sizes["transformer"]

    def build_transformer():
        tokens = fluid.layers.data(name="tokens", shape=[t["S"], 1],
                                   dtype="int64")
        labels = fluid.layers.data(name="labels", shape=[t["S"], 1],
                                   dtype="int64")
        loss = models.transformer_lm_loss(
            tokens, labels=labels, vocab_size=t["V"], d_model=t["D"],
            num_heads=t["D"] // 128, num_layers=t["L"])
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
        return loss

    feed = {k: jnp.asarray(rng.randint(0, t["V"], (t["B"], t["S"], 1))
                           .astype(np.int64)) for k in ("tokens", "labels")}
    # bf16 AMP on both sides; flash attention keeps its scores in f32
    # VMEM where the XLA lowering rounds the S x S scores to bf16, so
    # the losses differ by bf16 rounding (ulp 3.9e-3), not more.
    _kernel_case("transformer_lm", "flash_attention", build_transformer,
                 feed, t["steps"], tol=2e-2)

    s = sizes["lstm"]

    def build_lstm():
        ids = fluid.layers.data(name="ids", shape=[s["T"], 1], dtype="int64")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        pred = models.lstm_text_classifier(ids, class_dim=2,
                                           emb_dim=s["emb"],
                                           hidden=s["hidden"])
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
        return loss

    feed = {"ids": jnp.asarray(rng.randint(0, 10000, (s["B"], s["T"], 1))
                               .astype(np.int64)),
            "label": jnp.asarray(rng.randint(0, 2, (s["B"], 1))
                                 .astype(np.int64))}
    _kernel_case("lstm_text", "lstm", build_lstm, feed, s["steps"], tol=2e-2)

    m = sizes["softmax"]

    def build_softmax():
        x = fluid.layers.data(name="x", shape=[m["cols"]], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=m["cols"])
        loss = fluid.layers.mean(fluid.layers.cross_entropy(
            input=fluid.layers.softmax(h), label=label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        return loss

    feed = {"x": jnp.asarray(rng.randn(m["rows"], m["cols"])
                             .astype("float32")),
            "label": jnp.asarray(rng.randint(0, m["cols"], (m["rows"], 1))
                                 .astype(np.int64))}
    # the fc is the same bf16 matmul on both sides; the softmax itself
    # is f32 in both, so only f32 reduction order differs
    _kernel_case("softmax", "softmax", build_softmax, feed, m["steps"],
                 tol=1e-3)
    amp.enable(False)
    _hybrid_step_case(sizes["hybrid"], seed)
    _hybrid_prefill_case(sizes["hybrid"], seed)


# -- phase: serve --------------------------------------------------------


def _http(address, path, payload=None, timeout=600):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://{address}{path}", data=data,
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read(), time.perf_counter() - t0


def _generate_all(address, prompts, max_new_tokens):
    """POST /generate for every prompt at once (streamed); returns the
    token streams in prompt order."""
    out = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            code, body, dt = _http(address, "/generate",
                                   {"src": prompts[i],
                                    "max_new_tokens": max_new_tokens})
            lines = [json.loads(ln) for ln in body.decode().splitlines()
                     if ln.strip()]
            assert code == 200 and lines[-1].get("done"), (code, lines[-1:])
            assert "error" not in lines[-1], lines[-1]
            streamed = [ln["token"] for ln in lines[:-1]]
            assert streamed == lines[-1]["ids"], "stream != final ids"
            out[i] = (streamed, dt)
        except Exception as e:  # re-raised in the caller's thread
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return [o[0] for o in out], sorted(o[1] for o in out)


def forced_decode_logits(prompt, forced):
    """Next-token logits after ``prompt + forced`` through the PAGED
    decode path (prefill, then teacher-forced decode steps) of a fresh
    gen-config model, under the current pallas mode."""
    model = runpy.run_path(GEN_CONFIG)["make_decode_model"]()
    pages = model.allocator.alloc(
        model.context_pages(prompt, len(forced) + 1))
    n, _, logits = model.prefill(list(prompt), pages)
    table = model.pool_table(pages)[None]
    for tok in forced:
        logits, _ = model.decode(np.asarray([[tok]], np.int32), [], table,
                                 np.asarray([n], np.int32))
        logits, n = logits[0], n + 1
    return np.asarray(logits, np.float32)


def _explain_flip(prompt, kern, ref, tol):
    """A compiled kernel's rounding may flip a greedy choice between
    near-tied logits.  The two streams share everything before the
    first differing step, so the logits AT that step must agree within
    ``tol`` — anything else is a wrong kernel, not rounding."""
    import jax

    from paddle_tpu import pallas as pk

    j = next(i for i, (a, b) in enumerate(zip(kern, ref)) if a != b)
    got = {}
    for mode in ("auto", "off"):
        pk.enable(mode)
        jax.clear_caches()
        got[mode] = forced_decode_logits(prompt, kern[:j])
    pk.enable("auto")
    diff = float(np.max(np.abs(got["auto"] - got["off"])))
    gap = float(abs(got["off"][kern[j]] - got["off"][ref[j]]))
    assert diff <= tol and gap <= 2 * tol, (
        f"/generate streams differ at step {j} and rounding does not "
        f"explain it: logits maxabs diff {diff:.2e}, gap between the two "
        f"choices {gap:.2e} (tol {tol})")
    return j, diff, gap


def phase_serve(size, seed):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import amp, cli, executor as em, pallas as pk
    from paddle_tpu.models import resnet_imagenet

    amp.enable(False)  # `paddle serve` runs the export as saved: f32
    rng = np.random.RandomState(seed)
    image, classes = size["image"], size["classes"]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    servers = []
    try:
        # a save_inference_model export of ResNet-50 (seeded random init)
        fluid.framework.reset_default_programs()
        img = fluid.layers.data(name="img", shape=list(image),
                                dtype="float32")
        pred = resnet_imagenet(img, class_dim=classes, is_test=True)
        exe, scope = fluid.Executor(fluid.TPUPlace()), em.Scope()
        with em.scope_guard(scope):
            exe.run(fluid.default_startup_program())
            fluid.io.save_inference_model(tmp, ["img"], [pred], exe)
        say(f"  ResNet-50 inference export written to {tmp}")

        max_batch = max(size["batches"])
        t0 = time.perf_counter()
        srv = cli.build_inference_server(
            {"model_dir": tmp, "port": "0", "max_batch": str(max_batch),
             "gen_config": GEN_CONFIG, "gen_slots": str(size["gen_slots"]),
             "gen_max_tokens": str(size["gen_tokens"])},
            flags=("--warmup",))
        servers.append(srv)
        say(f"  server up on {srv.address} in {time.perf_counter() - t0:.1f}s "
            f"(bucket-ladder warmup compiles included)")

        code, body, _ = _http(srv.address, "/health")
        health = json.loads(body)
        assert code == 200 and health["status"] == "ok", (code, health)
        assert health["device"]["platform"] == EXPECT["platform"], health
        say(f"  GET /health 200: device {health['device']}")

        # /predict must equal in-process exe.run on the same rows
        prog, feeds, fetches = fluid.io.load_inference_model(
            tmp, exe, scope=scope)
        for b in size["batches"]:
            rows = rng.randn(b, *image).astype("float32")
            code, body, dt = _http(srv.address, "/predict",
                                   {"img": rows.tolist()})
            assert code == 200, (code, body[:200])
            got = np.asarray(json.loads(body)["outputs"][0], np.float32)
            (want,) = exe.run(prog, feed={"img": rows}, fetch_list=fetches,
                              scope=scope)
            # a request may ride a padded power-of-two bucket while the
            # in-process run compiles its exact batch: same f32 math,
            # different conv tiling — probabilities agree to ~1e-6
            err = float(np.max(np.abs(got - want)))
            assert got.shape == want.shape == (b, classes), got.shape
            assert np.all(np.isfinite(got)) and err <= 1e-4, err
            say(f"  POST /predict batch {b}: 200 in {dt * 1e3:.0f} ms "
                f"(JSON both ways), maxabs vs in-process exe.run {err:.1e}")

        # /generate: paged decode with the Pallas kernel vs the same
        # requests with the jnp reference attention
        prompts = [rng.randint(2, 60, rng.randint(3, 9)).tolist()
                   for _ in range(size["gen_requests"])]
        before = _counter("pallas_dispatch_total",
                          kernel="ragged_paged_attention",
                          path=EXPECT["kernel_path"])
        steps0, toks0 = (_counter("decode_steps_total"),
                         _counter("decode_tokens_total"))
        kern, lat = _generate_all(srv.address, prompts, size["gen_tokens"])
        steps = _counter("decode_steps_total") - steps0
        toks = _counter("decode_tokens_total") - toks0
        assert toks > steps > 0, (
            f"{toks} tokens in {steps} decode steps: no step was shared")
        say(f"  POST /generate x{len(prompts)} concurrent: {int(toks)} tokens "
            f"in {int(steps)} decode steps, request latency median "
            f"{lat[len(lat) // 2] * 1e3:.0f} ms (first request compiles)")
        model = srv._generator.model
        from paddle_tpu.decode import model as dm

        S = size["gen_slots"]
        text = dm._decode_step.lower(
            model.params, model.k_pool, model.v_pool,
            np.zeros((S, model.pages_per_seq), np.int32),
            np.zeros((S,), np.int32), np.zeros((S,), np.int32),
            heads=model.heads, page_size=model.page_size).as_text()
        _assert_kernel("ragged_paged_attention", text, before)
        srv.stop()
        servers.remove(srv)

        pk.enable(False)
        jax.clear_caches()
        ref_srv = cli.build_inference_server(
            {"port": "0", "gen_config": GEN_CONFIG,
             "gen_slots": str(size["gen_slots"]),
             "gen_max_tokens": str(size["gen_tokens"])})
        servers.append(ref_srv)
        ref, _ = _generate_all(ref_srv.address, prompts, size["gen_tokens"])
        ref_srv.stop()
        servers.remove(ref_srv)
        pk.enable("auto")
        jax.clear_caches()
        flips = 0
        for p, a, b in zip(prompts, kern, ref):
            if a != b:
                # f32 logits of a 32-wide random-weight LM; the kernel
                # sums in f32 on the VPU where the reference einsum may
                # use the MXU's reduced-precision passes
                j, diff, gap = _explain_flip(p, a, b, tol=5e-3)
                flips += 1
                say(f"  /generate prompt {p}: greedy flip at step {j} "
                    f"between near-tied logits (gap {gap:.1e}; kernel vs "
                    f"reference logits maxabs {diff:.1e}, tol 5e-3)")
        say(f"  /generate: {len(prompts) - flips}/{len(prompts)} token "
            "streams identical to the reference-attention run"
            + ("" if not flips else f", {flips} explained by rounding"))
    finally:
        for s in servers:
            s.stop()
        pk.enable("auto")
        shutil.rmtree(tmp, ignore_errors=True)


# -- phase: four chips ----------------------------------------------------


def _spread(name, exe, scope, feed, fed_name, devices):
    """Show the work is spread: the fed batch and the largest state
    array have addressable shards on every device, all devices hold
    live memory, and the compiled step contains collectives."""
    import re

    comp, lowered = _last_step(exe, scope, feed)
    text = lowered.compile().as_text()
    colls = {}
    for op in re.findall(r"\b(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)(?:-start)?\(", text):
        colls[op] = colls.get(op, 0) + 1
    assert colls, f"{name}: no collective in the compiled step"
    def split(n):  # is this state array sharded (not just replicated)?
        a = scope.values[n]
        return a.addressable_shards[0].data.shape != a.shape

    big = max(comp.state_names, key=lambda n: (split(n), scope.values[n].size))
    for what, arr in ((f"feed {fed_name!r}", feed[fed_name]),
                      (f"state {big!r}", scope.values[big])):
        on = {s.device for s in arr.addressable_shards}
        assert on == set(devices), f"{name}: {what} lives on {on}"
        say(f"  {name}: {what} {tuple(arr.shape)} -> shards of "
            f"{tuple(arr.addressable_shards[0].data.shape)} on "
            f"{len(on)} distinct devices")
    say(f"  {name}: collectives in the compiled step {colls}")
    if EXPECT["memory_stats"]:  # the CPU backend reports none
        live = [d.memory_stats()["bytes_in_use"] for d in devices]
        assert all(b > 0 for b in live), f"{name}: bytes_in_use {live}"
        say(f"  {name}: bytes_in_use per device "
            f"{[f'{b / 2**30:.2f} GiB' for b in live]}")
    if EXPECT["marker"]:
        say(f"  {name}: {EXPECT['marker']} x{text.count(EXPECT['marker'])} "
            "in the compiled step")


def phase_multichip(sizes, seed, devices=None):
    import jax
    from jax.sharding import NamedSharding

    import __graft_entry__ as graft
    import paddle_tpu as fluid
    from paddle_tpu import amp, executor as em, models
    from paddle_tpu.parallel import (DataParallelStrategy,
                                     HybridParallelStrategy, make_mesh)

    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    rng = np.random.RandomState(seed)
    amp.enable()
    # __graft_entry__._report's gate.  The transformer keeps _report's
    # own 2e-3.  Its 2e-4 for conv models was set for f32 programs on
    # the CPU mesh, where only psum order differs; ResNet-50 BS=256
    # fits ONE 16 GB chip only under bf16 AMP, and there a different
    # reduction order across devices flips BatchNorm'd activations by a
    # bf16 ulp (3.9e-3 relative), so the first loss agrees to ~3e-3 (CPU
    # mesh, toy size), not 1e-6.  A wrong sharding rule (a batch
    # statistic taken per shard, a dropped all-reduce) moves the loss by
    # far more than 1e-2.
    AMP_TOL = 1e-2
    UPDATE_TOL = 0.08  # a weight gradient sums bf16-rounded terms (seen: 2e-2)

    def compare(name, main, startup, loss, host_feed, strategies, steps,
                fed_name, tol, watch, every_step):
        """Run the program under each strategy from the same seeded
        init.  The first loss (taken before any update) must agree
        within ``tol`` — graft._report's gate.  Then either every later
        loss must too (``every_step``: Adam at 1e-4 under LayerNorm is
        smooth), or — for ResNet-50, whose first Momentum steps from a
        random init amplify a 1e-6 difference a thousandfold (seen in
        f32 on the CPU mesh) — the first UPDATE of parameter ``watch``
        must agree: that is the gradient, all-reduce included, without
        the chaotic loss surface in between."""
        runs = {}
        block = main.global_block()
        for tag, strat in strategies:
            exe = fluid.Executor(fluid.TPUPlace(), strategy=strat)
            scope = em.Scope()
            t0 = time.perf_counter()
            # the batch is fed already laid out as the strategy shards it
            feed = {k: jax.device_put(v, NamedSharding(
                strat.mesh, strat.feed_spec(k, block.find_var(k))))
                for k, v in host_feed.items()}
            losses, first, later, update = _run_steps(
                exe, scope, main, startup, feed, loss, steps, watch=watch)
            say(f"  {name} [{tag}]: compile+first step {first:.1f}s, later "
                f"steps median {later * 1e3:.1f} ms, losses "
                f"{[round(l, 5) for l in losses]}")
            assert all(np.isfinite(losses)), f"{name}: non-finite {losses}"
            if tag != "one device":
                _spread(name, exe, scope, feed, fed_name, devices)
            runs[tag] = (losses, update, time.perf_counter() - t0)
            del exe, scope
        single, upd1, _ = runs["one device"]
        meshed, updn, dt = runs[strategies[1][0]]
        for i in range(steps if every_step else 1):
            graft._report(f"{name} step {i}", meshed[i], single[i], dt,
                          tol=tol)
        if not every_step:
            err = float(np.linalg.norm(updn - upd1) / np.linalg.norm(upd1))
            assert np.linalg.norm(upd1) > 0 and err <= UPDATE_TOL, (
                f"{name}: first update of {watch!r} differs from the "
                f"one-device run by rel L2 {err:.2e} > {UPDATE_TOL}")
            say(f"  {name}: first update of {watch!r} vs one device: rel L2 "
                f"{err:.2e} (tol {UPDATE_TOL}; a summed-not-averaged "
                f"gradient would show {n - 1}.0)")

    # (a) ResNet-50 data-parallel, global batch 256
    r = sizes["resnet"]
    built = _resnet50(r["batch"], r["image"], r["classes"])
    feed = {"img": rng.randn(r["batch"], *r["image"]).astype("float32"),
            "label": rng.randint(0, r["classes"],
                                 (r["batch"], 1)).astype("int64")}
    compare(f"resnet50 dp={n}", built["main"], built["startup"],
            built["loss"], feed,
            [("one device", DataParallelStrategy(
                make_mesh({"dp": 1}, devices=devices[:1]), axis="dp")),
             (f"dp={n}", DataParallelStrategy(
                 make_mesh({"dp": n}, devices=devices), axis="dp"))],
            r["steps"], "img", AMP_TOL, watch="fc_0.w_0", every_step=False)

    # (b) transformer tp x sp with ring attention at real widths
    t = sizes["transformer"]
    tp, sp = 2, n // 2
    fluid.framework.reset_default_programs()
    tokens = fluid.layers.data(name="tokens", shape=[t["S"], 1],
                               dtype="int64")
    labels = fluid.layers.data(name="labels", shape=[t["S"], 1],
                               dtype="int64")
    loss = models.transformer_lm_loss(
        tokens, labels=labels, vocab_size=t["V"], d_model=t["D"],
        num_heads=t["D"] // 128, num_layers=t["L"], tp_axis="tp")
    fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
    feed = {k: rng.randint(0, t["V"], (t["B"], t["S"], 1)).astype("int64")
            for k in ("tokens", "labels")}

    def hybrid(axes, devs):
        return HybridParallelStrategy(
            make_mesh(axes, devices=devs), dp_axis="dp", tp_axis="tp",
            sp_axis="sp", shard_all_seq=True)

    before = _counter("pallas_dispatch_total", kernel="ring_flash_attention",
                      path=EXPECT["kernel_path"])
    compare(f"transformer tp={tp} sp={sp} (ring attention)",
            fluid.default_main_program(), fluid.default_startup_program(),
            loss, feed,
            [("one device", hybrid({"dp": 1, "tp": 1, "sp": 1}, devices[:1])),
             (f"tp={tp} sp={sp}", hybrid({"dp": 1, "tp": tp, "sp": sp},
                                         devices))],
            t["steps"], "tokens", 2e-3, watch=None, every_step=True)
    ran = _counter("pallas_dispatch_total", kernel="ring_flash_attention",
                   path=EXPECT["kernel_path"]) - before
    assert ran > 0, ("the ring's per-shard blocks took the jnp fallback, "
                     "not the flash kernel")
    say(f"  ring attention: {int(ran)} {EXPECT['kernel_path']} flash-kernel "
        "dispatch decision(s) for the per-shard blocks")
    amp.enable(False)


# -- main ------------------------------------------------------------------


def _cache_events():
    """Count jax's persistent-compilation-cache hits and misses."""
    import jax

    seen = {"hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


@contextlib.contextmanager
def _phase(name):
    say(f"phase {name} ...")
    t0 = time.perf_counter()
    yield
    say(f"phase {name} OK in {time.perf_counter() - t0:.1f}s")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the multi-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from paddle_tpu import compile_cache

    cache_dir = compile_cache.configure()  # before first backend use
    device = require_device(args.chips)
    import jax

    from paddle_tpu import pallas as pk

    say(f"device {device}; jax {jax.__version__}")
    say(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    assert not pk.interpret_mode(), "interpreted kernels on a chip run"
    cache = _cache_events()

    t0 = time.perf_counter()
    if args.chips == 4:
        with _phase("multichip"):
            phase_multichip(SIZES["multichip"], args.seed)
    else:
        with _phase("train"):
            phase_train(SIZES["train"], args.seed)
        with _phase("kernels"):
            phase_kernels(SIZES, args.seed)
        with _phase("serve"):
            phase_serve(SIZES["serve"], args.seed)

    from paddle_tpu.observability import metrics

    snap = metrics.snapshot()
    for fam in ("pallas_dispatch_total",
                "executor_aot_export_skipped_total",
                "executor_donation_analysis_failed_total"):
        vals = {",".join(f"{k}={w}" for k, w in sorted(v["labels"].items())):
                int(v["value"]) for v in snap.get(fam, {"values": []})["values"]}
        say(f"counter {fam}: {vals or 0}")
    say(f"persistent compile cache: {cache['hits']} hits, "
        f"{cache['misses']} misses this run")
    say(f"all phases OK in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
