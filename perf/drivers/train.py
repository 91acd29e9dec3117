"""Driver ``train``: one ``fluid.Executor.run`` per step, as a user's
loop (``trainer/``, ``v2/``) dispatches it.

The program comes from ``perf/programs/<config.program>.py``, the
sizes from the configuration's file, the batch, the ring of batches,
the log period and the data-parallel width from the traffic file.  The
loss is fetched as a device array every step and read on the host every
``log_period``-th, as ``trainer/`` does.  The window is closed by
``block_until_ready`` on the last step's loss.
"""

import importlib
import shutil
import time

import numpy as np

from perf.harness import runtime, stats, trace as tr
from perf.harness.flops import assert_model_flops


def make_ring(seed, feeds, ring, shardings):
    """``ring`` distinct feeds, made on the device from the seed in ONE
    jitted call, each array laid out as the strategy shards it."""
    import jax
    import jax.numpy as jnp

    names = sorted(feeds)

    def draw(key):
        out = []
        for r in range(ring):
            row = {}
            for i, n in enumerate(names):
                k = jax.random.fold_in(jax.random.fold_in(key, r), i)
                f = feeds[n]
                if f["draw"] == "normal":
                    row[n] = jax.random.normal(k, tuple(f["shape"]),
                                               jnp.float32)
                else:
                    row[n] = jax.random.randint(k, tuple(f["shape"]), 0,
                                                f["high"], jnp.int32)
            out.append(row)
        return out

    out_sh = None
    if shardings is not None:
        out_sh = [{n: shardings[n] for n in names} for _ in range(ring)]
    return jax.jit(draw, out_shardings=out_sh)(
        jax.random.key(seed % (2 ** 31 - 1)))


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-8)


def _host_state(scope):
    return {n: np.asarray(v) for n, v in scope.values.items()}


def _scope_from(state):
    from paddle_tpu import executor as em

    sc = em.Scope()
    for n, v in state.items():
        sc.set(n, v)
    return sc


def verify_program_flops(env):
    """The program's forward work per item lies within the copied
    ``assert_model_flops`` tolerance of the published cost, and equals
    the count written into the cell's file when it was proved."""
    cfg, built, tol = env["config"], env["built"], env["workload"]["verify"]
    got = env["forward_flops"] / built["batch"] / 1e9
    if env["rehearse"]:     # toy sizes have no published cost
        return {"fwd_gflop_per_item": got}
    assert_model_flops(got, cfg["published_fwd_gflop_per_item"],
                       cfg["flops_rtol"], cfg["name"])
    want = tol.get("proved_fwd_gflop_per_item")
    if want is not None:
        assert abs(got - want) <= 1e-9 * want, (
            f"forward GFLOP per item {got!r} is not the proved {want!r}")
    return {"fwd_gflop_per_item": got}


def verify_cpu_place_small_batch(env):
    """One step at a small batch on TPUPlace under AMP against CPUPlace
    in float32, both from the run's startup state."""
    import paddle_tpu as fluid
    from paddle_tpu import amp

    built, tol = env["built"], env["workload"]["verify"]
    sb = int(tol["small_batch"])
    small = {k: np.asarray(v[:sb]) for k, v in env["ring"][0].items()}
    got = {}
    for tag, place, use_amp in (("tpu", fluid.TPUPlace(), env["amp"]),
                                ("cpu", fluid.CPUPlace(), False)):
        amp.enable(use_amp)
        (l,) = fluid.Executor(place).run(
            built["main"], feed=small, fetch_list=[built["loss"]],
            scope=_scope_from(env["state0"]))
        got[tag] = float(l)
    amp.enable(env["amp"])
    rel = _rel(got["tpu"], got["cpu"])
    assert np.isfinite(got["tpu"]) and rel <= tol["cpu_place_rel"], (
        f"TPUPlace {got['tpu']} vs CPUPlace {got['cpu']}: rel {rel:.2e} "
        f"> {tol['cpu_place_rel']}")
    return {"small_batch_loss_tpu": got["tpu"],
            "small_batch_loss_cpu": got["cpu"], "cpu_place_rel": rel}


def verify_one_device_mesh(env):
    """The first loss and the first update of the watched parameter
    under the cell's strategy against the same program on a one-device
    mesh, both from the run's startup state, at ``small_global_batch``
    rows of the ring's first batch (``chip_smoke --chips 4``'s
    comparison and bounds; one chip cannot hold the cell's whole batch).
    The measured scope is not touched."""
    import jax
    from jax.sharding import NamedSharding

    import paddle_tpu as fluid
    from paddle_tpu.parallel import DataParallelStrategy, make_mesh

    built, tol = env["built"], env["workload"]["verify"]
    watch, block = built["watch"], built["main"].global_block()
    n = int(tol["small_global_batch"])
    host_feed = {k: np.asarray(v[:n]) for k, v in env["ring"][0].items()}
    one = DataParallelStrategy(
        make_mesh({"dp": 1}, devices=env["devices"][:1]), axis="dp")
    out = {}
    for tag, strat in (("one", one), ("dp", env["strategy"])):
        exe = fluid.Executor(fluid.TPUPlace(), strategy=strat)
        scope = _scope_from(env["state0"])
        feed = {k: jax.device_put(v, NamedSharding(
            strat.mesh, strat.feed_spec(k, block.find_var(k))))
            for k, v in host_feed.items()}
        before = np.asarray(scope.values[watch], np.float32)
        (l,) = exe.run(built["main"], feed=feed, fetch_list=[built["loss"]],
                       scope=scope)
        out[tag] = (float(l),
                    np.asarray(scope.values[watch], np.float32) - before)
    rel = _rel(out["dp"][0], out["one"][0])
    upd = float(np.linalg.norm(out["dp"][1] - out["one"][1])
                / np.linalg.norm(out["one"][1]))
    assert rel <= tol["first_loss_rel"], (
        f"first loss {out['dp'][0]} vs one device {out['one'][0]}: "
        f"rel {rel:.2e} > {tol['first_loss_rel']}")
    assert upd <= tol["first_update_rel_l2"], (
        f"first update of {watch!r} differs from the one-device run by "
        f"rel L2 {upd:.2e} > {tol['first_update_rel_l2']}")
    return {"first_loss_rel": rel, "first_update_rel_l2": upd}


def _compiled_step(exe, scope, feed):
    """The executor's newest compiled step, lowered again for its
    memory plan and text (``chip_smoke._last_step``'s reach-in)."""
    comp = list(exe._cache.values())[-1]
    state = {n: scope.values[n] for n in comp.state_names}
    rest = (np.int64(0),) if comp.uses_rng else ()
    return comp.fn.lower(state, feed, *rest).compile()


def run(ctx):
    import jax
    from jax.sharding import NamedSharding

    import paddle_tpu as fluid
    from paddle_tpu import amp, executor as em
    from paddle_tpu.parallel import DataParallelStrategy, make_mesh

    cfg, traffic, wl = ctx["config"], ctx["traffic"], ctx["workload"]
    say, spans = runtime.say, runtime.Spans(ctx["trace"])
    devices = jax.devices()[:wl["chips"]]
    use_amp = cfg.get("amp") == "bf16"
    amp.enable(use_amp)

    mod = importlib.import_module(f"perf.programs.{cfg['program']}")
    built = mod.build(cfg, traffic)
    main, startup, loss = built["main"], built["startup"], built["loss"]
    # the startup program draws the weights from its seed: a run-time
    # argument of the compiled program, so every seed shares one compile
    startup.seed = ctx["seed"] % 2147

    dp = int(traffic["dp"])
    strategy, shardings = None, None
    if dp > 1:
        strategy = DataParallelStrategy(
            make_mesh({"dp": dp}, devices=devices), axis="dp")
        block = main.global_block()
        shardings = {n: NamedSharding(
            strategy.mesh, strategy.feed_spec(n, block.find_var(n)))
            for n in built["feeds"]}
    exe = fluid.Executor(fluid.TPUPlace(), strategy=strategy)
    scope = em.Scope()
    exe.run(startup, scope=scope)
    ring = make_ring(ctx["seed"], built["feeds"], int(traffic["ring"]),
                     shardings)

    env = {"config": cfg, "traffic": traffic, "workload": wl,
           "built": built, "exe": exe, "scope": scope, "ring": ring,
           "amp": use_amp, "devices": devices, "strategy": strategy,
           "rehearse": ctx["rehearse"],
           "forward_flops": mod.forward_flops_per_step(cfg, traffic, built)}
    kinds = wl["verify"]["kinds"]
    if any(k in ("cpu_place_small_batch", "one_device_mesh") for k in kinds):
        env["state0"] = _host_state(scope)
    facts, correct = {}, True
    for kind in kinds:
        check = globals().get(f"verify_{kind}") or getattr(
            mod, f"verify_{kind}")
        t0 = time.perf_counter()
        try:
            facts.update(check(env))
        except AssertionError as e:
            say(f"NOT CORRECT ({kind}): {e}")
            correct = False
        say(f"verify {kind}: {time.perf_counter() - t0:.1f}s")
    env.pop("state0", None)

    log_period = int(traffic["log_period"])
    reads, read_at = [], []

    def loop(seconds):
        """Steps until ``seconds`` have passed, then the block that
        closes the window.  Returns (steps, open, close)."""
        steps, last = 0, None
        t_open = time.perf_counter()
        while True:
            with spans.span("perf.exe_run"):
                (last,) = exe.run(main, feed=ring[steps % len(ring)],
                                  fetch_list=[loss], scope=scope,
                                  return_numpy=False)
            steps += 1
            if steps % log_period == 0:
                with spans.span("perf.loss_read"):
                    reads.append(float(np.asarray(last)))
                read_at.append(time.perf_counter())
            if time.perf_counter() - t_open >= seconds:
                break
        with spans.span("perf.block_until_ready"):
            jax.block_until_ready(last)
        t_close = time.perf_counter()
        reads.append(float(np.asarray(last)))
        return steps, t_open, t_close

    # warm-up: the step compiles (or is read from the cache) and runs
    # until the device queue is in its steady state
    t0 = time.perf_counter()
    loop(0.0)
    say(f"first step (compile or cache read): "
        f"{time.perf_counter() - t0:.1f}s")
    loop(float(traffic["warmup_seconds"]))
    t0 = time.perf_counter()
    compiled = _compiled_step(exe, scope, ring[0])
    planned = runtime.planned_bytes(compiled)
    say(f"planned bytes of the step on one device: {planned} (lowered "
        f"again in {time.perf_counter() - t0:.1f}s); the runtime's "
        f"peak_bytes_in_use: {runtime.memory_peak_bytes(devices)}")
    compiled_text = {"step": compiled.as_text()} if ctx["trace"] else {}
    del compiled
    spans.seconds.clear()
    del reads[:], read_at[:]

    seconds = (min(ctx["seconds"], float(traffic["trace_seconds"]))
               if ctx["trace"] else ctx["seconds"])
    events0 = ctx["compile_events"].snapshot()
    ctx["mark_setup_done"]()
    with runtime.profiler_trace(ctx["trace"]) as trace_dir:
        with spans.span(tr.WINDOW_SPAN):
            steps, t_open, t_close = loop(seconds)
    events1 = ctx["compile_events"].snapshot()
    compiles = events1["requests"] - events0["requests"]
    if compiles:
        say(f"NOT CORRECT: {compiles} compile request(s) inside the window")
        correct = False
    finite = all(np.isfinite(reads))
    if not finite:
        say(f"NOT CORRECT: non-finite loss among {reads}")
        correct = False
    window_s = t_close - t_open
    rate = stats.rate(steps * built["work_per_step"], t_open, t_close)
    say(f"{steps} steps in {window_s:.3f}s: {rate:.1f} "
        f"{built['work_unit']}/s; losses read {reads[:3]}..{reads[-1:]}")
    # a run that reads low: one long period is a stall, all of them
    # longer is a slower device (a label for the log, no metric)
    periods = np.diff([t_open] + read_at)
    if len(periods):
        say(f"{log_period} steps from loss read to loss read: median "
            f"{np.median(periods):.4f}s, longest {periods.max():.4f}s "
            f"(period {int(periods.argmax()) + 1} of {len(periods)})")

    record = {
        "correct": correct, "attempted": steps,
        "failed": 0 if finite else steps,
        "end_to_end": {wl["rate_metric"]: rate},
        "steps": steps, "window_s": window_s,
        "work_per_step": built["work_per_step"],
        "train_flops_per_step": 3.0 * env["forward_flops"],
        "span_seconds": spans.seconds, "facts": facts,
        "planned_bytes": planned, "devices": devices, "trace": None,
        "compiled_text": compiled_text,
    }
    if trace_dir:
        record["trace"] = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return record
