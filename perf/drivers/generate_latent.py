"""Driver ``generate_latent``: ``generate_paged`` for a model over the
paged skeleton whose pages hold latent rows
(``paddle_tpu/models/kanana_mla.py``: one row a token a layer, read
absorbed by a decode step and expanded by a prefill), with a held range
of experts.

What differs from ``generate_paged``, and why it could not be told to
that driver by data: the reference takes the latent geometry (nope and
rope head sizes) and the held range; ``correct`` holds the median row
beside all rows, as ``generate_window``'s does and for its reason (a
row whose top-k set the bf16 rounding flipped carries most of a
comparison's distance), holds each ablation and each precision variant
to one of the two limits, and checks the suffix prefill over cached
rows on the ablation prompt (the first is shorter than the cached
part); the bytes a decode step's kernel reads are latent rows', not K
and V heads'; the plan is the larger of the step's and the top bucket's;
and the window samples the ``decode_cache_rows`` gauge at kind
``latent``.  The load, the window, the record's keys and so the readers
are ``generate_paged``'s; ``through_the_cache``, the ladder and the
warm-up are that file's own.
"""

import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from perf.drivers.generate import (_generate, client_metrics,
                                   client_report, instrument)
from perf.drivers.generate_paged import (_count, buckets_of,
                                         through_the_cache, warm)
from perf.harness import loadgen, modules, runtime
from perf.harness import trace as tr

SAMPLE_EVERY_S = 0.25


def routed_sets(model, tokens):
    """(routed layers, T, E) bool: the experts the SYSTEM chooses for
    each row of one sequence, by its own block functions over the dense
    forward (a probe from the benchmark's side; the program hands out
    counts, not sets)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import moe
    from paddle_tpu.models.olmoe import rms_norm

    block = model.block

    @jax.jit
    def run(params, toks):
        T = toks.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        x = block.embed(params, toks, pos)
        sets = []
        for lp in params["layers"]:
            x, _ = block.prompt_mixer(lp, x, pos, model.heads, None)
            if "wr" in lp:
                m = rms_norm(x, lp["w_post"], block.eps).astype(
                    lp["wr"].dtype)
                _, idx = moe.route(m, lp["wr"], block.top_k,
                                   moe.sigmoid_scores(lp["b"], block.scale))
                E = lp["wr"].shape[1]
                sets.append(jnp.any(idx[..., None] == jnp.arange(E),
                                    axis=1))
            x, _ = block.mlp(lp, x, None)
        return jnp.stack(sets)

    return np.asarray(run(model.params, jnp.asarray(tokens, jnp.int32)))


def verify(model, address, wl, traffic, seed, say):
    """(a) prefill through the bucket's program (expanded), then 16
    teacher-forced decode steps through the latent pages at the serving
    step's shape (absorbed): all 17 logits rows of each seeded prompt
    against the reference's full forward over prompt + tokens, by
    relative RMS over all rows and by the median row's; (b) on prompt
    ``ablation_prompt``: the same rows again with the suffix prefilled
    over ``cached_len`` cached rows, each ablation of the reference at
    its stated multiple of one of the two limits, and the reference in
    each precision below over one of them; (c) greedy streams through
    /generate end with their count of tokens."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    median_limit = float(tol["logits_rel_rms_median_row"])
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32), num_heads=model.heads,
            nope=block.nope, rope_dim=block.rope_dim, top_k=block.top_k,
            scale=block.scale, held=block.held, eps=block.eps,
            theta=block.theta, ablate=ablate, rows=rows)

    def both(got, want):
        rows = [ref.rel_rms(g, w) for g, w in zip(got, want)]
        return ref.rel_rms(got, want), float(np.median(rows)), max(rows)

    worst = worst_median = 0.0

    def held_to_the_limits(name, got, want):
        nonlocal worst, worst_median
        rms, median, worst_row = both(got, want)
        facts[name] = rms
        facts[name + "_median_row"] = median
        facts[name + "_worst_row"] = worst_row
        worst, worst_median = max(worst, rms), max(worst_median, median)

    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got = through_the_cache(model, prompt, tokens, slots)
        rows = list(range(T - 1, T + n))
        want, masks = reference(prompt + tokens, rows)
        held_to_the_limits(f"logits_rel_rms_T{T}_{i}", got, want)
        if i != int(tol["ablation_prompt"]):
            continue
        differ = np.any(routed_sets(model, prompt + tokens)
                        != np.asarray(masks), axis=-1)      # (layers, T)
        facts["top_k_set_differs_share"] = float(differ.mean())
        c = int(tol["cached_len"])
        held_to_the_limits(
            f"suffix_prefill_rel_rms_cached{c}",
            through_the_cache(model, prompt, tokens, slots, cached_len=c),
            want)
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol["ablations"]]
        # over a limit at all: the reference in a precision below the
        # configuration's must come out as not correct
        variants += [(p, f"reference_in_{p}", 1.0)
                     for p in tol["precisions_below"]]
        for ablate, name, factor in variants:
            wrong, _ = reference(prompt + tokens, rows, ablate)
            rms, median, _ = both(got, wrong)
            facts[f"logits_rel_rms_{name}"] = rms
            facts[f"logits_rel_rms_{name}_median_row"] = median
            if rms <= factor * limit and median <= factor * median_limit:
                problems.append(
                    f"neither limit would catch {name} by {factor}x: "
                    f"{rms:.3e} of {limit}, median row {median:.3e} of "
                    f"{median_limit}")
    facts["logits_rel_rms_worst"] = worst
    facts["logits_rel_rms_median_row_worst"] = worst_median
    if not worst <= limit:
        problems.append(f"logits relative RMS {worst:.3e} > {limit}")
    if not worst_median <= median_limit:
        problems.append(f"logits relative RMS of the median row "
                        f"{worst_median:.3e} > {median_limit}")
    for _ in range(int(tol["streams"])):
        p = rng.randint(2, model.vocab, int(tol["stream_prompt_len"])).tolist()
        ids = _generate(address, p, n)
        if len(ids) != n:
            problems.append(f"/generate gave {len(ids)} tokens of {n}")
    say(f"reference check: {facts}")
    for problem in problems:
        say(f"NOT CORRECT: {problem}")
    return not problems, facts


def compiled_texts(model, slots, ladder):
    """The model's own decode step and one prefill program a bucket,
    as compiled text, and the planned bytes of the larger of the step
    and the top bucket's prefill."""
    from paddle_tpu.decode import model as dm

    step = dm._decode_step.lower(
        model.params, model.k_pool, model.v_pool,
        np.zeros((slots, model.pages_per_seq), np.int32),
        np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
        heads=model.heads, page_size=model.page_size,
        block=model.block).compile()
    texts = {"decode_step": step.as_text()}
    planned = runtime.planned_bytes(step)
    for b in ladder:
        prefill = dm._prefill_bucket.lower(
            model.params, model.k_pool, model.v_pool,
            np.zeros((b,), np.int32), np.zeros((b,), np.int32), np.int32(1),
            heads=model.heads, block=model.block).compile()
        texts[f"prefill_bucket_{b}"] = prefill.as_text()
        planned = max(planned, runtime.planned_bytes(prefill))
    return texts, planned


def sampled_window(seconds):
    """Sleep through the window, reading the ``decode_cache_rows``
    gauge at kind ``latent`` every ``SAMPLE_EVERY_S``: [(latent rows,
    0)], the samples with a sequence seated, in the shape
    ``cache_bytes_per_live_row`` reads (rows that keep every row, rows
    on rings).  None from a program that has no such gauge."""
    from paddle_tpu.observability import metrics

    gauge = metrics.REGISTRY.get("decode_cache_rows")
    samples, t_end = [], time.perf_counter() + seconds
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        time.sleep(min(SAMPLE_EVERY_S, left))
        if gauge is not None:
            rows = gauge.value(kind="latent")
            if rows > 0:
                samples.append((rows, 0))
    return samples if gauge is not None else None


def run(ctx):
    import jax

    from paddle_tpu import cli
    from paddle_tpu.observability import metrics

    cfg, traffic, wl = ctx["config"], ctx["traffic"], ctx["workload"]
    loadgen.check_deal(traffic)
    say, spans = runtime.say, runtime.Spans(ctx["trace"])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gen_config = os.path.join(here, "configs", cfg["generate"]["gen_config"])
    os.environ["PERF_GEN_SEED"] = str(ctx["seed"] % (2 ** 31 - 1))
    os.environ["PERF_GEN_REHEARSE"] = "1" if ctx["rehearse"] else "0"
    t0 = time.perf_counter()
    srv = cli.build_inference_server(
        {"port": "0", "gen_config": gen_config,
         "gen_slots": str(traffic["gen_slots"]),
         "gen_queue": str(traffic["gen_queue"]),
         "gen_max_tokens": str(max(b for b, _ in traffic["max_tokens"]))})
    child = None
    try:
        engine = srv._generator
        model = engine.model
        jax.block_until_ready(model.params)
        say(f"server up on {srv.address} in {time.perf_counter() - t0:.1f}s; "
            f"pool {model.allocator.num_pages} pages x {model.page_size} "
            f"rows x {model.block.width} lanes, {model.pages_per_seq} pages "
            f"a sequence, {model.k_pool.dtype} latent rows")
        ladder = warm(model, say)
        say("peak bytes in use after warming: "
            f"{runtime.memory_peak_bytes(jax.devices())}")
        t0 = time.perf_counter()
        correct, facts = verify(model, srv.address, wl, traffic,
                                ctx["seed"], say)
        say(f"verify: {time.perf_counter() - t0:.1f}s, correct={correct}; "
            f"peak bytes in use {runtime.memory_peak_bytes(jax.devices())}")
        compiled_text, planned = {}, 0
        if ctx["trace"]:
            instrument(engine, spans)
            compiled_text, planned = compiled_texts(
                model, int(traffic["gen_slots"]), ladder)

        seconds = (min(ctx["seconds"], float(traffic["trace_seconds"]))
                   if ctx["trace"] else ctx["seconds"])
        spec = loadgen.spec_of(traffic, srv.address, seconds, ctx["seed"],
                               model.vocab)
        ramp = spec["ramp_seconds"]
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump(spec, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(here, "harness", "loadgen.py"),
             f.name], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True)
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator said {ready!r}")
        child.stdin.write("GO\n")
        child.stdin.flush()
        time.sleep(ramp)       # the closed loop settles; not measured
        spans.seconds.clear()
        events0 = ctx["compile_events"].snapshot()
        before = metrics.snapshot()
        ctx["mark_setup_done"]()
        with runtime.profiler_trace(ctx["trace"]) as trace_dir:
            with spans.span(tr.WINDOW_SPAN):
                cache_rows = sampled_window(seconds)
                after = metrics.snapshot()
        out = json.loads(child.stdout.readline())
        child.wait(timeout=180)
        os.unlink(f.name)
        events1 = ctx["compile_events"].snapshot()
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        srv.stop()

    cm = client_metrics(out)
    compiles = events1["requests"] - events0["requests"]
    if compiles:
        say(f"NOT CORRECT: {compiles} compile request(s) inside the window "
            "or the drain after it")
        correct = False
    if cm["failed"]:
        say(f"{cm['failed']} of {cm['attempted']} requests failed: "
            f"{cm['failures']}")
    e2e = client_report(cm, out, say)
    facts["requests_in_window"] = cm["attempted"]
    facts["cache_row_samples"] = len(cache_rows or ())
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        # one layer's count of the live rows the window's decode steps
        # read; the readers multiply by the layers
        "latent_rows": cm["kv_rows"],
        # as STORED (cache_bytes_per_live_row: what is resident)
        "kv_row_bytes": model.row_bytes, "full_layers": model.layers,
        "cache_rows": cache_rows,
        "span_seconds": spans.seconds, "facts": facts,
        "planned_bytes": planned, "devices": jax.devices()[:wl["chips"]],
        "trace": None, "compiled_text": compiled_text,
    }
    if trace_dir:
        record["trace"] = tr.load(trace_dir)
        record["trace_modules"] = modules.load(trace_dir)
        say("module runs in the trace: " + json.dumps(
            {p: _count(ms) for p, ms in record["trace_modules"].items()}))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return record
