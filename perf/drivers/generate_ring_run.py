"""Driver ``generate_ring_run``: a model over the paged skeleton whose
full layers keep a page run and whose window layers keep a ring entry a
sequence, K and V of different widths, a held range of sigmoid-routed
experts and no shared one, and whose long prompts run in chunks over
the rings (``paddle_tpu/models/mimo_v2.py``).

What differs from ``generate_window`` and ``generate_conv_hybrid``, and
why it could not be told to either by data: ``generate_window`` knows a
model whose rings are pages of the one pool (``model.rings``,
``ring_pages``, a prefill address a LAYER) and has no chunk programs to
warm or to keep the texts of; ``generate_conv_hybrid`` holds a conv
tail and a reference given the system's router sets.  This one warms
the ladder and every chunk program the traffic's and the check's
prompts run (``generate_conv_hybrid.warm``; that driver's
``compiled_texts`` and its probe of the router's sets serve as they are:
the block has ``router_rows``, ``scores`` and ``experts`` as LFM2's
has), hands the reference this
model's geometry (two K/V head counts, two head widths, the rotated
channels, two thetas, the value scale), and holds ``correct`` as
``generate_window`` does: four seeded prompts (under one window; over
one, the ablations'; one bucket; the top bucket and three chunks over
the rings) prefilled through the timed programs, 16 seeded tokens teacher-forced through both caches at
the serving step's shape, all 17 logits rows against the reference's
full forward by TWO limits (the median row's relative RMS for the
bfloat16 rounding, all rows' for the router's flips), each ablation by
its stated factor on one of them, the reference in float8 over one.
The load, the window and the record's keys are ``generate_window``'s;
``kv_bytes`` are the full layers' PUBLISHED bytes
(``perf/harness/mimo.py``); ``compiled_text`` also holds the chunk
programs (``prefill_state_chunk_<rows>_over_<done>``).
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
from perf.drivers.generate_conv_hybrid import (compiled_texts, routed_sets,
                                               warm)
from perf.drivers.generate_paged import _count, through_the_cache
from perf.drivers.generate_window import sampled_window
from perf.harness import loadgen, mimo, modules, runtime
from perf.harness import trace as tr


def verify(model, address, wl, traffic, seed, say):
    """The module's docstring says what is held; every reading is
    written down in the facts."""
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
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            kv_heads=block.kv_heads, window_kv_heads=block.window_kv_heads,
            head_dim=block.head_dim, value_dim=block.value_dim,
            rotary=block.rotary, window=block.window, top_k=block.top_k,
            scale=block.scale, held=block.held, eps=block.eps,
            theta=block.theta, window_theta=block.window_theta,
            value_scale=block.value_scale, ablate=ablate, rows=rows)

    def both(got, want):
        """(relative RMS over all rows, the median row's, the worst
        row's): the second is blind to the few rows whose top-k set the
        bf16 rounding flipped, which carry most of the first."""
        rows = [ref.rel_rms(g, w) for g, w in zip(got, want)]
        return ref.rel_rms(got, want), float(np.median(rows)), max(rows)

    worst = worst_median = 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        t0 = time.perf_counter()
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got = through_the_cache(model, prompt, tokens, slots)
        rows = list(range(T - 1, T + n))
        want, masks = reference(prompt + tokens, rows)
        rms, median, worst_row = both(got, want)
        facts[f"logits_rel_rms_T{T}_{i}"] = rms
        facts[f"logits_rel_rms_T{T}_{i}_median_row"] = median
        facts[f"logits_rel_rms_T{T}_{i}_worst_row"] = worst_row
        worst, worst_median = max(worst, rms), max(worst_median, median)
        if T + n <= model.prefill_cap:
            # the probe is one dense program over the whole sequence:
            # beside the pools it fits up to the top bucket
            differ = np.any(routed_sets(model, prompt + tokens)
                            != np.asarray(masks), axis=-1)   # (layers, T)
            facts[f"top_k_set_differs_share_T{T}_{i}"] = float(
                differ.mean())
        facts[f"verify_seconds_T{T}_{i}"] = round(
            time.perf_counter() - t0, 1)
        if i != int(tol.get("ablation_prompt", 0)):
            continue
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol.get("ablations", ())]
        if tol.get("precision_below"):
            # over a limit at all: the reference in the precision below
            # the configuration's must come out as not correct
            variants.append((tol["precision_below"],
                             f"reference_in_{tol['precision_below']}", 1.0))
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
        alloc = model.allocator
        say(f"server up on {srv.address} in {time.perf_counter() - t0:.1f}s; "
            f"run pool {alloc.num_pages} pages x {model.page_size} rows for "
            f"{model.full_layers} full layers (K {model.k_pool.shape[3:]}, "
            f"V {model.v_pool.shape[3:]}), {model.full_pages} pages a run; "
            f"{alloc.state_entries} ring entries of {model.entry_bytes()} "
            f"bytes for {model.window_layers} window layers; top bucket "
            f"{model.prefill_cap}, chunks of {model.chunk_rows}")
        tol = wl["verify"]
        lengths = ([p for p, _ in traffic["prompt_lengths"]]
                   + list(tol["prompt_lens"]))
        ladder, chunks = warm(model, say, lengths)
        say("peak bytes in use after warming: "
            f"{runtime.memory_peak_bytes(jax.devices())}")
        t0 = time.perf_counter()
        correct, facts = verify(model, srv.address, wl, traffic,
                                ctx["seed"], say)
        facts["verify_seconds"] = round(time.perf_counter() - t0, 1)
        say(f"verify: {time.perf_counter() - t0:.1f}s, correct={correct}; "
            f"peak bytes in use {runtime.memory_peak_bytes(jax.devices())}")
        compiled_text, planned = {}, 0
        if ctx["trace"]:
            instrument(engine, spans)
            compiled_text, planned = compiled_texts(
                model, int(traffic["gen_slots"]), ladder, chunks)

        seconds = (min(ctx["seconds"], float(traffic["trace_seconds"]))
                   if ctx["trace"] else ctx["seconds"])
        # a traced window opens behind the clients' first prompts as the
        # untraced one does (the traffic file's ``ramp_why``)
        ramped = ({**traffic, "ramp_seconds": traffic["trace_ramp_seconds"]}
                  if ctx["trace"] else traffic)
        spec = loadgen.spec_of(ramped, srv.address, seconds, ctx["seed"],
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
                cache_rows = sampled_window(seconds, model)
                after = metrics.snapshot()
        out = json.loads(child.stdout.readline())
        child.wait(timeout=300)
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
    if cache_rows:
        facts["cache_rows_mean"] = {
            "full": float(np.mean([f for f, _ in cache_rows])),
            "window": float(np.mean([w for _, w in cache_rows]))}
    # what the walk kernel of a decode step has to read: the full
    # layers' rows at their PUBLISHED bytes (``perf/harness/mimo.py``)
    full_layers, _, kv_heads, _, numbers, _, itemsize = mimo.sizes(
        {"config": cfg})
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        "kv_bytes": mimo.full_bytes(cm["kv_rows"], full_layers, kv_heads,
                                    numbers, itemsize),
        "kv_row_bytes": mimo.full_bytes(1, 1, kv_heads, numbers, itemsize),
        "full_layers": full_layers, "cache_rows": cache_rows,
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
