"""Driver ``generate_hybrid``: ``generate_window`` for a model over the
paged skeleton whose linear-attention layers keep a recurrent state a
sequence beside the K/V pages of its full-attention layers, in one
cache manager (``paddle_tpu/models/olmo_hybrid.py``).

What differs from ``generate_window``, and why it could not be told to
that driver by data: the reference takes this model's geometry (the
linear layers' heads and widths; no window, no experts) and hands back
logits alone (nothing is routed); ``correct`` holds every ablation to
ONE limit by its stated factor and reports ``state_bf16`` beside it;
the model's programs take the state pools donated with the K/V pools
(``extra``) and a prefill's addresses are the page run's rows and the
state entry; the K/V bytes a decode step's kernel reads are the full
layers' at the heads a page is stored at; and the window samples the
``decode_cache_bytes`` gauges.  The load, the window, the record's keys
and so the readers are ``generate_paged``'s.
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
from perf.drivers.generate_paged import _count, through_the_cache
from perf.drivers.generate_window import buckets_of, warm
from perf.harness import loadgen, modules, runtime
from perf.harness import trace as tr

SAMPLE_EVERY_S = 0.25


def verify(model, address, wl, traffic, seed, say):
    """(a) each seeded prompt prefilled through its bucket's program,
    then 16 seeded tokens teacher-forced through both caches at the
    serving step's shape: all 17 logits rows against the reference's
    full forward over prompt + tokens, by relative RMS; each ablation
    of the reference, and the reference in the precision below, against
    the rows of prompt ``ablation_prompt``, which the limit has to
    catch by the ablation's stated factor (``reported``: read and
    written down, held by the CPU test); (b) greedy streams through
    /generate end with their count of tokens."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            head_dim=block.head_dim, lin_heads=block.lin_heads,
            d_k=block.d_k, d_v=block.d_v, eps=block.eps, ablate=ablate,
            rows=rows)

    worst = 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got = through_the_cache(model, prompt, tokens, slots)
        rows = list(range(T - 1, T + n))
        want = reference(prompt + tokens, rows)
        rms = ref.rel_rms(got, want)
        facts[f"logits_rel_rms_T{T}_{i}"] = rms
        facts[f"logits_rel_rms_T{T}_{i}_worst_row"] = max(
            ref.rel_rms(g, w) for g, w in zip(got, want))
        worst = max(worst, rms)
        if i != int(tol.get("ablation_prompt", 0)):
            continue
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol.get("ablations", ())]
        if tol.get("precision_below"):
            # over the limit at all: the reference in the precision below
            # the configuration's must come out as not correct
            variants.append((tol["precision_below"],
                             f"reference_in_{tol['precision_below']}", 1.0))
        for ablate, name, factor in variants:
            rms = ref.rel_rms(got, reference(prompt + tokens, rows, ablate))
            facts[f"logits_rel_rms_{name}"] = rms
            if rms <= factor * limit:
                problems.append(f"the limit {limit} would not catch {name} "
                                f"by {factor}x: {rms:.3e}")
        for ablate in tol.get("reported", ()):
            facts[f"logits_rel_rms_without_{ablate}"] = ref.rel_rms(
                got, reference(prompt + tokens, rows, ablate))
    facts["logits_rel_rms_worst"] = worst
    if not worst <= limit:
        problems.append(f"logits relative RMS {worst:.3e} > {limit}")
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

    cache = model._cache()
    step = dm._decode_step.lower(
        model.params, *cache[:2],
        np.zeros((slots, model.pages_per_seq), np.int32),
        np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
        heads=model.heads, page_size=model.page_size, block=model.block,
        extra=cache[2:]).compile()
    texts = {"decode_step": step.as_text()}
    planned = runtime.planned_bytes(step)
    for b in ladder:
        prefill = dm._prefill_bucket.lower(
            model.params, *cache[:2], np.zeros((b,), np.int32),
            (np.zeros((b,), np.int32), np.int32(0)), np.int32(1),
            heads=model.heads, block=model.block,
            extra=cache[2:]).compile()
        texts[f"prefill_bucket_{b}"] = prefill.as_text()
        planned = max(planned, runtime.planned_bytes(prefill))
    return texts, planned


def sampled_window(seconds):
    """Sleep through the window, reading the ``decode_cache_bytes``
    gauges every ``SAMPLE_EVERY_S``: [(full bytes, state bytes)], the
    samples with a sequence seated.  None from a program that has no
    such gauge."""
    from paddle_tpu.observability import metrics

    gauge = metrics.REGISTRY.get("decode_cache_bytes")
    samples, t_end = [], time.perf_counter() + seconds
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        time.sleep(min(SAMPLE_EVERY_S, left))
        if gauge is not None:
            state = gauge.value(kind="state")
            if state > 0:
                samples.append((gauge.value(kind="full"), state))
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
        alloc = model.allocator
        say(f"server up on {srv.address} in {time.perf_counter() - t0:.1f}s; "
            f"pool {alloc.num_pages} pages x {model.page_size} rows of "
            f"{model.stored_heads} stored heads for {model.full_layers} "
            f"full layers, {model.full_pages} pages a run; "
            f"{alloc.state_entries} state entries of {model.entry_bytes()} "
            f"bytes for {model.linear_layers} linear layers")
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
                cache_bytes = sampled_window(seconds)
                after = metrics.snapshot()
        out = json.loads(child.stdout.readline())
        child.wait(timeout=120)
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
    facts["cache_byte_samples"] = len(cache_bytes or ())
    # what the paged kernel of a decode step reads: the full layers'
    # rows, at the heads a page is stored at (bytes as read)
    kv_row_bytes = (2.0 * model.stored_heads * model.dh
                    * np.dtype(model.k_pool.dtype).itemsize)
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        "kv_bytes": kv_row_bytes * cm["kv_rows"] * model.full_layers,
        "kv_row_bytes": kv_row_bytes, "full_layers": model.full_layers,
        "cache_bytes": cache_bytes,
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
