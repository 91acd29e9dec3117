"""Driver ``generate_paged``: ``paddle serve --gen_config``'s server
object for a model over the paged decoder skeleton
(``paddle_tpu/decode/model.py:PagedDecoderLM``) with a block of its
own, under the load generator of ``perf/drivers/generate.py``.

What differs from ``generate``: ``correct`` compares LOGITS, prefill
and decode through the paged cache, with the plain reference the
cell's file names (``verify.reference``: a module of
``perf/reference/`` with ``forward(params, tokens, num_heads=, top_k=,
eps=, theta=, ablate=, rows=)`` and ``rel_rms``); set-up warms every
prefill bucket of the ladder, not the traffic's lengths; the compiled
texts of the model's own decode step and prefill programs are kept for
the readers, with the trace's module runs (``perf/harness/modules.py``).
The record's keys are ``generate``'s, so its readers work unchanged.
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
from perf.harness import loadgen, modules, runtime, trace as tr
from perf.harness.flops import kv_read_bytes


def buckets_of(model):
    """The prefill ladder: every bucket a prompt of this model can
    fall in."""
    cap = min(model.max_len, model.pages_per_seq * model.page_size)
    out, n = [], 1
    while n <= cap:
        b = model.prefill_bucket(n)
        if b not in out:
            out.append(b)
        n = b + 1
    return out


def warm(model, say):
    """Every bucket's prefill program, once."""
    t0 = time.perf_counter()
    ladder = buckets_of(model)
    for T in ladder:
        pages = model.allocator.alloc(model.context_pages([2] * T, 0))
        try:
            model.prefill([2] * T, pages)
        finally:
            model.allocator.free(pages)
    say(f"prefill warmed for buckets {ladder} in "
        f"{time.perf_counter() - t0:.1f}s")
    return ladder


def through_the_cache(model, prompt, tokens, slots, cached_len=0):
    """Prefill ``prompt`` (its suffix over cached pages when
    ``cached_len``), then feed ``tokens`` teacher-forced, one decode
    step each, through the paged cache at the serving step's shape:
    the len(tokens) + 1 logits rows."""
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    try:
        if cached_len:
            model.prefill(prompt[:cached_len], pages)
        ctx, _, last = model.prefill(prompt, pages, cached_len=cached_len)
        rows = [np.asarray(last, np.float32)]
        slot = slots // 2
        tables = np.zeros((slots, model.pages_per_seq), np.int32)
        tables[slot] = model.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), model.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = model.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot], np.float32))
    finally:
        model.allocator.free(pages)
    return np.stack(rows)


def routed_sets(model, tokens):
    """(L, T, E) bool: the experts the SYSTEM routes each row of one
    sequence to, by its own block functions over the dense forward (a
    probe from the benchmark's side; the program hands out counts, not
    sets).  None for a block that routes nothing."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode.attention import dense_prefill_attention
    from paddle_tpu.models import moe

    block = model.block
    if not hasattr(block, "router_rows"):
        return None

    @jax.jit
    def run(params, toks):
        T = toks.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        x = block.embed(params, toks, pos)
        sets = []
        for lp in params["layers"]:
            q, k, v = block.qkv(lp, x, pos, model.heads)
            a = dense_prefill_attention(q, k, v, causal=True)
            x = block.attn_out(lp, x, a.reshape(T, -1))
            _, idx = moe.route(block.router_rows(lp, x), lp["wr"],
                               block.top_k)
            E = lp["wr"].shape[1]
            sets.append(jnp.any(idx[..., None] == jnp.arange(E), axis=1))
            x, _ = block.mlp(lp, x, None)
        return jnp.stack(sets)

    return np.asarray(run(model.params, jnp.asarray(tokens, jnp.int32)))


def verify(model, address, wl, traffic, seed, say):
    """(a) prefill, then 16 teacher-forced decode steps through the
    paged cache: all 17 logits rows of each seeded prompt against the
    reference's full forward over prompt + tokens, by relative RMS,
    and each ablation of the reference against the first prompt's
    rows; (b) a suffix prefill over cached pages against the same
    reference row; (c) greedy streams through /generate end with their
    count of tokens."""
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
            model.params, jnp.asarray(ids, jnp.int32), num_heads=model.heads,
            top_k=block.top_k, eps=block.eps, theta=block.theta,
            ablate=ablate, rows=rows)

    worst = 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got = through_the_cache(model, prompt, tokens, slots)
        rows = list(range(T - 1, T + n))
        want, masks = reference(prompt + tokens, rows)
        per_row = [ref.rel_rms(got[j], want[j]) for j in range(n + 1)]
        facts[f"logits_rel_rms_T{T}_{i}"] = ref.rel_rms(got, want)
        facts[f"logits_rel_rms_T{T}_{i}_worst_row"] = max(per_row)
        worst = max(worst, facts[f"logits_rel_rms_T{T}_{i}"])
        if i:
            continue
        sets = routed_sets(model, prompt + tokens)
        if sets is not None:
            differ = np.any(sets != np.asarray(masks), axis=-1)   # (L, T)
            facts["top_k_set_differs_share"] = float(differ.mean())
        for ablate in tol.get("ablations", ()):
            wrong, _ = reference(prompt + tokens, rows, ablate)
            k = f"logits_rel_rms_without_{ablate}"
            facts[k] = ref.rel_rms(got, wrong)
            factor = tol.get("ablation_factor", {}).get(ablate, 4)
            if facts[k] < factor * limit:
                problems.append(f"the tolerance {limit} would not catch "
                                f"{ablate} by {factor}x ({k} "
                                f"{facts[k]:.3e})")
        below = tol.get("precision_below")
        if below:
            k = f"logits_rel_rms_reference_in_{below}"
            facts[k] = ref.rel_rms(got, reference(prompt + tokens, rows,
                                                  below)[0])
            if facts[k] <= limit:
                problems.append(f"the tolerance {limit} would pass the "
                                f"reference computed in {below} "
                                f"({facts[k]:.3e})")
        c = int(tol["cached_len"])
        suffix = through_the_cache(model, prompt, tokens, slots,
                                   cached_len=c)
        facts[f"suffix_prefill_rel_rms_cached{c}"] = ref.rel_rms(suffix,
                                                                 want)
        worst = max(worst, facts[f"suffix_prefill_rel_rms_cached{c}"])
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
    as compiled text, and the decode step's planned bytes."""
    from paddle_tpu.decode import model as dm

    step = dm._decode_step.lower(
        model.params, model.k_pool, model.v_pool,
        np.zeros((slots, model.pages_per_seq), np.int32),
        np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
        heads=model.heads, page_size=model.page_size,
        block=model.block).compile()
    texts = {"decode_step": step.as_text()}
    for b in ladder:
        texts[f"prefill_bucket_{b}"] = dm._prefill_bucket.lower(
            model.params, model.k_pool, model.v_pool,
            np.zeros((b,), np.int32), np.zeros((b,), np.int32), np.int32(1),
            heads=model.heads, block=model.block).compile().as_text()
    return texts, runtime.planned_bytes(step)


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
            f"rows, {model.pages_per_seq} pages a sequence, "
            f"{model.k_pool.dtype} pages")
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
                time.sleep(seconds)
                after = metrics.snapshot()
        out = json.loads(child.stdout.readline())
        child.wait(timeout=60)
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
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        "kv_bytes": kv_read_bytes(
            cm["kv_rows"], model.heads, model.dh, model.layers,
            np.dtype(model.k_pool.dtype).itemsize),
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


def _count(module_events):
    out = {}
    for name, _, _ in module_events:
        key = name.split("(")[0]
        out[key] = out.get(key, 0) + 1
    return out
