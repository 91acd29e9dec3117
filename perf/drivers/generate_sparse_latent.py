"""Driver ``generate_sparse_latent``: ``generate_latent`` for a latent
model whose every query row reads only the cached rows a learned indexer
chooses (``paddle_tpu/models/glm_dsa.py``: an index-key row beside every
latent row on the same pages, the selection inside the decode step, a
prompt over the top bucket prefilled in chunks over what is cached).

What differs from ``generate_latent``, and why it could not be told to
that driver by data: the reference takes the indexer's geometry, hands
back the selected sets and can be GIVEN sets; ``correct`` holds the
median row POOLED over all the prompts' rows and every prompt's whole
RMS (PERF.md section 7, what refused PR 49: the median of ONE 17-row
prompt is a limit on whether 9 of 17 rows carry a flipped expert), holds
the system's selected sets to the reference's as sets and the logits
again to the reference given those sets, and reports what float8 index
rows do to the sets (which need not fail the logits); set-up warms the
ladder up to the top bucket and then the chunk programs by one prompt of
the traffic's longest; the compiled texts hold the chunk programs beside the
buckets'; the cache's bytes a row are the latent row's and the index
row's.  The load, the window, the record's keys and so the readers are
``generate_paged``'s.
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
from perf.drivers.generate_latent import sampled_window
from perf.drivers.generate_paged import _count, through_the_cache
from perf.harness import loadgen, modules, runtime
from perf.harness import trace as tr


def ladder_of(model):
    """The bucket ladder up to the model's top bucket."""
    out, n = [], 1
    while n <= model.prefill_cap:
        b = model.prefill_bucket(n)
        out.append(b)
        n = b + 1
    return out


def chunks_of(model, rows):
    """[(chunk rows, extent pages)] of the chunk programs a prompt of
    ``rows`` rows runs after its top bucket."""
    out, done, cap = [], model.prefill_cap, model.prefill_cap
    while done < rows:
        C = model._chunk_of(rows - done)
        extent = min(model.seq_rows, -(-(done + C) // cap) * cap)
        out.append((C, extent // model.page_size))
        done += min(C, rows - done)
    return out


def warm(model, say, longest):
    """Every bucket's prefill program, once, and the chunk programs of
    a prompt of ``longest`` rows."""
    t0 = time.perf_counter()
    ladder = ladder_of(model)
    for T in ladder + ([longest] if longest > model.prefill_cap else []):
        pages = model.allocator.alloc(model.context_pages([2] * T, 0))
        try:
            model.prefill([2] * T, pages)
        finally:
            model.allocator.free(pages)
    chunks = sorted(set(chunks_of(model, longest)))
    say(f"prefill warmed for buckets {ladder} and chunks {chunks} (rows, "
        f"extent pages) in {time.perf_counter() - t0:.1f}s")
    return ladder, chunks


def _whole_blocks(ids):
    """``ids`` padded on the right to whole blocks of 512 rows, so that
    the probes below run the prefill's kernels (causal: no row sees the
    padding)."""
    return list(ids) + [2] * (-len(ids) % 512)


def system_sets(model, ids):
    """((layers, T, T) bool, (routed layers, T, experts) bool): the rows
    the SYSTEM's block functions select for each row of one sequence and
    the experts its router chooses, in the weights' precision, by ONE
    dense forward (``glm_dsa.chosen_sets``)."""
    from paddle_tpu.models.glm_dsa import chosen_sets

    T = len(ids)
    sets, routed = chosen_sets(model, _whole_blocks(ids))
    return sets[:, :T, :T], routed[:, :T]


def members_differ(a, b):
    """Share of the selected (row, member) pairs of two sets of masks
    that only one of them holds: |a xor b| / (|a| + |b|)."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.logical_xor(a, b).sum() / (a.sum() + b.sum()))


def verify(model, address, wl, traffic, seed, say):
    """(a) each seeded prompt (one under ``index_topk`` rows, one through
    the top bucket, one prefilled in chunks) through the timed programs,
    then 16 teacher-forced decode steps through the pages at the serving
    step's shape: all 17 logits rows against the reference's full
    forward over prompt + tokens, the reference selecting for itself:
    every prompt's relative RMS over its rows is held to one limit and
    the median row of ALL the prompts' rows pooled to the other.  A row's
    2,048th index score is an edge bfloat16 cannot resolve, a member that
    flips there carries a random value vector, and so these two limits
    are wide (the workload's ``why``).  So (b), on prompt
    ``ablation_prompt``: the SETS the system's block functions select are
    held to the reference's as sets (``index_members_differ_share``), and
    the logits again, and the suffix prefilled over ``cached_len`` cached
    rows, to the reference GIVEN the system's sets, at two tight limits;
    each ablation of the indexer must move the sets by its stated
    multiple of that limit, an expert fewer a row the ROUTER's chosen
    sets by its multiple of theirs (``top_k_set_differs_share``: one
    expert in eight, held here one time in sixteen, moves the median row
    by 2 to 10 times the noise, seed by seed), each other ablation and
    each precision below the logits given the sets by its multiple of
    one of the tight two;
    what float8 index rows do to the sets is reported; (c) greedy
    streams through /generate end with their count of tokens."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    median_limit = float(tol["logits_rel_rms_median_row"])
    tight = float(tol["given_sets_rel_rms"])
    tight_median = float(tol["given_sets_rel_rms_median_row"])
    sets_limit = float(tol["index_members_differ_share"])
    router_limit = float(tol["top_k_set_differs_share"])
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None, sets=False, given=None):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32), num_heads=model.heads,
            nope=block.nope, rope_dim=block.rope_dim,
            index_heads=block.index_heads, index_rope=block.index_rope,
            index_topk=block.index_topk, top_k=block.top_k,
            scale=block.scale, held=block.held, eps=block.eps,
            theta=block.theta, ablate=ablate, rows=rows, sets=sets,
            given=given)

    def readings(name, got, want):
        rows = [ref.rel_rms(g, w) for g, w in zip(got, want)]
        facts[name] = ref.rel_rms(got, want)
        facts[name + "_median_row"] = float(np.median(rows))
        facts[name + "_worst_row"] = max(rows)
        return rows

    pooled, worst, given_rows, given_worst = [], 0.0, [], 0.0
    for i, T in enumerate(tol["prompt_lens"]):
        t0 = time.perf_counter()
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        ids = prompt + tokens
        got = through_the_cache(model, prompt, tokens, slots)
        t1 = time.perf_counter()
        rows = list(range(T - 1, T + n))
        ablating = i == int(tol["ablation_prompt"])
        want, masks, sets = reference(ids, rows, sets=ablating)
        name = f"logits_rel_rms_T{T}_{i}"
        pooled.extend(readings(name, got, want))
        worst = max(worst, facts[name])
        facts[f"seconds_T{T}_{i}"] = [round(t1 - t0, 2),
                                      round(time.perf_counter() - t1, 2)]
        if not ablating:
            continue
        mine, routed = system_sets(model, ids)

        def router_differs(masks):
            """Share of (routed layer, row) pairs whose chosen experts
            are not the system's."""
            return float(np.any(routed != np.asarray(masks), axis=-1).mean())

        facts["top_k_set_differs_share"] = router_differs(masks)
        if not facts["top_k_set_differs_share"] <= router_limit:
            problems.append(
                "the router's chosen sets differ from the reference's in "
                f"{facts['top_k_set_differs_share']:.3e} of the rows > "
                f"{router_limit}")
        sets = np.asarray(sets)
        facts["index_set_differs_share"] = float(
            np.any(mine != sets, axis=-1).mean())
        facts["index_members_differ_share"] = members_differ(mine, sets)
        if not facts["index_members_differ_share"] <= sets_limit:
            problems.append(
                "the selected sets differ from the reference's in "
                f"{facts['index_members_differ_share']:.3e} of their "
                f"members > {sets_limit}")
        given = jnp.asarray(mine)
        want_given = reference(ids, rows, given=given)[0]
        c = int(tol["cached_len"])
        for name, rows_got in (
                ("given_sets_rel_rms", got),
                (f"given_sets_suffix_prefill_rel_rms_cached{c}",
                 through_the_cache(model, prompt, tokens, slots,
                                   cached_len=c))):
            given_rows.extend(readings(name, rows_got, want_given))
            given_worst = max(given_worst, facts[name])
        on_sets = set(tol["judged_on_sets"])
        on_router = set(tol["judged_on_router_sets"])
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol["ablations"]]
        # over a limit at all: the reference in a precision below the
        # configuration's must come out as not correct
        variants += [(p, f"reference_in_{p}", 1.0)
                     for p in tol["precisions_below"]]
        variants += [(p, f"reference_in_{p}", None)
                     for p in tol.get("precisions_reported", ())]
        for ablate, name, factor in variants:
            free = ablate in on_sets or factor is None
            wrong, wrong_masks, wrong_sets = reference(
                ids, rows, ablate, sets=free, given=None if free else given)
            wrong_rows = [ref.rel_rms(g, w) for g, w in zip(got, wrong)]
            rms, median = ref.rel_rms(got, wrong), float(
                np.median(wrong_rows))
            facts[f"logits_rel_rms_{name}"] = rms
            facts[f"logits_rel_rms_{name}_median_row"] = median
            if free:
                share = members_differ(mine, wrong_sets)
                facts[f"index_members_differ_share_{name}"] = share
                if factor is not None and share <= factor * sets_limit:
                    problems.append(
                        f"the sets' limit would not catch {name} by "
                        f"{factor}x: {share:.3e} of {sets_limit}")
            elif ablate in on_router:
                share = router_differs(wrong_masks)
                facts[f"top_k_set_differs_share_{name}"] = share
                if share <= factor * router_limit:
                    problems.append(
                        f"the router's limit would not catch {name} by "
                        f"{factor}x: {share:.3e} of {router_limit}")
            elif rms <= factor * tight and median <= factor * tight_median:
                problems.append(
                    f"neither limit given the sets would catch {name} by "
                    f"{factor}x: {rms:.3e} of {tight}, median row "
                    f"{median:.3e} of {tight_median}")
        facts["seconds_ablations"] = round(time.perf_counter() - t1, 2)
    pooled_median = float(np.median(pooled))
    given_median = float(np.median(given_rows))
    facts["logits_rel_rms_worst"] = worst
    facts["logits_rel_rms_median_row_pooled"] = pooled_median
    facts["rows_pooled"] = len(pooled)
    facts["given_sets_rel_rms_worst"] = given_worst
    facts["given_sets_rel_rms_median_row_pooled"] = given_median
    for what, read, lim in (
            ("logits relative RMS", worst, limit),
            (f"logits relative RMS of the median of all {len(pooled)} rows",
             pooled_median, median_limit),
            ("logits relative RMS given the sets", given_worst, tight),
            ("logits relative RMS given the sets of the median of "
             f"{len(given_rows)} rows", given_median, tight_median)):
        if not read <= lim:
            problems.append(f"{what} {read:.3e} > {lim}")
    for _ in range(int(tol["streams"])):
        p = rng.randint(2, model.vocab, int(tol["stream_prompt_len"])).tolist()
        out = _generate(address, p, n)
        if len(out) != n:
            problems.append(f"/generate gave {len(out)} tokens of {n}")
    say(f"reference check: {facts}")
    for problem in problems:
        say(f"NOT CORRECT: {problem}")
    return not problems, facts


def compiled_texts(model, slots, ladder, chunks):
    """The model's own decode step, one prefill program a bucket and one
    a chunk shape, as compiled text, and the planned bytes of the
    largest."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import glm_dsa as gd

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
    for C, extent in chunks:
        chunk = gd._prefill_bucket_chunk.lower(
            model.params, model.k_pool, model.v_pool,
            np.zeros((model.pages_per_seq,), np.int32), np.int32(0),
            np.zeros((C,), np.int32), np.int32(1), heads=model.heads,
            page_size=model.page_size, block=model.block,
            extent=extent).compile()
        texts[f"prefill_bucket_chunk_{C}_over_{extent}"] = chunk.as_text()
        planned = max(planned, runtime.planned_bytes(chunk))
    return texts, planned


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
            f"rows x ({model.block.width} + {model.block.index_dim}) lanes, "
            f"{model.pages_per_seq} pages a sequence, {model.k_pool.dtype} "
            "latent and index rows")
        ladder, chunks = warm(model, say,
                              max(p for p, _ in traffic["prompt_lengths"]))
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
        # a traced window opens behind the clients' first prompts (the
        # traffic file's ``trace_ramp_why``)
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
        # had cached (a dense walk's read; the steps read the selected)
        "latent_rows": cm["kv_rows"],
        # as STORED (cache_bytes_per_live_row: what is resident): the
        # latent row and the index row beside it
        "kv_row_bytes": model.row_bytes + model.index_row_bytes,
        "full_layers": model.layers, "cache_rows": cache_rows,
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
