"""Driver ``generate_hybrid_latent``: ``generate_hybrid`` for a model
over the paged skeleton whose recurrent layers keep a state entry a
sequence beside ONE latent row a token in its attention layer, with a
held range of experts under a router that has a group step
(``paddle_tpu/models/ling_hybrid.py``).

What differs from ``generate_hybrid`` and ``generate_latent``, and why
it could not be told to either by data: the reference takes this
model's geometry (the KDA heads and the lower bound of their gate, the
latent head sizes, the held range, the groups) and hands back the
router's chosen sets beside the logits; ``correct`` holds a row the
router's flipped choices leave clean beside all rows (``CLEAN_ROW``:
the row at the first quartile of the compared rows, where
``generate_latent`` takes the median; the reason is at the constant),
each ablation and each precision below to one of the two limits by its
stated factor, the ablations that move the
router's sets more surely than a logit (the group step off) on those
sets, and the STATES' precision on a state entry itself, as
``generate_ssm`` does and for its reason (sixteen teacher-forced rows
of a random model read nearly the same with every state rounded to
bfloat16 after every row; the states are a quarter of what a decode
step moves): the first KDA layer's entry as a prefill and then 16 steps
leave it against the entry ONE prefill of the same rows leaves, where
both routes see the same inputs and agree to float32 rounding; and the
LATENT ROWS' precision on the rows the pages hold themselves (ONE latent
layer of six: float8 rows move a logit by a tenth of what the bf16
program's rounding does, and by the sixth layer that rounding has moved
a row by as much as float8 would): the sequence's rows as the prefill
and the 16 steps wrote them are held to the reference's, loosely, and to
their OWN rounding to float8, which rows that carry bfloat16's mantissa
stand ~0.025 away from and float8 rows stand on; no suffix prefill is
checked (a prefill over cached rows is
refused over a state); the model's programs take the state pools
donated with the page pools (``extra``) and a prefill's addresses are
the page run's rows and the state entry; the bytes a decode step's
kernel reads are latent rows'; and the window samples the
``decode_cache_bytes`` gauges at kinds ``latent`` and ``state``.  The
load, the window, the record's keys and so the readers are
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
from perf.drivers.generate_paged import _count, warm
from perf.harness import loadgen, modules, runtime
from perf.harness import trace as tr

SAMPLE_EVERY_S = 0.25
WHOLE = 128        # rows the probe pads a sequence to a multiple of
# Which of the compared rows stands for "a row": the one at the first
# quartile by its distance.  With the reference made to choose the
# SYSTEM's experts every row reads the same 0.010-0.012 (bf16 operands;
# PERF.md section 6, PR 55).  A row where the bf16 router chose another
# expert of the held range than the float32 one reads 0.04-0.12, and
# the rows after it a little of that through the states.  That is a row
# in about three of ten (5-16 of a run's 51 read over 0.04), each on
# its own, so nine of ONE prompt's 17, which is what moves their
# MEDIAN, is a prompt in 25-45 (two of 90 prompts read 0.0452 and
# 0.0505 where the limit is 0.04, and the driver's check met the
# second), while 38 of the three prompts' 51, which is what moves their
# first quartile, is under one run in a million even at four rows of
# ten.  A fault of precision or of an equation moves every row, and so
# this one.
CLEAN_ROW = 0.25


def routed_sets(model, tokens):
    """(routed layers, T, E) bool: the experts the SYSTEM chooses for
    each row of one sequence, by its own block functions over the dense
    forward (a probe from the benchmark's side; the program hands out
    counts, not sets).  The sequence is padded on the right to whole
    chunks so that the probe runs the prefill's kernels (causal: no row
    sees the padding)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import moe
    from paddle_tpu.models.olmoe import rms_norm

    block, T = model.block, len(tokens)
    ids = list(tokens) + [2] * (-T % WHOLE)

    @jax.jit
    def run(params, toks):
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)
        x = block.embed(params, toks, pos)
        sets = []
        for li, lp in enumerate(params["layers"]):
            lb = block.layer(li)
            x, _ = lb.prompt_mixer(lp, x, pos, model.heads, None)
            if "wr" in lp:
                m = rms_norm(x, lp["w_post"], block.eps).astype(
                    lp["wr"].dtype)
                _, idx = moe.route(m, lp["wr"], block.top_k,
                                   moe.sigmoid_scores(lp["b"], block.scale),
                                   groups=block.groups)
                E = lp["wr"].shape[1]
                sets.append(jnp.any(idx[..., None] == jnp.arange(E),
                                    axis=1))
            x, _ = lb.mlp(lp, x, None)
        return jnp.stack(sets)

    return np.asarray(run(model.params, jnp.asarray(ids, jnp.int32)))[:, :T]


def latent_rows(model, pages, n):
    """The first ``n`` rows of the sequence that holds ``pages`` as the
    first latent layer's pages hold them, the algorithm's lanes alone:
    (n, rank + rope) float32, on the host."""
    lat = model.block.latent
    run = np.asarray(model.allocator.pages_of(pages))
    rows = np.asarray(model.k_pool[0, run].astype(np.float32))
    return rows.reshape(-1, rows.shape[-1])[:n, :lat.rank + lat.rope_dim]


def float8_gap(rows):
    """How far ``rows`` stand from their own rounding to float8_e4m3fn,
    relative RMS: ~0.025 for rows that carry more mantissa than float8
    has, 0 for rows float8 holds."""
    import jax.numpy as jnp

    rows = np.asarray(rows, np.float32)
    held = np.asarray(jnp.asarray(rows).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    return float(np.sqrt(np.mean(np.square(held - rows))
                         / np.mean(np.square(rows))))


def first_state(model, pages):
    """The first KDA layer's state of the sequence that holds ``pages``:
    (H, d_v, d_k) float32, on the host."""
    entry = model.allocator.entry_of(pages)
    return np.asarray(model.state_pool[0, entry])[..., :model.block.d_k]


def through_the_cache(model, prompt, tokens, slots):
    """``generate_paged.through_the_cache`` (prefill, then ``tokens``
    teacher-forced, one decode step each, at the serving step's shape)
    -> (the len(tokens) + 1 logits rows, the first KDA layer's state
    after the last token, the latent rows of prompt + tokens as the
    pages hold them)."""
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    try:
        ctx, _, last = model.prefill(prompt, pages)
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
        state = first_state(model, pages)
        stored = latent_rows(model, pages, len(prompt) + len(tokens))
    finally:
        model.allocator.free(pages)
    return np.stack(rows), state, stored


def through_one_prefill(model, ids):
    """The first KDA layer's state ONE prefill of ``ids`` leaves."""
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    try:
        model.prefill(ids, pages)
        return first_state(model, pages)
    finally:
        model.allocator.free(pages)


def verify(model, address, wl, traffic, seed, say):
    """(a) each seeded prompt prefilled through its bucket's program
    (the chunked KDA kernel, latent attention expanded), then 16 seeded
    tokens teacher-forced through the entries and the pages at the
    serving step's shape (the KDA step kernel, latent attention
    absorbed): all 17 logits rows against the reference's full forward
    over prompt + tokens, by relative RMS over a prompt's rows and by
    the ``CLEAN_ROW`` row's of all prompts' rows; (b) on prompt
    ``ablation_prompt``: the share of (routed layer, row) pairs whose
    chosen experts are not the reference's, each
    ablation of the reference at its stated multiple of one of the two
    limits (those of ``judged_on_router_sets``: of the sets' limit), the
    reference in each precision below over one of the two, but the
    latent rows' and the states': the rows the latent layer's pages hold
    of prompt + tokens against the reference's (``latent_rows_rel_rms``,
    loose) and against their own rounding to float8
    (``latent_rows_float8_gap``, a floor), which the reference's rows
    rounded to float8 have to read under; the
    first KDA layer's entry after prefill + steps against the entry one
    prefill of the same rows leaves (``state_rel_rms``), which the
    reference's final state with its states rounded to bfloat16 after
    every row, against its own float32 one, has to read over; (c)
    greedy streams through /generate end with their count of
    tokens."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    clean_limit = float(tol["logits_rel_rms_quartile_row"])
    router_limit = float(tol["top_k_set_differs_share"])
    state_limit = float(tol["state_rel_rms"])
    rows_limit = float(tol["latent_rows_rel_rms"])
    gap_floor = float(tol["latent_rows_float8_gap"])
    on_router = set(tol.get("judged_on_router_sets", ()))
    on_state = set(tol.get("judged_on_state", ()))
    on_rows = set(tol.get("judged_on_latent_rows", ()))
    block = model.block
    facts, problems = {}, []

    def reference(ids, rows, ablate=None, states=False):
        lat = block.latent
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            nope=lat.nope, rope_dim=lat.rope_dim, lin_heads=block.lin_heads,
            d_k=block.d_k, d_v=block.d_v, lower_bound=block.lower_bound,
            top_k=block.top_k, scale=block.scale, held=block.held,
            n_group=block.groups[0], topk_group=block.groups[1],
            eps=block.eps, theta=lat.theta, ablate=ablate, rows=rows,
            states=states)

    def by_row(got, want):
        return [ref.rel_rms(g, w) for g, w in zip(got, want)]

    def clean_row(rows):
        return float(np.quantile(rows, CLEAN_ROW))

    worst, every_row = 0.0, []
    for i, T in enumerate(tol["prompt_lens"]):
        t0 = time.perf_counter()
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        got, state, stored = through_the_cache(model, prompt, tokens, slots)
        t1 = time.perf_counter()
        rows = list(range(T - 1, T + n))
        ablating = i == int(tol["ablation_prompt"])
        want, masks, *kept = reference(prompt + tokens, rows, states=ablating)
        rms, per_row = ref.rel_rms(got, want), by_row(got, want)
        name = f"logits_rel_rms_T{T}_{i}"
        facts.update({name: rms,
                      name + "_quartile_row": clean_row(per_row),
                      name + "_median_row": float(np.median(per_row)),
                      name + "_worst_row": max(per_row)})
        worst = max(worst, rms)
        every_row += per_row
        facts[f"seconds_T{T}_{i}"] = [round(t1 - t0, 2),
                                      round(time.perf_counter() - t1, 2)]
        if not ablating:
            continue
        # the entry two ways through the system, and (written down, not
        # held: bfloat16 operands move every layer's inputs) against the
        # reference's final state
        facts["state_rel_rms"] = ref.rel_rms(
            state, through_one_prefill(model, prompt + tokens))
        facts["state_rel_rms_to_reference"] = ref.rel_rms(state, kept[0][0])
        if not facts["state_rel_rms"] <= state_limit:
            problems.append("the first KDA layer's state "
                            f"{facts['state_rel_rms']:.3e} > {state_limit}")
        facts["latent_rows_rel_rms"] = ref.rel_rms(stored, kept[1][0])
        if not facts["latent_rows_rel_rms"] <= rows_limit:
            problems.append("the latent rows on the pages "
                            f"{facts['latent_rows_rel_rms']:.3e} > "
                            f"{rows_limit}")
        facts["latent_rows_float8_gap"] = float8_gap(stored)
        if not facts["latent_rows_float8_gap"] >= gap_floor:
            problems.append("the latent rows on the pages stand "
                            f"{facts['latent_rows_float8_gap']:.3e} from "
                            f"float8 rows < {gap_floor}")
        routed = routed_sets(model, prompt + tokens)

        def router_differs(masks):
            return float(np.any(routed != np.asarray(masks), axis=-1).mean())

        facts["top_k_set_differs_share"] = router_differs(masks)
        if not facts["top_k_set_differs_share"] <= router_limit:
            problems.append(
                "the router's chosen sets differ from the reference's in "
                f"{facts['top_k_set_differs_share']:.3e} of the rows > "
                f"{router_limit}")
        variants = [(a, f"without_{a}", tol["ablation_factor"][a])
                    for a in tol["ablations"]]
        # over a limit at all: the reference in a precision below the
        # configuration's must come out as not correct
        variants += [(p, f"reference_in_{p}", 1.0)
                     for p in tol["precisions_below"]]
        # read and written down, not held
        variants += [(a, f"without_{a}", None)
                     for a in tol.get("ablations_reported", ())]
        for ablate, name, factor in variants:
            wrong, wrong_masks, *wrong_kept = reference(
                prompt + tokens, rows, ablate,
                states=ablate in on_state | on_rows)
            rms, clean = ref.rel_rms(got, wrong), clean_row(by_row(got, wrong))
            # (the L2 norms off: the delta rule's transition leaves the
            # unit ball and the state overflows; read as infinitely far)
            rms, clean = (x if np.isfinite(x) else float("inf")
                          for x in (rms, clean))
            facts[f"logits_rel_rms_{name}"] = rms
            facts[f"logits_rel_rms_{name}_quartile_row"] = clean
            if factor is None:
                continue
            if ablate in on_state:
                far = ref.rel_rms(wrong_kept[0][0], kept[0][0])
                facts[f"state_rel_rms_{name}"] = far
                if far <= factor * state_limit:
                    problems.append(
                        f"the state's limit would not catch {name} by "
                        f"{factor}x: {far:.3e} of {state_limit}")
            elif ablate in on_rows:
                facts[f"latent_rows_rel_rms_{name}"] = ref.rel_rms(
                    stored, wrong_kept[1][0])
                gap = float8_gap(wrong_kept[1][0])
                facts[f"latent_rows_float8_gap_{name}"] = gap
                if factor * gap >= gap_floor:
                    problems.append(
                        f"the rows' floor would not catch {name} by "
                        f"{factor}x: {gap:.3e} of {gap_floor}")
            elif ablate in on_router:
                share = router_differs(wrong_masks)
                facts[f"top_k_set_differs_share_{name}"] = share
                if share <= factor * router_limit:
                    problems.append(
                        f"the router's limit would not catch {name} by "
                        f"{factor}x: {share:.3e} of {router_limit}")
            elif rms <= factor * limit and clean <= factor * clean_limit:
                problems.append(
                    f"neither limit would catch {name} by {factor}x: "
                    f"{rms:.3e} of {limit}, quartile row {clean:.3e} of "
                    f"{clean_limit}")
        facts["seconds_ablations"] = round(time.perf_counter() - t1, 2)
    facts["logits_rel_rms_worst"] = worst
    facts["logits_rel_rms_quartile_row"] = clean_row(every_row)
    facts["logits_rel_rms_rows_over_quartile_limit"] = int(
        np.sum(np.asarray(every_row) > clean_limit))
    if not worst <= limit:
        problems.append(f"logits relative RMS {worst:.3e} > {limit}")
    if not facts["logits_rel_rms_quartile_row"] <= clean_limit:
        problems.append(
            f"logits relative RMS of the quartile row of {len(every_row)} "
            f"{facts['logits_rel_rms_quartile_row']:.3e} > {clean_limit}")
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
    gauges every ``SAMPLE_EVERY_S``: [(latent bytes, state bytes)], the
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
                samples.append((gauge.value(kind="latent"), state))
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
            f"pool {alloc.num_pages} pages x {model.page_size} rows x "
            f"{model.block.latent.width} lanes for {model.full_layers} "
            f"latent layer(s), {model.full_pages} pages a run; "
            f"{alloc.state_entries} state entries of {model.entry_bytes()} "
            f"bytes for {model.linear_layers} KDA layers")
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
    facts["cache_byte_samples"] = len(cache_bytes or ())
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        # one latent layer's count of the live rows the window's decode
        # steps read; the readers multiply by the latent layers
        "latent_rows": cm["kv_rows"],
        "kv_row_bytes": model.page_row_bytes,     # as STORED
        "full_layers": model.full_layers,
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
