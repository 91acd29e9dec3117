"""Driver ``generate_conv_hybrid``: ``generate_hybrid`` for a model over
the paged skeleton whose recurrent layers are gated short convs (their
whole state a sequence is a conv tail in a state entry, no state pool),
beside K/V pages of RoPE attention layers, with sigmoid-routed experts
all held, and whose long prompts run in chunks over the state entry
(``paddle_tpu/models/lfm2_moe.py``).

What differs from ``generate_hybrid`` and ``generate_sparse_latent``,
and why it could not be told to either by data: set-up warms the chunk
programs the traffic's and the check's prompts run beside the ladder;
the reference takes this model's geometry and hands back the router's
chosen sets and the conv layers' last gated rows beside the logits;
``correct`` holds

- the LOGITS of three seeded prompts (one inside a bucket, one at the
  top bucket, one through the chunk path) and 16 teacher-forced tokens
  through pages and entries at the serving step's shape: against the
  reference on its OWN router's sets loosely (the bf16 router chooses
  another expert than the float32 one on about an eighth of (layer,
  row), ten routed layers deep, and such a row reads several times a
  clean one), and against the reference GIVEN THE SYSTEM'S SETS (a probe
  over the dense forward: ``routed_sets``): all rows loosely again,
  and tightly the row at the FIRST QUARTILE of the pooled rows
  (``generate_hybrid_latent.CLEAN_ROW``, for its reason: the probe is
  another program than the serving ones, so a third of the rows and in
  one run of fifteen more than half still read behind a flipped choice,
  0.05-0.29 where a clean row reads 0.016-0.021; the median of 51 rows
  read 0.018-0.029 in fourteen runs and 0.048 in the fifteenth); the
  share of (layer, row) on which the two routers differ is held too;
- the FIRST ROWS OF A CHUNK: prompts of ``top bucket + 1 .. + 4`` rows,
  each the bucket and then a chunk of 1 to 4 real rows, whose last
  row's logits are the only ones a wrong carried tail moves directly;
- a sequence's ENTRY: each conv layer's tail as prefill (bucket and
  chunk) and 16 steps leave it against the reference's last two gated
  rows, the first conv layer's held (both routes see the same inputs
  there) and every layer's written down;
- what the tails and the K rows ARE: their distance from their own
  rounding to float8 (rows that carry bfloat16's mantissa stand ~0.025
  away, float8 rows stand on it), which is how a float8 tail or float8
  K/V comes out as not correct where the logits cannot tell;
- each ablation by its stated factor on the limit that sees it (the
  router's sets for ``bias_off``, the chunk's first rows for
  ``tail_zero_at_chunk``, the quartile row given the sets for the
  others), and each precision below over at least one limit.

The load, the window, the record's keys and so the readers are
``generate_hybrid``'s; ``compiled_text`` also holds the chunk programs
(``prefill_state_chunk_<rows>_over_<done>``).
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
from perf.drivers.generate_hybrid import sampled_window
from perf.drivers.generate_hybrid_latent import CLEAN_ROW
from perf.drivers.generate_paged import _count, buckets_of
from perf.harness import loadgen, modules, runtime
from perf.harness import trace as tr

WHOLE = 128        # rows the probe pads a sequence to a multiple of


def chunks_of(model, lengths):
    """{(rows, done): the shortest of the prompts of ``lengths`` rows
    that runs that chunk program, cut behind the chunk}, in order of
    first use."""
    first = {}
    for n in lengths:
        if n > model.prefill_cap:
            for done, C, real in model.prompt_chunks(int(n)):
                first.setdefault((C, done), done + real)
    return first


def warm(model, say, lengths):
    """Every bucket's prefill program, and every chunk program a prompt
    of ``lengths`` runs, once."""
    t0 = time.perf_counter()
    ladder = [b for b in buckets_of(model) if b <= model.prefill_cap]
    first = chunks_of(model, lengths)
    for T in ladder + sorted(set(first.values())):
        pages = model.allocator.alloc(model.context_pages([2] * T, 0))
        try:
            model.prefill([2] * T, pages)
        finally:
            model.allocator.free(pages)
    chunks = list(first)
    say(f"prefill warmed for buckets {ladder} and chunks (rows, done) "
        f"{chunks} in {time.perf_counter() - t0:.1f}s")
    return ladder, chunks


def routed_sets(model, tokens):
    """(routed layers, T, E) bool: the experts the SYSTEM chooses for
    each row of one sequence, by its own block functions over the dense
    forward (a probe from the benchmark's side; the program hands out
    counts, not sets)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import moe

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
                _, idx = moe.route(lb.router_rows(lp, x), lp["wr"],
                                   block.top_k, lb.scores(lp))
                sets.append(jnp.any(
                    idx[..., None] == jnp.arange(block.experts), axis=1))
            x, _ = lb.mlp(lp, x, None)
        return jnp.stack(sets)

    return np.asarray(run(model.params, jnp.asarray(ids, jnp.int32)))[:, :T]


def float8_gap(rows):
    """Relative RMS between ``rows`` and their own rounding to
    float8_e4m3fn."""
    import jax.numpy as jnp

    from perf.reference.lfm2_moe_block import rel_rms

    rows = jnp.asarray(rows, jnp.float32)
    return rel_rms(rows.astype(jnp.float8_e4m3fn).astype(jnp.float32), rows)


def entry_tails(model, pages):
    """The sequence's conv tails, (conv layers, taps - 1, d) float32."""
    entry = model.allocator.entry_of(pages)
    return np.asarray(model.conv_pool[:, entry], np.float32).reshape(
        model.linear_layers, model.conv_taps - 1, model.d)


def first_k_rows(model, pages, n):
    """The first attention layer's K rows of the sequence's first page,
    as stored: (n, K/V heads x head size) float32."""
    page = model.allocator.pages_of(pages)[0]
    return np.asarray(model.k_pool[0, page, :n], np.float32).reshape(n, -1)


def through_the_cache(model, prompt, tokens, slots):
    """Prefill ``prompt`` (its bucket, or the top bucket and chunks),
    then ``tokens`` teacher-forced, one decode step each, at the serving
    step's shape -> (the len(tokens) + 1 logits rows, the entry's tails
    after the last token, the first page's K rows)."""
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
        tails = entry_tails(model, pages)
        k_rows = first_k_rows(model, pages, min(ctx, model.page_size))
    finally:
        model.allocator.free(pages)
    return np.stack(rows), tails, k_rows


def last_logits(model, prompt):
    """The logits of the prompt's last row, through its prefill."""
    pages = model.allocator.alloc(model.context_pages(prompt, 0))
    try:
        return np.asarray(model.prefill(prompt, pages)[2], np.float32)
    finally:
        model.allocator.free(pages)


def verify(model, address, wl, traffic, seed, say):
    """The module's docstring says what is held; every reading is
    written down in the facts."""
    import jax.numpy as jnp

    tol = wl["verify"]
    ref = importlib.import_module(f"perf.reference.{tol['reference']}")
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    n, slots = int(tol["tokens"]), int(traffic["gen_slots"])
    limit = float(tol["logits_rel_rms"])
    given_limit = float(tol["given_sets_rel_rms"])
    row_limit = float(tol["given_sets_rel_rms_quartile_row"])
    sets_limit = float(tol["top_k_set_differs_share"])
    chunk_limit = float(tol["chunk_first_rows_rel_rms"])
    entry_limit = float(tol["entry_rel_rms"])
    gap_limit = float(tol["float8_gap"])
    block, cap = model.block, model.prefill_cap
    facts, problems = {}, []

    def reference(ids, rows, ablate=None, given=None, chunk_at=None):
        return ref.forward(
            model.params, jnp.asarray(ids, jnp.int32),
            layer_types=block.layer_types, num_heads=model.heads,
            head_dim=block.head_dim, top_k=block.top_k, scale=block.scale,
            route_eps=block.route_eps, eps=block.eps, theta=block.theta,
            ablate=ablate, rows=rows, tails=True, chunk_at=chunk_at,
            given=given)

    def by_row(got, want):
        return [ref.rel_rms(g, w) for g, w in zip(got, want)]

    def clean(per_row):
        """The row at the first quartile: one the flipped choices of
        half the rows and more leave as it is."""
        return float(np.quantile(per_row, CLEAN_ROW))

    def differs(mine, theirs):
        return float(np.any(mine != np.asarray(theirs), axis=-1).mean())

    on_sets = set(tol.get("judged_on_router_sets", ()))
    on_chunk = set(tol.get("judged_on_chunk_rows", ()))
    worst = worst_given = worst_sets = 0.0
    pooled = []
    for i, T in enumerate(tol["prompt_lens"]):
        prompt = rng.randint(2, model.vocab, int(T)).tolist()
        tokens = rng.randint(2, model.vocab, n).tolist()
        ids, rows = prompt + tokens, list(range(T - 1, T + n))
        got, tails, k_rows = through_the_cache(model, prompt, tokens, slots)
        mine = routed_sets(model, ids)
        free, masks, _ = reference(ids, rows)
        want, _, want_tails = reference(ids, rows, given=mine)
        tag = f"T{T}_{i}"
        facts[f"logits_rel_rms_{tag}"] = ref.rel_rms(got, free)
        facts[f"logits_rel_rms_{tag}_worst_row"] = max(by_row(got, free))
        worst = max(worst, facts[f"logits_rel_rms_{tag}"])
        share = differs(mine, masks)
        facts[f"top_k_set_differs_share_{tag}"] = share
        worst_sets = max(worst_sets, share)
        per_row = by_row(got, want)
        pooled += per_row
        facts[f"given_sets_rel_rms_{tag}"] = ref.rel_rms(got, want)
        facts[f"given_sets_rel_rms_{tag}_quartile_row"] = clean(per_row)
        facts[f"given_sets_rel_rms_{tag}_rows"] = [
            round(x, 4) for x in per_row]
        worst_given = max(worst_given, facts[f"given_sets_rel_rms_{tag}"])
        by_layer = by_row(tails, want_tails)
        facts[f"entry_rel_rms_first_layer_{tag}"] = by_layer[0]
        facts[f"entry_rel_rms_by_layer_{tag}"] = [
            round(x, 5) for x in by_layer]
        if not by_layer[0] <= entry_limit:
            problems.append(
                f"the first conv layer's tail in the entry after {T} + {n} "
                f"rows: relative RMS {by_layer[0]:.3e} > {entry_limit}")
        gaps = {"tail": float8_gap(tails), "k_rows": float8_gap(k_rows)}
        facts[f"float8_gap_{tag}"] = {k: round(v, 5)
                                      for k, v in gaps.items()}
        for what, gap in gaps.items():
            if not gap >= gap_limit:
                problems.append(
                    f"the {what} of the {T}-row prompt stand {gap:.3e} from "
                    f"their own float8 rounding (< {gap_limit}): they do "
                    "not carry bfloat16's mantissa")
        if i != int(tol.get("ablation_prompt", 0)):
            continue
        variants = [(a, f"without_{a}", float(tol["ablation_factor"][a]))
                    for a in tol["ablations"] if a not in on_chunk]
        variants += [(p, f"reference_in_{p}", 1.0)
                     for p in tol["precisions_below"]]
        for ablate, name, factor in variants:
            wrong, wrong_masks, wrong_tails = reference(
                ids, rows, ablate, given=None if ablate in on_sets else mine)
            at_row = clean(by_row(got, wrong))
            facts[f"given_sets_rel_rms_{name}"] = ref.rel_rms(got, wrong)
            facts[f"given_sets_rel_rms_{name}_quartile_row"] = at_row
            if ablate in on_sets:
                share = differs(mine, wrong_masks)
                facts[f"top_k_set_differs_share_{name}"] = share
                if share <= factor * sets_limit:
                    problems.append(
                        f"the sets' limit would not catch {name} by "
                        f"{factor}x: {share:.3e} of {sets_limit}")
                continue
            over = [at_row > factor * row_limit]
            if ablate in tol["precisions_below"]:
                # a precision below need only fail ONE limit: the
                # logits', the entry's, or what the stored rows are
                tail_rms = ref.rel_rms(tails[0], wrong_tails[0])
                facts[f"entry_rel_rms_first_layer_{name}"] = tail_rms
                # rows kept in float8 stand on their own rounding: the
                # reference's tails as it made them, the system's K rows
                # rounded as float8 pages would hold them
                stored = {"tail_fp8": wrong_tails,
                          "kv_fp8": jnp.asarray(k_rows).astype(
                              jnp.float8_e4m3fn)}.get(ablate)
                over += [tail_rms > entry_limit,
                         stored is not None
                         and float8_gap(stored) < gap_limit]
            if not any(over):
                problems.append(
                    f"no limit would catch {name} by {factor}x: quartile "
                    f"row {at_row:.3e} of {row_limit}")
    at_row = clean(pooled)
    facts["logits_rel_rms_worst"] = worst
    facts["given_sets_rel_rms_worst"] = worst_given
    facts["given_sets_rel_rms_quartile_row_pooled"] = at_row
    facts["given_sets_rel_rms_median_row_pooled"] = float(np.median(pooled))
    facts["rows_pooled"] = len(pooled)
    facts["top_k_set_differs_share"] = worst_sets
    for what, read, lim in (
            ("logits relative RMS", worst, limit),
            ("logits relative RMS given the sets", worst_given, given_limit),
            ("logits relative RMS given the sets at the first quartile of "
             f"{len(pooled)} rows", at_row, row_limit),
            ("the router's sets differ from the reference's on a share of "
             "(layer, row)", worst_sets, sets_limit)):
        if not read <= lim:
            problems.append(f"{what} {read:.3e} > {lim}")

    # the first rows of a chunk: the top bucket, then 1..4 real rows
    first = int(tol["chunk_first_rows"])
    ids = rng.randint(2, model.vocab, cap + first).tolist()
    got = np.stack([last_logits(model, ids[:cap + j + 1])
                    for j in range(first)])
    rows = list(range(cap, cap + first))
    mine = routed_sets(model, ids)
    want = reference(ids, rows, given=mine)[0]
    rms = ref.rel_rms(got, want)
    facts["chunk_first_rows_rel_rms"] = rms
    facts["chunk_first_rows_rel_rms_by_row"] = [
        round(x, 5) for x in by_row(got, want)]
    if not rms <= chunk_limit:
        problems.append(f"the chunk's first {first} rows: logits relative "
                        f"RMS given the sets {rms:.3e} > {chunk_limit}")
    for ablate in on_chunk:
        factor = float(tol["ablation_factor"][ablate])
        wrong = reference(ids, rows, ablate, given=mine, chunk_at=cap)[0]
        rms = ref.rel_rms(got, wrong)
        facts[f"chunk_first_rows_rel_rms_without_{ablate}"] = rms
        if rms <= factor * chunk_limit:
            problems.append(
                f"the chunk rows' limit would not catch {ablate} by "
                f"{factor}x: {rms:.3e} of {chunk_limit}")

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
    from paddle_tpu.decode import state_entry as se

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
    for C, done in chunks:
        chunk = se._prefill_state_chunk.lower(
            model.params, *cache[:2],
            np.zeros((model.pages_per_seq,), np.int32),
            np.zeros((C,), np.int32), np.int32(1), heads=model.heads,
            page_size=model.page_size, block=model.block, done=done,
            extra=cache[2:]).compile()
        texts[f"prefill_state_chunk_{C}_over_{done}"] = chunk.as_text()
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
        alloc = model.allocator
        say(f"server up on {srv.address} in {time.perf_counter() - t0:.1f}s; "
            f"pool {alloc.num_pages} pages x {model.page_size} rows of "
            f"{model.stored_heads} stored heads for {model.full_layers} "
            f"attention layers, {model.full_pages} pages a run; "
            f"{alloc.state_entries} state entries of {model.entry_bytes()} "
            f"bytes for {model.linear_layers} conv layers; top bucket "
            f"{model.prefill_cap}, chunks of {model.chunk_rows}")
        tol = wl["verify"]
        lengths = ([p for p, _ in traffic["prompt_lengths"]]
                   + list(tol["prompt_lens"])
                   + [model.prefill_cap + j + 1
                      for j in range(int(tol["chunk_first_rows"]))])
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
    facts["cache_byte_samples"] = len(cache_bytes or ())
    # what the paged kernel of a decode step reads: the attention
    # layers' rows, at the heads a page is stored at (bytes as read)
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
