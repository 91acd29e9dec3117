"""Driver ``generate``: ``paddle serve --gen_config``'s server object,
built in this process (the process that holds the chip is the only one
that can trace it), under load from ``perf/harness/loadgen.py`` in a
child process that never imports jax.

Every latency is taken on the client's clock.  Parent and child read
the same monotonic clock (``time.perf_counter`` is CLOCK_MONOTONIC on
Linux), so client timestamps and the parent's spans share a time base.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from perf.harness import loadgen, runtime, stats, trace as tr
from perf.harness.flops import kv_read_bytes


def _generate(address, prompt, max_tokens):
    """One ``/generate`` call through the load generator's own stream
    reader; the ids of its final line."""
    import http.client

    host, port = address.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=600)
    try:
        code, _, stamps, final = loadgen.stream_generate(
            conn, prompt, max_tokens)
    finally:
        conn.close()
    if code != 200 or final is None or "error" in final:
        raise AssertionError(f"/generate: {code} {final}")
    return final["ids"]


def warm_prefill(model, traffic, say):
    """Every (prompt length, token budget) the traffic sends, prefilled
    once: a prompt runs as its length bucket's one jitted program
    (PR 25), so this compiles one program a bucket the traffic uses
    and the further calls find it."""
    t0 = time.perf_counter()
    for T, _ in traffic["prompt_lengths"]:
        for budget, _ in traffic["max_tokens"]:
            prompt = [2] * int(T)
            pages = model.allocator.alloc(
                model.context_pages(prompt, int(budget)))
            try:
                _, _, logits = model.prefill(prompt, pages)
                np.asarray(logits)
            finally:
                model.allocator.free(pages)
    say(f"prefill warmed for {len(traffic['prompt_lengths'])} lengths x "
        f"{len(traffic['max_tokens'])} budgets in "
        f"{time.perf_counter() - t0:.1f}s")


def dense_greedy_fixed(model, prompt, n):
    """``model.dense_greedy``'s no-cache oracle (the full dense forward
    re-run for every token) on a buffer of one fixed length, so that
    the un-jitted forward compiles its ops for one shape and not for
    ``n``: attention is causal, so the padding after the last real
    token cannot reach it.  Returns (tokens, the logits row that chose
    each)."""
    import jax.numpy as jnp

    ids = list(prompt) + [model.bos_id] * n
    out, rows = [], []
    for i in range(n):
        logits, _, _ = model._forward(jnp.asarray(ids, jnp.int32))
        row = np.asarray(logits[len(prompt) + i - 1], np.float32)
        tok = int(np.argmax(row))
        out.append(tok)
        rows.append(row)
        ids[len(prompt) + i] = tok
    return out, rows


def verify(model, address, cfg, wl, seed, say):
    """Greedy streams through /generate against the dense no-cache
    oracle, and one prompt's prefill logits against the plain
    reference."""
    import jax
    import jax.numpy as jnp

    from perf.reference import gpt2_block as ref

    tol = wl["verify"]
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    T, n = int(tol["prompt_len"]), int(tol["tokens"])
    facts, ok = {"greedy_flips_forgiven": 0}, True
    prompts = [rng.randint(2, model.vocab, T).tolist()
               for _ in range(int(tol["prompts"]))]
    for p in prompts:
        got = _generate(address, p, n)
        want, rows = dense_greedy_fixed(model, p, n)
        if got == want:
            continue
        j = next(i for i, (a, b) in enumerate(zip(got, want)) if a != b)
        gap = float(abs(rows[j][want[j]] - rows[j][got[j]]))
        say(f"greedy flip at token {j}: oracle gap between the two "
            f"choices {gap:.3e} (tolerance {tol['top2_gap']})")
        if gap <= tol["top2_gap"]:
            facts["greedy_flips_forgiven"] += 1
        else:
            ok = False
    # prefill logits against the float32 reference
    p = prompts[0]
    pages = model.allocator.alloc(model.context_pages(p, 1))
    try:
        _, _, last = model.prefill(p, pages)
    finally:
        model.allocator.free(pages)
    params = ref.from_decode_model(model.params)
    act = ref.ACTIVATIONS[cfg["generate"]["activation"]]
    run = jax.jit(ref.forward, static_argnums=(2, 3, 4))
    toks = jnp.asarray(p, jnp.int32)

    more, problems = ref.compare(
        jnp.asarray(last, jnp.float32),
        lambda ablate: run(params, toks, model.heads, act, ablate)[-1],
        tol, "prefill_logits_rel_rms")
    facts.update(more)
    say(f"reference check: {facts}")
    for problem in problems:
        say(f"NOT CORRECT: {problem}")
    return ok and not problems, facts


def instrument(engine, spans):
    """Spans round the calls into the decode engine's layers, written
    from the benchmark's side (no file of the program is edited): the
    engine's tick, and inside it the model's prefill and decode.  What
    a tick spends outside those two is sampling and scheduling."""
    model, session = engine.model, engine.session
    for owner, attr, name in ((session, "step", "perf.engine_step"),
                              (model, "prefill", "perf.prefill"),
                              (model, "decode", "perf.decode")):
        inner = getattr(owner, attr)

        def wrapped(*a, _inner=inner, _name=name, **kw):
            with spans.span(_name):
                return _inner(*a, **kw)

        setattr(owner, attr, wrapped)


def client_metrics(out):
    """What the clients saw, from the child's records."""
    t_open, t_close = out["open"], out["close"]
    recs = [r for r in out["records"] if r["t_send"] is not None]
    sent = [r for r in out["records"]
            if r["t_send"] is None or t_open <= r["t_send"] < t_close]
    failed = [r for r in sent if not r["complete"]]
    tokens = sum(stats.in_window(r["stamps"], t_open, t_close)
                 for r in recs)
    first = [r for r in recs if r["stamps"] and r["t_send"] >= t_open]
    ttft = [(r["stamps"][0] - r["t_send"]) * 1e3 for r in first]
    itl = [(b - a) * 1e3 for r in recs
           for a, b in zip(r["stamps"], r["stamps"][1:])
           if t_open <= b < t_close]
    # K/V rows the decode steps of the window had to read: token i of a
    # request (i >= 1; token 0 comes from prefill) attends over the
    # prompt and the i tokens before it
    rows = sum(r["prompt_len"] + i for r in recs
               for i, t in enumerate(r["stamps"])
               if i >= 1 and t_open <= t < t_close)
    # the longest silence of the whole engine: every slot streams a
    # token each step, so a gap far over a prefill is a stall
    every = sorted(t for r in recs for t in r["stamps"]
                   if t_open <= t < t_close)
    silence = max(((b - a, a - t_open) for a, b in zip(every, every[1:])),
                  default=(0.0, 0.0))
    return {"attempted": len(sent), "failed": len(failed),
            "longest_silence_s": silence,
            "failures": [f"{r['status']} {r.get('error')}"
                         for r in failed][:5],
            "tokens": tokens, "window_s": t_close - t_open,
            "ttft_ms": ttft, "sent_s": [r["t_send"] - t_open for r in first],
            "itl_ms": itl, "kv_rows": rows,
            "drain_s": out["end"] - t_close}


CONVOY_WITHIN_S = 0.005


def client_report(cm, out, say):
    """The generate cells' end-to-end numbers from ``client_metrics``'s
    reduction, with the log lines every generate driver prints.  Two
    times to first token may be judged: the mean of the middle half of
    the window's requests (``stats.interquartile_mean``) and the 95th
    percentile of all of them.  The median, ``n`` and the share of the
    requests sent within 5 ms after another client's (a convoy: they
    are seated one after another in one tick, each behind the prefills
    before it) go with them: ``run.py`` prints what BENCHMARK.json does
    not list for the cell under the result line's ``beside``."""
    if not cm["ttft_ms"] or cm["tokens"] <= 0:
        raise SystemExit("perf: no request produced a token in the window")
    ttft = cm["ttft_ms"]
    e2e = {"gen_tokens_per_s": stats.rate(cm["tokens"], out["open"],
                                          out["close"]),
           "gen_ttft_mid_ms": stats.interquartile_mean(ttft),
           "gen_ttft_p95_ms": stats.percentile(ttft, 0.95),
           "gen_ttft_median_ms": stats.median(ttft),
           "gen_ttft_n": len(ttft),
           "gen_convoy_share": stats.follower_share(cm["sent_s"],
                                                    CONVOY_WITHIN_S)}
    say(f"{cm['attempted']} requests, {cm['tokens']} tokens in "
        f"{cm['window_s']:.3f}s; ttft mid {e2e['gen_ttft_mid_ms']} ms "
        f"median {e2e['gen_ttft_median_ms']} ms p95 "
        f"{e2e['gen_ttft_p95_ms']} ms mean {sum(ttft) / len(ttft)} ms "
        f"(n={len(ttft)}); sent within 5 ms after another "
        f"{e2e['gen_convoy_share']:.4f}; "
        f"itl median "
        f"{stats.median(cm['itl_ms']) if cm['itl_ms'] else None} ms "
        f"(n={len(cm['itl_ms'])}); drain after the window "
        f"{cm['drain_s']:.1f}s; longest silence of all streams "
        f"{cm['longest_silence_s'][0]:.3f}s, "
        f"{cm['longest_silence_s'][1]:.1f}s into the window")
    say("requests as sent [send - open s, prompt, budget, ttft ms]: "
        + json.dumps([[round(r["t_send"] - out["open"], 4), r["prompt_len"],
                       r["max_tokens"],
                       round((r["stamps"][0] - r["t_send"]) * 1e3, 2)
                       if r["stamps"] else None]
                      for r in sorted(
                          (r for r in out["records"]
                           if r["t_send"] is not None),
                          key=lambda r: r["t_send"])]))
    return e2e


def run(ctx):
    import jax

    from paddle_tpu import cli
    from paddle_tpu.observability import metrics

    cfg, traffic, wl = ctx["config"], ctx["traffic"], ctx["workload"]
    loadgen.check_deal(traffic)
    say, spans = runtime.say, runtime.Spans(ctx["trace"])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gen_config = os.path.join(here, "configs", cfg["generate"]["gen_config"])
    # the gen_config script reads its sizes from the configuration's
    # file and these two variables (`paddle serve` execs it with no
    # arguments)
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
            f"rows, {model.pages_per_seq} pages a sequence")
        warm_prefill(model, traffic, say)
        t0 = time.perf_counter()
        correct, facts = verify(model, srv.address, cfg, wl, ctx["seed"],
                                say)
        say(f"verify: {time.perf_counter() - t0:.1f}s, correct={correct}")
        compiled_text = {}
        if ctx["trace"]:
            instrument(engine, spans)
            from paddle_tpu.decode import model as dm

            S = int(traffic["gen_slots"])
            compiled_text["decode_step"] = dm._decode_step.lower(
                model.params, model.k_pool, model.v_pool,
                np.zeros((S, model.pages_per_seq), np.int32),
                np.zeros((S,), np.int32), np.zeros((S,), np.int32),
                heads=model.heads, page_size=model.page_size
            ).compile().as_text()

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
            # requests in flight at the close stream to their end
            # outside the window (and outside the trace)
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
    itemsize = np.dtype(model.k_pool.dtype).itemsize
    record = {
        "correct": correct, "attempted": cm["attempted"],
        "failed": cm["failed"], "end_to_end": e2e,
        "window_s": cm["window_s"], "client": cm,
        "registry": {"before": before, "after": after},
        "kv_bytes": kv_read_bytes(cm["kv_rows"], model.heads, model.dh,
                                  model.layers, itemsize),
        "span_seconds": spans.seconds, "facts": facts,
        "planned_bytes": 0, "devices": jax.devices()[:wl["chips"]],
        "trace": None, "compiled_text": compiled_text,
    }
    if trace_dir:
        record["trace"] = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return record
