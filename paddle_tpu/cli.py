#!/usr/bin/env python3
"""The `paddle` command (reference: paddle/scripts/submit_local.sh.in —
the shell wrapper exposing train / version / merge_model; plus the
TPU-era additions: pserver / master / coord service launchers).

Subcommands:
  paddle train --config=conf.py [--num_passes=N] [--save_dir=D] [--config_args=k=v,...]
  paddle version
  paddle merge_model --model_dir=DIR --out=OUT_DIR [--config_args=...]
      (reference `paddle merge_model` fused config+params into one
       binary for the C API; here: re-parse the v1 config, load the
       pass params, export a save_inference_model directory that
       capi/paddle_tpu_capi.h consumes)
  paddle compile --model_dir=DIR --out=DIR [--max_batch=N]
                 [--buckets=1,2,4] [--no-optimize] [--gen_config=SCRIPT]
                 [--smoke]
      (AOT serving artifacts — paddle_tpu/aot: run the serving warmup
       paths under export capture and serialize every bucket-ladder /
       decode-step executable into a versioned artifact directory that
       `paddle serve --artifacts=DIR` boots from without JIT compiling;
       --smoke is the self-contained export->boot->parity CI gate)
  paddle serve [--model_dir=DIR] [--port=N] [--replicas=N] [--max_batch=N]
               [--batch_timeout_ms=MS] [--warmup] [--artifacts=DIR]
               [--request_timeout=SECONDS] [--max_inflight=N]
               [--gen_config=SCRIPT] [--gen_pages=N] [--gen_page_size=N]
               [--gen_pages_per_seq=N] [--gen_slots=N] [--gen_queue=N]
               [--gen_max_tokens=N] [--beam_max=K] [--prefix_cache]
               [--prefix_cache_pages=N] [--spec_draft=ngram] [--spec_k=N]
      (HTTP JSON inference over a save_inference_model export —
       paddle_tpu/serving: bucketed request coalescing into power-of-two
       batch shapes + a pool of executor replicas; --warmup pre-compiles
       the bucket ladder; --request_timeout returns 504 on expiry,
       --max_inflight sheds load with 503 instead of piling up threads.
       --gen_config mounts POST /generate: token streaming over the
       paged-KV continuous-batching decode engine, paddle_tpu/decode —
       the script defines make_generator() -> (beam_gen, parameters)
       or make_decode_model() -> paged LM, see demos/seq2seq/
       gen_config.py; --beam_max enables beam search over CoW sibling
       slots, --prefix_cache shares prompt-prefix KV pages across
       requests, --spec_draft/--spec_k enable speculative decoding)
  paddle elastic --coord=HOST:PORT --checkpoint-dir=DIR [--job=NAME]
                 [--tasks=N] [--passes=P] [--worker-id=ID] ...
      (preemption-safe demo training worker —
       paddle_tpu/distributed/elastic.py; kill it mid-epoch and a
       relaunched worker resumes from the last committed checkpoint)
  paddle lint <program.json|config.py> [--level=...] [--strict] [--json]
      (static program verification — paddle_tpu/analysis; exits nonzero
       on error diagnostics.  --audit-registry checks op-metadata
       coverage against the checked-in baseline)
  paddle stats [--json] [--run=script.py] [--file=telemetry.json]
               [--url=http://host:port] [--trace=out.json]
      (snapshot the telemetry registry — paddle_tpu/observability — as
       a human table or JSON; --run execs a fluid script first so its
       Executor.run counters show, --url scrapes a live `paddle serve`
       /stats endpoint, --file renders a saved snapshot or artifact,
       --trace also exports the host event ring as Chrome-trace JSON)
  paddle pserver [--port=P] [--checkpoint=PATH] [--checkpoint_sec=S]
  paddle master [--port=P] [--lease_sec=S] [--failure_max=N]
  paddle coord  [--port=P]
"""

import os
import sys


def _kv_args(argv):
    out = {}
    rest = []
    for a in argv:
        if a.startswith("--") and "=" in a:
            k, v = a[2:].split("=", 1)
            out[k] = v
        else:
            rest.append(a)
    return out, rest


def cmd_version(_):
    import jax

    import paddle_tpu

    print(f"paddle_tpu {paddle_tpu.__version__}")
    print(f"  jax {jax.__version__}; backend "
          f"{jax.default_backend()} x{jax.device_count()}")
    return 0


def _cwd_importable():
    # v1 config files import their own package tree relative to the
    # invocation directory (reference: `paddle train` ran from the
    # workdir with PYTHONPATH=.)
    if os.getcwd() not in sys.path:
        sys.path.insert(0, os.getcwd())


def cmd_train(argv):
    _cwd_importable()
    from paddle_tpu.trainer.trainer import main as trainer_main

    return trainer_main(argv)


def cmd_merge_model(argv):
    _cwd_importable()
    args, _ = _kv_args(argv)
    model_dir = args.get("model_dir")
    out = args.get("out")
    if not model_dir or not out:
        print("usage: paddle merge_model --model_dir=DIR --out=OUT_DIR",
              file=sys.stderr)
        return 2
    config = args.get("config") or os.path.join(model_dir, "trainer_config.py")
    from paddle_tpu.trainer.trainer import Trainer
    from paddle_tpu.trainer.config_parser import parse_config
    import paddle_tpu as fluid

    conf = parse_config(config, args.get("config_args", ""))
    t = Trainer(conf)
    t.load_parameters(model_dir)
    t.export_inference_model(out)
    print(f"merged model written to {out}")
    return 0


def _serve(make_server, argv, label):
    import signal
    import threading

    args, _ = _kv_args(argv)
    srv = make_server(args)
    print(f"{label} listening on {srv.address}", flush=True)
    done = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: done.set())
    signal.signal(signal.SIGINT, lambda *a: done.set())
    done.wait()
    srv.stop()
    return 0


def _load_generator(args, flags=()):
    """Build a paged-KV GenerationEngine from a --gen_config script.

    The script is exec'd and must define ``make_generator()`` returning
    ``(beam_gen, parameters)`` — a v1 ``beam_search`` spec plus trained
    parameters (see demos/seq2seq/gen_config.py) — or
    ``make_decode_model()`` returning a paged decoder-LM model (the
    path that supports prefix caching and speculative decoding).
    Page-pool geometry comes from the --gen_* flags; ``--beam_max=K``
    enables POST /generate ``{"beam": k}``; ``--prefix_cache`` /
    ``--spec_draft=ngram`` (or a ``make_draft_model()`` in the config)
    turn on prompt-prefix page reuse and speculative decoding for
    models that support them."""
    _cwd_importable()
    from paddle_tpu.decode import GenerationEngine

    path = args["gen_config"]
    glb = {"__file__": path, "__name__": "__paddle_serve_gen__"}
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), glb)
    beam_max = int(args.get("beam_max", 0))
    spec_draft = None
    if "make_draft_model" in glb:
        spec_draft = glb["make_draft_model"]()
    elif args.get("spec_draft") == "ngram":
        from paddle_tpu.decode.spec import NgramDraft

        spec_draft = NgramDraft()
    if "make_decode_model" in glb:
        return GenerationEngine(
            glb["make_decode_model"](),
            max_slots=int(args.get("gen_slots", 8)),
            max_waiting=int(args.get("gen_queue", 64)),
            max_new_tokens=int(args.get("gen_max_tokens", 32)),
            prefix_cache="--prefix_cache" in flags,
            prefix_cache_pages=(int(args["prefix_cache_pages"])
                                if args.get("prefix_cache_pages") else None),
            spec_draft=spec_draft,
            spec_k=int(args.get("spec_k", 4)),
            beam_max=beam_max)
    if "make_generator" not in glb:
        raise RuntimeError(
            f"{path} defines no make_generator() -> (beam_gen, parameters) "
            "and no make_decode_model() -> paged decoder model")
    beam_gen, parameters = glb["make_generator"]()
    return GenerationEngine.for_seq2seq(
        beam_gen, parameters,
        num_pages=int(args.get("gen_pages", 64)),
        page_size=int(args.get("gen_page_size", 8)),
        pages_per_seq=int(args.get("gen_pages_per_seq", 2)),
        max_slots=int(args.get("gen_slots", 8)),
        max_waiting=int(args.get("gen_queue", 64)),
        max_new_tokens=(int(args["gen_max_tokens"])
                        if args.get("gen_max_tokens") else None),
        beam_max=beam_max)


def cmd_serve(argv):
    """paddle serve [--model_dir=DIR] [--port=N] [--replicas=N]
    [--max_batch=N] [--batch_timeout_ms=MS] [--warmup]
    [--artifacts=DIR] [--request_timeout=S] [--max_inflight=N]
    [--tenants=NAME:RATE[:BURST[:WEIGHT]],...] [--tenant_config=FILE]
    [--max_attempts=N] [--replica_heartbeat_ms=MS]
    [--dispatch_timeout=S] [--chaos=KIND[@N[:rIDX]]]
    [--gen_config=SCRIPT --gen_pages=N --gen_page_size=N
     --gen_pages_per_seq=N --gen_slots=N --gen_queue=N
     --gen_max_tokens=N --beam_max=K --prefix_cache
     --prefix_cache_pages=N --spec_draft=ngram --spec_k=N]
    — HTTP inference over a save_inference_model
    export (paddle_tpu/serving): concurrent requests coalesce into
    power-of-two batch buckets dispatched across a pool of executor
    replicas, with graceful-degradation bounds (504 on deadline expiry,
    503 on overload).  Replicas are supervised and self-healing:
    crashed or hung dispatches requeue their batch (up to
    --max_attempts per request) onto a respawned replica.
    --artifacts=DIR boots replicas from a `paddle compile` export:
    warmup deserializes the bucket ladder instead of JIT-compiling it
    (manifest mismatches fall back to JIT loudly — see
    aot_load_total{result} on /metrics).  --tenants
    gives each named tenant a token-bucket admission quota and a
    fair-queue weight ('*' entry templates unknown tenants;
    --tenant_config reads the same spec, one entry per line, from a
    file); --chaos arms a dev-only fault injector (die|raise|hang on
    the Nth dispatch).  With --gen_config, also mounts POST /generate —
    token streaming over the paged-KV continuous-batching decode
    engine (paddle_tpu/decode); --beam_max enables {"beam": k} beam
    search, --prefix_cache shares prompt-prefix KV pages across
    requests, --spec_draft/--spec_k turn on speculative decoding."""
    args, rest = _kv_args(argv)
    if not args.get("model_dir") and not args.get("gen_config"):
        print("usage: paddle serve [--model_dir=DIR] [--port=N] "
              "[--replicas=N] [--max_batch=N] [--batch_timeout_ms=MS] "
              "[--warmup] [--request_timeout=SECONDS] [--max_inflight=N] "
              "[--gen_config=SCRIPT ...] (need --model_dir and/or "
              "--gen_config)", file=sys.stderr)
        return 2
    return _serve(lambda a: build_inference_server(a, rest),
                  argv, "inference server")


def build_inference_server(a, flags=()):
    """The InferenceServer `paddle serve` runs, built from its parsed
    ``--key=value`` arguments ``a`` and bare ``flags`` — also the
    in-process entry chip_smoke.py drives."""
    from paddle_tpu.serving import InferenceServer

    def _tenant_spec(a):
        if a.get("tenant_config"):
            with open(a["tenant_config"]) as fh:
                entries = [ln.strip() for ln in fh
                           if ln.strip() and not ln.startswith("#")]
            return ",".join(entries)
        return a.get("tenants")

    return InferenceServer(
        a.get("model_dir"), port=int(a.get("port", 0)),
        request_timeout=(float(a["request_timeout"])
                         if a.get("request_timeout") else None),
        max_inflight=(int(a["max_inflight"])
                      if a.get("max_inflight") else None),
        replicas=int(a.get("replicas", 1)),
        max_batch=int(a.get("max_batch", 8)),
        batch_timeout_ms=float(a.get("batch_timeout_ms", 0.0)),
        warmup="--warmup" in flags,
        tenants=_tenant_spec(a),
        max_attempts=int(a.get("max_attempts", 3)),
        replica_heartbeat_ms=float(a.get("replica_heartbeat_ms",
                                         1000.0)),
        dispatch_timeout=(float(a["dispatch_timeout"])
                          if a.get("dispatch_timeout") else None),
        chaos=a.get("chaos"),
        artifacts=a.get("artifacts"),
        generator=(_load_generator(a, flags) if a.get("gen_config")
                   else None))


def cmd_compile(argv):
    """paddle compile --model_dir=DIR --out=DIR [--max_batch=N]
    [--buckets=1,2,4] [--no-optimize] [--gen_config=SCRIPT ...]
    [--smoke] — export AOT serving artifacts (paddle_tpu/aot): the
    bucket-ladder (and decode-step) executables a `paddle serve
    --warmup` boot would JIT-compile, serialized under a versioned
    manifest so `paddle serve --artifacts=DIR` boots without
    compiling.  --smoke runs the self-contained export->boot->parity
    gate CI uses."""
    from paddle_tpu.aot.compile_cli import main as compile_main

    return compile_main(argv)


def cmd_elastic(argv):
    """paddle elastic ... — preemption-safe demo training worker
    (paddle_tpu/distributed/elastic.py)."""
    from paddle_tpu.distributed.elastic import main as elastic_main

    return elastic_main(argv)


def cmd_pserver(argv):
    from paddle_tpu.distributed import ParameterServer

    return _serve(
        lambda a: ParameterServer(port=int(a.get("port", 0)),
                                  checkpoint_path=a.get("checkpoint", ""),
                                  checkpoint_sec=int(a.get("checkpoint_sec", 0))),
        argv, "pserver")


def cmd_master(argv):
    from paddle_tpu.distributed import MasterServer

    return _serve(
        lambda a: MasterServer(port=int(a.get("port", 0)),
                               lease_sec=int(a.get("lease_sec", 10)),
                               failure_max=int(a.get("failure_max", 3))),
        argv, "master")


def cmd_coord(argv):
    from paddle_tpu.distributed import CoordServer

    return _serve(lambda a: CoordServer(port=int(a.get("port", 0))),
                  argv, "coord")


def _lint_load(target, config_args=""):
    """Resolve a lint target to (program, feed_names|None, fetch_names|None).

    ``*.json``: a save_inference_model __model__.json (program + feed/
    fetch lists) or a bare Program.to_dict dump.  ``*.py``: a v1 trainer
    config (parsed and traced to a Program via Topology) or a fluid-style
    script that builds the default main program when exec'd.
    """
    import json

    from paddle_tpu import framework

    if target.endswith(".json"):
        with open(target) as f:
            meta = json.load(f)
        if "program" in meta:
            feeds = meta.get("feed_names")
            return (framework.Program.from_dict(meta["program"]),
                    set(feeds) if feeds is not None else None,
                    meta.get("fetch_names") or None)
        return framework.Program.from_dict(meta), None, None

    _cwd_importable()
    v1_err = None
    try:
        from paddle_tpu.trainer.config_parser import parse_config
        from paddle_tpu.v2.topology import Topology

        conf = parse_config(target, config_args)
        if conf.cost is not None:
            topo = Topology(conf.cost, extra_layers=conf.evaluators)
            fetches = [v.name for v in topo.output_vars]
            return topo.main_program, set(topo.feed_names()), fetches
    except Exception as e:
        v1_err = e  # remember; maybe it's a fluid script instead
    main, startup = framework.Program(), framework.Program()
    try:
        with framework.program_guard(main, startup):
            glb = {"__file__": target, "__name__": "__paddle_lint__"}
            with open(target) as f:
                exec(compile(f.read(), target, "exec"), glb)
    except Exception as e:
        if v1_err is not None:
            raise RuntimeError(
                f"not a v1 config ({type(v1_err).__name__}: {v1_err}) "
                f"nor a fluid script ({type(e).__name__}: {e})") from e
        raise
    if v1_err is not None and not any(b.ops for b in main.blocks):
        # exec "succeeded" but built nothing: the v1 parse error is the
        # real diagnostic, not a silent clean
        raise RuntimeError(
            f"v1 config parse failed: {type(v1_err).__name__}: {v1_err}")
    return main, None, None


def cmd_lint(argv):
    """paddle lint <program.json|config.py> [--level=warning] [--strict]
    [--json] [--fetch=a,b] [--feed=a,b] [--optimize]
    | paddle lint --audit-registry

    Run the static verifier (paddle_tpu/analysis) and print structured
    diagnostics.  Exit 1 when errors fire (or warnings, with --strict).

    ``--optimize`` additionally dry-runs the whole-program optimizer
    (analysis/optimize.py) over each target and prints its report —
    ops removed per pass, constant folds, CSE hits, and the
    donation-safety mask — then re-verifies the rewritten program
    (exit 1 if the optimizer output has any error, which the pipeline's
    internal verify-or-revert gate should make impossible).
    """
    import json as json_mod

    from paddle_tpu import analysis

    args, rest = _kv_args(argv)
    flags = {a for a in rest if a.startswith("--")}
    targets = [a for a in rest if not a.startswith("--")]
    as_json = "--json" in flags
    strict = "--strict" in flags
    do_optimize = "--optimize" in flags

    audit = "--audit-registry" in flags or bool(args.get("audit-registry"))
    diags = []
    if audit:
        diags.extend(analysis.audit_registry())
    if not targets and not audit:
        print("usage: paddle lint <program.json|config.py> "
              "[--level=error|warning|all] [--strict] [--json] "
              "[--fetch=a,b] [--feed=a,b] [--audit-registry]",
              file=sys.stderr)
        return 2

    level = args.get("level", "warning")
    if level not in ("error", "warning", "info", "all"):
        print(f"bad --level={level}; one of error|warning|info|all",
              file=sys.stderr)
        return 2
    unusable = False  # a bad target never downgrades to "clean"
    opt_reports = []  # (target, OptReport) pairs under --optimize
    for target in targets:
        if not os.path.exists(target):
            print(f"lint target not found: {target}", file=sys.stderr)
            unusable = True
            continue
        try:
            program, feeds, fetches = _lint_load(target,
                                                 args.get("config_args", ""))
        except Exception as e:
            print(f"cannot load lint target {target}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            unusable = True
            continue
        if not any(b.ops for b in program.blocks):
            # a target that builds zero ops was not actually analyzed —
            # reporting "clean" here would be a false negative
            print(f"lint target {target} built an empty program "
                  "(no ops); nothing to analyze", file=sys.stderr)
            unusable = True
            continue
        if args.get("feed"):
            feeds = set(args["feed"].split(","))
        if args.get("fetch"):
            fetches = args["fetch"].split(",")
        diags.extend(analysis.verify_program(
            program, feed_names=feeds, fetch_names=fetches, level=level))
        if do_optimize:
            optimized, report = analysis.optimize_program(
                program, feed_names=feeds, fetch_names=fetches)
            opt_reports.append((target, report))
            # the pipeline reverts any pass whose output fails error-
            # tier verification, so errors here mean a gate bug — fail
            # loudly rather than report a broken rewrite as clean
            diags.extend(analysis.verify_program(
                optimized, feed_names=feeds, fetch_names=fetches,
                level="error"))

    if as_json:
        doc = [d.to_dict() for d in diags]
        if do_optimize:
            doc = {"diagnostics": doc,
                   "optimize": {t: r.to_dict() for t, r in opt_reports}}
        print(json_mod.dumps(doc, indent=1))
    elif diags or not unusable:  # no "clean" claim if nothing was analyzed
        for target, report in opt_reports:
            print(f"== optimize: {target}")
            print(report.format())
        print(analysis.format_report(diags))
    if unusable:
        return 2
    bad = [d for d in diags if d.severity == analysis.Severity.ERROR
           or (strict and d.severity == analysis.Severity.WARNING)]
    return 1 if bad else 0


def cmd_stats(argv):
    """paddle stats [--json] [--run=script.py] [--file=artifact.json]
    [--url=http://host:port] [--trace=out.json [--seconds=N]]

    Dump the observability registry (paddle_tpu/observability): every
    counter/gauge/histogram the executor, serving, and trainer paths
    recorded, as a human table or JSON.  Sources, in precedence order:
    a live server's /stats endpoint (--url), a saved snapshot or a
    telemetry artifact (--file: a document with the registry under
    "metrics"), or this process's registry (optionally after exec'ing a
    fluid script via --run so its Executor.run calls are measured).

    --trace writes the program's spans as Chrome-trace JSON: those of
    the --run script (the span ring is on while it runs), the events a
    --file artifact embeds, or — with --url — what the server records
    over the next --seconds (default 5) through its GET /trace.
    """
    import json as json_mod

    from paddle_tpu import observability as obs

    args, rest = _kv_args(argv)
    as_json = "--json" in rest
    trace = None          # a Chrome-trace document fetched from elsewhere
    if args.get("url"):
        import urllib.request

        base = args["url"].rstrip("/")
        if args.get("trace"):
            seconds = float(args.get("seconds", 5))
            with urllib.request.urlopen(
                    f"{base}/trace?seconds={seconds}",
                    timeout=seconds + 30) as r:
                trace = json_mod.loads(r.read())
        with urllib.request.urlopen(base + "/stats", timeout=30) as r:
            snap = json_mod.loads(r.read())
    elif args.get("file"):
        with open(args["file"]) as f:
            data = json_mod.load(f)
        # a telemetry artifact nests the registry under "metrics";
        # a raw snapshot dump IS the registry
        snap = data.get("metrics", data) or {}
    else:
        if args.get("run"):
            import contextlib

            _cwd_importable()
            path = args["run"]
            glb = {"__file__": path, "__name__": "__paddle_stats__"}
            with open(path) as f:
                code = compile(f.read(), path, "exec")
            with (obs.recording() if args.get("trace")
                  else contextlib.nullcontext()):
                exec(code, glb)
        snap = obs.snapshot()
    if as_json:
        print(json_mod.dumps(snap, indent=1, sort_keys=True))
    else:
        table = obs.format_snapshot(snap)
        print(table if table else
              "telemetry registry is empty (no metrics recorded)")
    if args.get("trace"):
        if args.get("file") and not args.get("url"):
            # an artifact may embed its run's Chrome trace ("events"):
            # export that, not this CLI process's (empty) event ring.
            # Nothing in the tree writes one since PR 57 (ROADMAP D5)
            trace = data.get("events")
            if not trace:
                print(f"--trace: {args['file']} carries no embedded "
                      "host events", file=sys.stderr)
                return 2
        if trace is not None:
            with open(args["trace"], "w") as f:
                json_mod.dump(trace, f)
        else:
            obs.export_chrome_trace(args["trace"])
        print(f"host events written to {args['trace']} "
              "(chrome://tracing)", file=sys.stderr)
    return 0


COMMANDS = {
    "train": cmd_train,
    "version": cmd_version,
    "merge_model": cmd_merge_model,
    "compile": cmd_compile,
    "serve": cmd_serve,
    "lint": cmd_lint,
    "stats": cmd_stats,
    "pserver": cmd_pserver,
    "master": cmd_master,
    "coord": cmd_coord,
    "elastic": cmd_elastic,
}


def main():
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help"):
        print(__doc__)
        return 0 if len(sys.argv) >= 2 else 2
    cmd = COMMANDS.get(sys.argv[1])
    if cmd is None:
        print(f"unknown command {sys.argv[1]!r}; "
              f"one of {sorted(COMMANDS)}", file=sys.stderr)
        return 2
    from paddle_tpu import compile_cache

    compile_cache.configure()  # before first backend use
    return cmd(sys.argv[2:])


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
