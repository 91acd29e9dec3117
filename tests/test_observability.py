"""Runtime telemetry subsystem (paddle_tpu/observability): registry
semantics, executor instrumentation, the /metrics + /stats serving
surface, `paddle stats`, Chrome-trace export, and the satellite fixes
(stat.timed wraps, profiler kwargs, trainer show_layer_stat)."""

import io
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability.metrics import (
    Histogram, MetricsRegistry, format_table,
)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests")
    c.inc()
    c.inc(2, code="200")
    c.inc(code="200")
    assert c.value() == 1
    assert c.value(code="200") == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create is idempotent; kind clash is an error
    assert reg.counter("requests_total") is c
    with pytest.raises(TypeError):
        reg.gauge("requests_total")

    g = reg.gauge("inflight")
    g.inc()
    g.inc()
    g.dec()
    assert g.value() == 1
    g.set(7, worker="a")
    assert g.value(worker="a") == 7

    snap = reg.snapshot()
    assert snap["requests_total"]["type"] == "counter"
    vals = {tuple(v["labels"].items()): v["value"]
            for v in snap["requests_total"]["values"]}
    assert vals[()] == 1 and vals[(("code", "200"),)] == 3


def test_histogram_bucketing_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for _ in range(50):
        h.observe(0.05)
    for _ in range(30):
        h.observe(0.5)
    for _ in range(15):
        h.observe(5.0)
    for _ in range(5):
        h.observe(50.0)
    (child,) = h.snapshot()["values"]
    assert child["count"] == 100
    # buckets are cumulative, le-inclusive
    assert child["buckets"] == {"0.1": 50, "1": 80, "10": 95, "+Inf": 100}
    assert child["max"] == 50.0
    assert 0 < child["p50"] <= 0.1
    assert 1.0 < child["p95"] <= 10.0
    assert child["p99"] == 50.0  # +Inf bucket clamps to max observed
    assert h.quantile(0.5) == child["p50"]
    # boundary value lands in its own bucket (le inclusive)
    h2 = reg.histogram("edge_seconds", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.snapshot()["values"][0]["buckets"]["1"] == 1
    # all-zero observations: quantiles clamp to the true max (0), not
    # to a bucket-edge interpolation
    h3 = reg.histogram("zeros_seconds", buckets=(0.5, 1.0))
    for _ in range(10):
        h3.observe(0.0)
    assert h3.quantile(0.5) == 0.0
    assert h3.snapshot()["values"][0]["p99"] == 0.0


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs_seconds")

    def work():
        for _ in range(500):
            c.inc(program="p")
            h.observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(program="p") == 4000
    assert h.snapshot()["values"][0]["count"] == 4000


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("resp_total", "responses").inc(2, code="200")
    h = reg.histogram("req_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render_prometheus()
    assert "# HELP resp_total responses" in text
    assert "# TYPE resp_total counter" in text
    assert 'resp_total{code="200"} 2' in text
    assert "# TYPE req_seconds histogram" in text
    assert 'req_seconds_bucket{le="0.1"} 1' in text
    assert 'req_seconds_bucket{le="+Inf"} 2' in text
    assert "req_seconds_count 2" in text
    assert text.endswith("\n")


def test_reset_preserves_registered_families():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    c.inc(5)
    reg.reset()
    assert c.value() == 0
    c.inc()  # the module-level handle must stay live after reset
    assert reg.snapshot()["x_total"]["values"][0]["value"] == 1


def test_format_table_alignment():
    out = format_table([("alpha", "1"), ("b", "22")],
                       headers=("name", "n"))
    lines = out.splitlines()
    assert lines[0].startswith("name")
    assert lines[1].startswith("alpha")
    # numeric column right-aligned under its header
    assert lines[1].rstrip().endswith(" 1")


# ---------------------------------------------------------------------------
# Chrome-trace events
# ---------------------------------------------------------------------------


@pytest.fixture
def ring():
    """The span ring is off by default: record for one test."""
    with obs.recording() as r:
        yield r


def test_chrome_trace_export_well_formed(tmp_path, ring):
    with obs.span("outer", program="p"):
        with obs.span("inner"):
            pass
    ring.instant("marker", cat="test")
    path = obs.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    assert trace["displayTimeUnit"] == "ms"
    # the ring's epoch on both clocks: wall, and the perf_counter a load
    # generator stamps its sends on
    other = trace["otherData"]
    assert other["epoch_unix_sec"] > 0
    assert 0 < other["epoch_perf_counter_sec"] <= time.perf_counter()
    evs = trace["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(ev)
        assert ev["ts"] >= 0
    complete = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"outer", "inner"}
    for e in complete:
        assert e["dur"] >= 0
    outer = next(e for e in complete if e["name"] == "outer")
    assert outer["args"]["program"] == "p"
    # the ring is bounded
    small = obs.EventRecorder(max_events=4)
    for i in range(10):
        small.instant(f"e{i}")
    assert len(small.events()) == 4
    # clear() keeps the epoch: a span started before a concurrent
    # clear() must still complete with a sane non-negative timestamp
    t_before = small.now()
    small.clear()
    assert not small.events()
    small.complete("inflight", t_before, small.now() - t_before)
    (ev,) = small.events()
    assert ev["ts"] >= 0 and ev["dur"] >= 0


# ---------------------------------------------------------------------------
# Executor instrumentation
# ---------------------------------------------------------------------------


def _tiny_model():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    pred = fluid.layers.fc(input=x, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return exe, pred


def _prog_label():
    from paddle_tpu.executor import Executor

    return Executor._program_key(fluid.default_main_program())[:12]


def test_executor_cache_miss_then_hit_counters(ring):
    """Two identical Executor.run calls: the first is a compile-cache
    miss, the second a hit — the acceptance-criterion transition."""
    exe, pred = _tiny_model()
    xs = np.random.RandomState(0).randn(2, 4).astype("float32")
    exe.run(feed={"x": xs}, fetch_list=[pred])
    exe.run(feed={"x": xs}, fetch_list=[pred])
    label = _prog_label()
    snap = obs.snapshot()

    def by_label(name):
        return {tuple(sorted(v["labels"].items())): v
                for v in snap[name]["values"]}

    miss = by_label("executor_compile_cache_miss_total")
    hit = by_label("executor_compile_cache_hit_total")
    assert miss[(("program", label), ("source", "jit"))]["value"] == 1
    assert hit[(("program", label), ("source", "jit"))]["value"] == 1

    # per-fingerprint compile + step + feed metrics rode along
    compile_sec = by_label("executor_compile_seconds")
    assert compile_sec[(("program", label),)]["count"] == 1
    steps = snap["executor_step_seconds"]["values"]
    tags = {(v["labels"]["program"], v["labels"]["cached"]): v["count"]
            for v in steps}
    assert tags[(label, "miss")] == 1 and tags[(label, "hit")] == 1
    feed = by_label("executor_feed_convert_seconds")
    assert feed[(("program", label),)]["count"] == 2
    fetched = by_label("executor_fetch_device_to_host_bytes_total")
    assert fetched[(("program", label),)]["value"] == 2 * 2 * 3 * 4  # f32

    # host events recorded the compile + both steps
    names = [e["name"] for e in ring.events()]
    assert names.count("executor.step") >= 2
    assert "executor.compile" in names


def test_trace_ops_flag_is_part_of_cache_key():
    """trace_ops=1 wraps op lowering in named_scope/TraceAnnotation —
    a different traced program, so it must recompile, and numerics must
    be identical."""
    from paddle_tpu.flags import FLAGS

    exe, pred = _tiny_model()
    xs = np.random.RandomState(1).randn(2, 4).astype("float32")
    (base,) = exe.run(feed={"x": xs}, fetch_list=[pred])
    label = _prog_label()
    try:
        FLAGS.set("trace_ops", True)
        (traced,) = exe.run(feed={"x": xs}, fetch_list=[pred])
        (traced2,) = exe.run(feed={"x": xs}, fetch_list=[pred])
    finally:
        FLAGS.set("trace_ops", False)
    np.testing.assert_allclose(np.asarray(traced), np.asarray(base),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(traced2), np.asarray(base),
                               rtol=1e-6)
    miss = obs.REGISTRY.get("executor_compile_cache_miss_total")
    hit = obs.REGISTRY.get("executor_compile_cache_hit_total")
    # plain + traced variants, both fresh JIT compiles
    assert miss.value(program=label, source="jit") == 2
    assert hit.value(program=label, source="jit") == 1  # traced rerun cached


def test_step_overhead_within_budget():
    """The per-step telemetry write set must stay far inside the 2%
    hot-path budget (2% of the ~97 ms ResNet step is ~2 ms; of a 2.5 ms
    toy step, 50 µs).  Measured cost is single-digit µs; assert an
    order of magnitude of slack for loaded CI machines."""
    overhead = obs.measure_step_overhead(iters=1000)
    assert overhead < 200e-6, f"telemetry overhead {overhead*1e6:.1f}µs"


# ---------------------------------------------------------------------------
# paddle stats CLI
# ---------------------------------------------------------------------------


def test_paddle_stats_cli_table_and_json(capsys):
    from paddle_tpu.cli import cmd_stats

    exe, pred = _tiny_model()
    xs = np.random.RandomState(0).randn(2, 4).astype("float32")
    exe.run(feed={"x": xs}, fetch_list=[pred])
    exe.run(feed={"x": xs}, fetch_list=[pred])
    label = _prog_label()

    assert cmd_stats([]) == 0
    table = capsys.readouterr().out
    assert "executor_compile_cache_miss_total" in table
    assert "executor_compile_cache_hit_total" in table
    assert f"program={label}" in table

    assert cmd_stats(["--json"]) == 0
    snap = json.loads(capsys.readouterr().out)
    miss = {v["labels"]["program"]: v["value"]
            for v in snap["executor_compile_cache_miss_total"]["values"]}
    hit = {v["labels"]["program"]: v["value"]
           for v in snap["executor_compile_cache_hit_total"]["values"]}
    assert miss[label] == 1 and hit[label] == 1


def test_paddle_stats_empty_and_file_and_trace(tmp_path, capsys):
    from paddle_tpu.cli import cmd_stats

    assert cmd_stats([]) == 0
    assert "empty" in capsys.readouterr().out

    # --file renders an artifact's nested registry (nothing in the tree
    # writes one since PR 57; the CLI form stays, ROADMAP D5)
    reg = MetricsRegistry()
    reg.counter("demo_total").inc(3, program="abc")
    art = {"schema": "paddle_tpu.bench_telemetry.v1",
           "metrics": reg.snapshot()}
    p = tmp_path / "telemetry.json"
    p.write_text(json.dumps(art))
    assert cmd_stats([f"--file={p}"]) == 0
    out = capsys.readouterr().out
    assert "demo_total" in out and "program=abc" in out

    # --trace exports the host event ring as Chrome-trace JSON
    obs.GLOBAL_EVENTS.instant("marker")
    trace_path = tmp_path / "trace.json"
    assert cmd_stats([f"--trace={trace_path}"]) == 0
    capsys.readouterr()
    with open(trace_path) as f:
        trace = json.load(f)
    assert any(e["name"] == "marker" for e in trace["traceEvents"])

    # --file --trace exports the artifact's EMBEDDED events, not this
    # process's ring; an artifact without events is a clear error
    rec = obs.EventRecorder(max_events=8)
    rec.instant("from_artifact")
    art_ev = {"schema": "paddle_tpu.bench_telemetry.v1",
              "metrics": reg.snapshot(),
              "events": rec.to_chrome_trace()}
    p2 = tmp_path / "with_events.json"
    p2.write_text(json.dumps(art_ev))
    t2 = tmp_path / "art_trace.json"
    assert cmd_stats([f"--file={p2}", f"--trace={t2}"]) == 0
    capsys.readouterr()
    with open(t2) as f:
        embedded = json.load(f)
    assert [e["name"] for e in embedded["traceEvents"]] == ["from_artifact"]
    assert cmd_stats([f"--file={p}", f"--trace={t2}"]) == 2  # no events
    capsys.readouterr()

    # --run --trace records the script's own spans: the ring is on
    # while it runs and off again afterwards
    script = tmp_path / "script.py"
    script.write_text(
        "from paddle_tpu import observability as obs\n"
        "with obs.span('script.work', n=1):\n"
        "    pass\n")
    t3 = tmp_path / "run_trace.json"
    assert cmd_stats([f"--run={script}", f"--trace={t3}"]) == 0
    capsys.readouterr()
    with open(t3) as f:
        ran = json.load(f)["traceEvents"]
    assert [e["name"] for e in ran] == ["script.work"]
    assert not obs.GLOBAL_EVENTS.enabled


# ---------------------------------------------------------------------------
# Serving: /metrics + /stats on a live InferenceServer
# ---------------------------------------------------------------------------


def _export_model(tmp_path):
    exe, pred = _tiny_model()
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    return d


def _predict(base, xs, timeout=60):
    import urllib.request

    req = urllib.request.Request(
        f"{base}/predict", data=json.dumps({"x": xs.tolist()}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def test_metrics_endpoint_on_live_server(tmp_path, capsys):
    """GET /metrics serves Prometheus text with the request-latency
    histogram and status counters; /stats serves the JSON snapshot that
    `paddle stats --url` renders."""
    import urllib.request

    from paddle_tpu.cli import cmd_stats
    from paddle_tpu.serving import InferenceServer

    d = _export_model(tmp_path)
    srv = InferenceServer(d)
    try:
        base = f"http://{srv.address}"
        xs = np.random.RandomState(0).randn(2, 4).astype("float32")
        _predict(base, xs)
        _predict(base, xs)

        # the latency observation lands in the handler's ``finally``
        # *after* the reply is on the wire — give the scrape a moment
        # to see both requests settle
        want = 'serving_request_seconds_count{endpoint="/predict"} 2'
        for scrapes in range(100):
            with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
                ctype = r.headers["Content-Type"]
                text = r.read().decode()
            if want in text:
                break
            time.sleep(0.05)
        assert ctype.startswith("text/plain")
        assert "# TYPE serving_request_seconds histogram" in text
        assert 'serving_request_seconds_bucket{endpoint="/predict",le="+Inf"} 2' in text
        assert 'serving_request_seconds_count{endpoint="/predict"} 2' in text
        # every earlier scrape was a 200 too
        assert f'serving_responses_total{{code="200"}} {2 + scrapes}' in text
        assert "serving_inflight_requests 0" in text
        # executor metrics ride on the same registry
        assert "executor_compile_cache_miss_total" in text

        with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
            snap = json.loads(r.read())
        (lat,) = snap["serving_request_seconds"]["values"]
        assert lat["count"] == 2
        assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]

        assert cmd_stats([f"--url={base}"]) == 0
        out = capsys.readouterr().out
        assert "serving_request_seconds" in out
    finally:
        srv.stop()


@pytest.mark.slow
def test_metrics_under_concurrent_load(tmp_path):
    """Latency histogram and status counters stay exact under
    concurrent clients; the in-flight gauge settles back to 0."""
    from paddle_tpu.serving import InferenceServer

    d = _export_model(tmp_path)
    srv = InferenceServer(d)
    try:
        base = f"http://{srv.address}"
        xs = np.random.RandomState(0).randn(2, 4).astype("float32")
        _predict(base, xs)  # compile once before the swarm
        errs = []

        def client():
            try:
                for _ in range(5):
                    _predict(base, xs)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        # handler threads observe the histogram after replying — wait
        # for the last observations to settle
        lat = obs.REGISTRY.get("serving_request_seconds")
        for _ in range(100):
            if lat.count(endpoint="/predict") >= 21:
                break
            time.sleep(0.05)
        assert lat.count(endpoint="/predict") == 21
        resp = obs.REGISTRY.get("serving_responses_total")
        assert resp.value(code="200") == 21
        assert obs.REGISTRY.get("serving_inflight_requests").value() == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Satellites: stat.timed wraps, StatSet delegation, profiler kwargs,
# trainer show_layer_stat / log_period, bench artifact writer
# ---------------------------------------------------------------------------


def test_stat_timed_preserves_wrapped_function():
    import inspect

    from paddle_tpu import stat

    s = stat.StatSet("t")

    @stat.timed("fn", stats=s)
    def add(a, b=1):
        """Adds things."""
        return a + b

    assert add(2, b=3) == 5
    assert add.__name__ == "add"
    assert add.__doc__ == "Adds things."
    assert add.__qualname__.endswith("add")
    assert list(inspect.signature(add).parameters) == ["a", "b"]
    assert add.__wrapped__ is not add
    assert s.items()["fn"].count == 1


def test_statset_print_status_uses_shared_formatter():
    from paddle_tpu import stat

    s = stat.StatSet("fmt")
    with stat.timer("forwardBackward", stats=s):
        pass
    buf = io.StringIO()
    s.print_status(out=buf)
    out = buf.getvalue()
    assert "StatSet: [fmt]" in out
    assert "forwardBackward" in out
    assert "total_ms" in out and "count" in out  # shared table header


def test_profiler_forwards_and_rejects_kwargs(monkeypatch):
    import jax

    from paddle_tpu import profiler as prof

    calls = {}

    def fake_start(log_dir, create_perfetto_link=False,
                   create_perfetto_trace=False):
        calls["start"] = (log_dir, create_perfetto_link,
                         create_perfetto_trace)

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.setdefault("stop", True))

    with prof.profiler("/tmp/x", create_perfetto_trace=True):
        pass
    assert calls["start"] == ("/tmp/x", False, True)
    assert calls["stop"] is True

    calls.clear()
    with pytest.raises(TypeError, match="bogus_option"):
        with prof.profiler("/tmp/x", bogus_option=1):
            pass
    assert "start" not in calls  # rejected before the trace started


def test_trainer_show_layer_stat_and_log_period_flags(capsys):
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.trainer.trainer import (
        _dump_layer_stat, _resolve_log_period,
    )

    # log_period: explicit argument wins; flag is the default
    assert _resolve_log_period(7) == 7
    FLAGS.set("log_period", 13)
    try:
        assert _resolve_log_period(None) == 13
    finally:
        FLAGS.set("log_period", 100)

    # show_layer_stat dump includes live registry content
    exe, pred = _tiny_model()
    xs = np.random.RandomState(0).randn(2, 4).astype("float32")
    exe.run(feed={"x": xs}, fetch_list=[pred])
    buf = io.StringIO()
    _dump_layer_stat(0, 20, out=buf)
    out = buf.getvalue()
    assert "runtime stats (pass 0, batch 20)" in out
    assert "executor_compile_cache_miss_total" in out


# ---------------------------------------------------------------------------
# The one span API: ring (off by default) + TraceAnnotation
# ---------------------------------------------------------------------------


def _by_name(events):
    out = {}
    for e in events:
        out.setdefault(e["name"], []).append(e)
    return out


def test_span_nests_with_parent_ids_and_late_args(ring):
    with obs.span("a", rid=7) as a:
        with obs.span("b", rid=7):
            with obs.span("c"):
                pass
        with obs.span("b2"):
            pass
        a.set(cached="hit")
    with obs.span("root2"):
        pass
    ev = {n: es[0] for n, es in _by_name(ring.events()).items()}
    ids = {n: e["args"]["id"] for n, e in ev.items()}
    assert len(set(ids.values())) == 5
    assert ev["a"]["args"]["parent"] == 0 == ev["root2"]["args"]["parent"]
    assert ev["b"]["args"]["parent"] == ids["a"] == ev["b2"]["args"]["parent"]
    assert ev["c"]["args"]["parent"] == ids["b"]
    # spans of one request share rid; an argument set inside the span
    # is kept
    assert ev["a"]["args"]["rid"] == ev["b"]["args"]["rid"] == 7
    assert ev["a"]["args"]["cached"] == "hit"
    # a child lies inside its parent on the ring's clock
    assert ev["a"]["ts"] <= ev["b"]["ts"]
    assert ev["b"]["ts"] + ev["b"]["dur"] <= ev["a"]["ts"] + ev["a"]["dur"]


def test_span_is_reentrant_and_survives_exceptions(ring):
    s = obs.span("again", n=1)
    for _ in range(2):
        with s:
            pass
    with pytest.raises(KeyError):
        with obs.span("outer"):
            with obs.span("raises"):
                raise KeyError("x")
    with obs.span("after"):
        pass
    ev = _by_name(ring.events())
    assert len(ev["again"]) == 2
    assert ev["raises"][0]["args"]["parent"] == ev["outer"][0]["args"]["id"]
    # the thread's stack of open spans unwound: the next span is a root
    assert ev["after"][0]["args"]["parent"] == 0


def test_span_thread_safety_under_contention(ring):
    """More threads than cores, a short switch interval: every span is
    recorded once and parents never cross threads."""
    import sys

    n_threads, n_iter = 16, 200
    errs = []

    def work(k):
        try:
            for i in range(n_iter):
                with obs.span("t.outer", k=k):
                    with obs.span("t.inner", k=k):
                        pass
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errs
    ev = _by_name(ring.events())
    assert len(ev["t.outer"]) == len(ev["t.inner"]) == n_threads * n_iter
    outer = {e["args"]["id"]: e for e in ev["t.outer"]}
    assert len(outer) == n_threads * n_iter          # ids are unique
    for e in ev["t.inner"]:
        parent = outer[e["args"]["parent"]]
        assert parent["tid"] == e["tid"] and parent["args"]["k"] == e["args"]["k"]
    assert all(e["args"]["parent"] == 0 for e in ev["t.outer"])


def test_span_with_both_sinks_off_records_nothing():
    assert not obs.GLOBAL_EVENTS.enabled          # off by default
    with obs.span("nobody.listens", rid=1) as sp:
        sp.set(more=2)
        with obs.span("nested"):
            pass
    assert obs.GLOBAL_EVENTS.events() == []
    # ... and an Executor.run appends nothing either
    exe, pred = _tiny_model()
    xs = np.zeros((2, 4), "float32")
    exe.run(feed={"x": xs}, fetch_list=[pred])
    exe.run(feed={"x": xs}, fetch_list=[pred])
    assert obs.GLOBAL_EVENTS.events() == []


def test_recording_turns_the_ring_on_and_off():
    with obs.span("before"):
        pass
    with obs.recording() as r:
        assert r is obs.GLOBAL_EVENTS and r.enabled
        with obs.span("during"):
            pass
    with obs.span("after"):
        pass
    assert [e["name"] for e in obs.GLOBAL_EVENTS.events()] == ["during"]
    # a new recording starts empty
    with obs.recording() as r:
        assert r.events() == []


def _capture_profile(tmp_path, body):
    """Run ``body`` under a CPU jax.profiler capture; the host plane's
    events as {name: [(start_ns, dur_ns, stats dict)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData

    d = str(tmp_path / "profile")
    jax.profiler.start_trace(d)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.duration_ns, dict(ev.stats)))
    return out


def test_span_lands_in_a_jax_profile_and_in_the_ring(tmp_path, ring):
    """With both sinks on the same span is in both: as a
    TraceAnnotation on the profile's clock, its args as the event's
    stats, and in the ring with id and parent."""
    def body():
        with obs.span("both.outer", rid=41, tenant="t0") as sp:
            sp.set(cached="miss")
            with obs.span("both.inner", rid=41):
                time.sleep(0.002)

    prof = _capture_profile(tmp_path, body)
    (o_start, o_dur, o_stats), = prof["both.outer"]
    (i_start, i_dur, i_stats), = prof["both.inner"]
    assert o_stats["rid"] == 41 == i_stats["rid"]
    assert o_stats["tenant"] == "t0" and o_stats["cached"] == "miss"
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
    assert i_dur >= 2e6                               # ns
    ev = _by_name(ring.events())
    assert ev["both.inner"][0]["args"]["parent"] == \
        ev["both.outer"][0]["args"]["id"]
    assert ev["both.outer"][0]["args"]["rid"] == 41
    # the two clocks differ by an offset only: durations agree
    assert ev["both.inner"][0]["dur"] * 1e3 == pytest.approx(i_dur, rel=0.5)


def test_executor_span_tree_in_order(ring):
    """One Executor.run: executor.run with its children in order,
    executor.compile only on the miss, the run's args on the root."""
    exe, pred = _tiny_model()
    ring.clear()                                   # drop the startup run
    xs = np.random.RandomState(0).randn(2, 4).astype("float32")
    trees = []
    for _ in range(2):
        exe.run(feed={"x": xs}, fetch_list=[pred])
        evs = sorted(ring.events(), key=lambda e: e["ts"])
        ring.clear()
        (root,) = [e for e in evs if e["name"] == "executor.run"]
        kids = [e for e in evs if e["args"].get("parent") == root["args"]["id"]]
        assert len(kids) == len(evs) - 1
        trees.append((root, [k["name"] for k in kids]))
    cached = ["executor.feed", "executor.lookup", "executor.gather_state",
              "executor.step", "executor.commit_state", "executor.fetch"]
    assert trees[0][1] == cached[:2] + ["executor.compile"] + cached[2:]
    assert trees[1][1] == cached
    (miss, _), (hit, _) = trees
    assert miss["args"]["cached"] == "miss" and hit["args"]["cached"] == "hit"
    assert miss["args"]["program"] == hit["args"]["program"] == _prog_label()
    assert hit["args"]["step"] == miss["args"]["step"] + 1


def test_executor_step_is_named_in_the_compiled_module():
    """The jitted step has a stable name whatever the program: the
    module a device trace shows is jit_paddle_step, its ops sit under
    the paddle_step scope."""
    exe, pred = _tiny_model()
    xs = np.zeros((2, 4), "float32")
    exe.run(feed={"x": xs}, fetch_list=[pred])
    comp = list(exe._cache.values())[-1]
    state = {n: fluid.global_scope().get(n) for n in comp.state_names}
    lowered = comp.fn.lower(state, {"x": xs})
    assert "jit_paddle_step" in lowered.as_text()[:200]
    assert "paddle_step/" in lowered.as_text(debug_info=True)


def test_span_overhead_probe_reports_the_three_modes():
    got = obs.measure_span_overhead(iters=500)
    assert set(got) == {"off", "profile", "ring"}
    # a span nobody records must stay far under a dispatch (µs, not ms)
    assert 0 < got["off"] < 100e-6 and got["ring"] < 200e-6
    assert not obs.GLOBAL_EVENTS.enabled and not obs.GLOBAL_EVENTS.events()


def test_every_pallas_call_has_a_name():
    """An AST walk over paddle_tpu/: every pl.pallas_call passes a
    literal name= (the name a device trace and the compiled text's
    op_name show), distinct but for ``ragged_paged_attention``: the
    decode step's kernel on ungrouped heads, which is the walk where
    ``walk_fits`` takes the pages and the ``(S, P)`` grid elsewhere, and
    ONE kernel to whoever reads a trace (PR 60)."""
    import ast
    import os

    root = os.path.dirname(os.path.abspath(fluid.__file__))
    names, unnamed = [], []
    for dirpath, _, files in os.walk(root):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"):
                    kw = {k.arg: k.value for k in node.keywords}
                    if isinstance(kw.get("name"), ast.Constant):
                        names.append(kw["name"].value)
                    else:
                        unnamed.append(f"{path}:{node.lineno}")
    assert not unnamed
    assert len(names) == 25       # PR 64: grouped_gemm_up
    assert sorted(names) == sorted([*set(names), "ragged_paged_attention"])
    # the ring's name does not hold the grouped one's, which
    # perf/layer_metrics/attn_full_roofline.py counts kernels by
    assert [n for n in names if "ragged_paged_attention_gqa" in n] == [
        "ragged_paged_attention_gqa"]
    assert {"grouped_gemm", "grouped_gemm_gate_up", "ring_paged_attention",
            "flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv", "ragged_paged_attention",
            "ragged_paged_attention_chunk",
            "ragged_paged_attention_gqa",
            "gated_delta_step", "ssd_step", "s6_step", "conv_step",
            "gated_delta_chunked", "latent_paged_attention",
            "paged_index_scores", "index_scores", "selection_bias",
            "selected_flash_attention", "kda_step",
            "kda_chunked"} <= set(names)


@pytest.mark.parametrize("pool, heads_major, path", [
    ("bfloat16", False, "interpret_stored"),
    ("float32", False, "interpret"),
    ("bfloat16", True, "interpret"),
])
def test_the_grouped_walks_dispatch_names_the_body_it_took(
        monkeypatch, pool, heads_major, path):
    """``pallas_dispatch_total{kernel="ragged_paged_attention_gqa"}``
    tells the walk that consumes a page as it is stored from the one
    that widens it (PR 63): a row-major bfloat16 pool counts under
    ``<path>_stored``, a float32 pool and heads-major pages under the
    plain path, so a program's registry says which body it compiled."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import attention as A
    from tests.chip_compile import _walk_dispatches, _walks_took

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", True)
    S, Hq, Hkv, D, page, N, P = 2, 8, 2, 128, 8, 5, 2
    sds = jax.ShapeDtypeStruct
    pages = sds((N, Hkv, page, D) if heads_major else (N, page, Hkv, D),
                jnp.dtype(pool))
    before = _walk_dispatches()
    jax.make_jaxpr(lambda *a: A.paged_attention(*a, heads_major=heads_major))(
        sds((S, Hq, D), jnp.bfloat16), pages, pages, sds((S, P), jnp.int32),
        sds((S,), jnp.int32))
    _walks_took(before, **{path: 1})


def test_the_latent_layers_names():
    """What the latent layer adds to the tracing (PR 45): the kernel's
    dispatch under its own name, the causal-pairs counter the prefill
    share reads, the ``latent`` kind of the cache gauges, and the four
    device-side scopes, spelled as the readers of ``perf/harness/
    latent.py`` spell them."""
    import inspect

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import kanana_mla as km
    from perf.harness import latent

    pk.use_latent_paged_attention("bfloat16", 128, 32, 640, 512)
    fam = obs.metrics.REGISTRY.get("pallas_dispatch_total")
    assert any(v["labels"].get("kernel") == "latent_paged_attention"
               for v in fam.snapshot()["values"])
    assert obs.metrics.REGISTRY.get(latent.PAIRS_COUNTER) is not None
    model = km.KananaMlaLM(
        vocab=32, d_model=16, num_heads=2, num_layers=2,
        qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=4,
        kv_lora_rank=8, dense_width=16, expert_width=8,
        num_experts_published=4, held_experts=(0, 2), experts_per_tok=2,
        max_len=16, num_pages=4, page_size=8, pages_per_seq=2,
        dtype="float32")
    assert list(model.cache_rows([3])) == list(model.cache_bytes([3])) \
        == ["latent"]
    src = inspect.getsource(km)
    for scope in ("attn_latent", "attn_latent_down", "attn_latent_absorb",
                  "attn_latent_expand"):
        assert f'jax.named_scope("{scope}")' in src, scope
    assert latent.ANY_SCOPE == "/attn_latent/"
    assert latent.DECODE_KERNEL == "latent_paged_attention"


def test_the_short_convs_and_the_chunks_names():
    """What the gated short conv and the chunk over a state entry add to
    the tracing (PR 59): the host span a chunk program, the two counters
    the chunk readers take rows and pairs by, and the device-side scopes,
    spelled as the readers of ``perf/harness/short_conv.py`` spell
    them; the conv's kernel keeps its one name whatever its
    activation."""
    import inspect

    from paddle_tpu.decode import state_entry as se
    from paddle_tpu.models import lfm2_moe as lm
    from perf.harness import short_conv as sc

    for name in (sc.CHUNK_ROWS, sc.CHUNK_PAIRS):
        assert obs.metrics.REGISTRY.get(name) is not None, name
    assert (sc.CHUNK_ROWS, sc.CHUNK_PAIRS) == (
        "decode_prefill_chunk_rows_total", "decode_prefill_chunk_pairs_total")
    src = inspect.getsource(se)
    assert 'phase("decode.prefill_chunk", done=done, rows=real,' in src
    # the label is the model's (``chunk_over``: "ring" where the entry
    # holds window layers' rings, PR 62), "state" unless it says so
    assert 'chunk_over = "state"' in src and "over=self.chunk_over" in src
    assert se._prefill_state_chunk.__name__ == "_prefill_state_chunk"
    assert sc.CHUNK_MODULE == "_prefill_state_chunk"
    src = inspect.getsource(lm)
    for scope in ("short_conv", "short_conv_step", "short_conv_scan",
                  "attn_full", "attn_chunk"):
        assert f'jax.named_scope("{scope}")' in src, scope
    assert (sc.ANY_SCOPE, sc.CHUNK_ATTENTION_SCOPE) == (
        "/short_conv/", "/attn_chunk/")
    model = lm.Lfm2MoeLM(
        vocab=32, d_model=128, num_heads=2, num_kv_heads=2, head_dim=64,
        layer_types=lm.PERIOD, num_dense_layers=1, intermediate_size=16,
        moe_intermediate_size=128, num_experts=4, experts_per_tok=2,
        max_len=16, num_pages=6, page_size=4, pages_per_seq=4,
        state_entries=3, prefill_rows=8, chunk_rows=4, dtype="float32")
    assert list(model.cache_rows([3])) == list(model.cache_bytes([3])) \
        == ["full", "state"]


def test_the_program_writes_spans_through_span_only():
    """No TraceAnnotation and no direct ring write outside
    paddle_tpu/observability, except flags trace_ops's per-op wrap."""
    import os
    import re

    root = os.path.dirname(os.path.abspath(fluid.__file__))
    rx = re.compile(r"TraceAnnotation\(|_EVENTS\.(complete|span|instant)\("
                    r"|GLOBAL_EVENTS\.(complete|instant)\(")
    hits = []
    for dirpath, _, files in os.walk(root):
        if os.path.basename(dirpath) == "observability":
            continue
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    for i, line in enumerate(f, 1):
                        if rx.search(line):
                            hits.append((os.path.relpath(path, root), i))
    assert [h[0] for h in hits] == ["executor.py"], hits   # trace_ops


# ---------------------------------------------------------------------------
# /generate: one rid from the handler down to the decode tick; GET /trace
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_gen_server():
    from paddle_tpu.decode.engine import GenerationEngine
    from paddle_tpu.decode.model import TinyDecoderLM
    from paddle_tpu.serving import InferenceServer

    lm = TinyDecoderLM(vocab=32, d_model=16, num_heads=2, num_layers=2,
                       num_pages=32, page_size=4, pages_per_seq=8, seed=0)
    srv = InferenceServer(None, generator=GenerationEngine(
        lm, max_slots=2, max_new_tokens=8))
    try:
        _generate(srv, [1, 5, 9], 3)        # compiles outside the tests
        yield srv
    finally:
        srv.stop()


def _generate(srv, src, n):
    import urllib.request

    req = urllib.request.Request(
        f"http://{srv.address}/generate",
        data=json.dumps({"src": src, "max_new_tokens": n}).encode())
    with urllib.request.urlopen(req, timeout=300) as r:
        lines = r.read().decode().strip().splitlines()
    return json.loads(lines[-1])["ids"]


def _registry_totals(*names):
    snap = obs.snapshot()
    out = {}
    for n in names:
        vals = snap.get(n, {}).get("values", [])
        out[n] = sum(v.get("count", v.get("value", 0)) for v in vals)
    return out


def test_generate_spans_share_one_rid_and_counters_move(toy_gen_server):
    counters = ("decode_queue_wait_seconds",
                "decode_active_slot_steps_total",
                "serving_generate_first_write_lag_seconds",
                "decode_steps_total")
    before = _registry_totals(*counters)
    with obs.recording() as ring:
        ids = _generate(toy_gen_server, [1, 5, 9], 4)
        # the handler's span closes after the reply is on the wire
        for _ in range(200):
            ev = _by_name(ring.events())
            if "serving.generate" in ev:
                break
            time.sleep(0.01)
    assert len(ids) == 4
    (gen,) = ev["serving.generate"]
    rid = gen["args"]["rid"]
    assert gen["args"]["tenant"] == "default" and gen["args"]["parent"] == 0
    for name in ("serving.parse", "serving.submit", "serving.first_write"):
        (e,) = ev[name]
        assert e["args"]["rid"] == rid
        assert e["args"]["parent"] == gen["args"]["id"], name
    # the stepper thread: admit (with prefill and first token) inside a
    # tick, all carrying the handler's rid
    (admit,) = ev["decode.admit"]
    assert admit["args"]["rid"] == rid and admit["tid"] != gen["tid"]
    assert admit["args"]["prompt_len"] == 3 and admit["args"]["pages"] == 2
    assert admit["args"]["cached_len"] == 0
    for name in ("decode.prefill", "decode.first_token"):
        (e,) = ev[name]
        assert e["args"]["rid"] == rid
        assert e["args"]["parent"] == admit["args"]["id"]
    ticks = {e["args"]["id"]: e for e in ev["decode.tick"]}
    assert admit["args"]["parent"] in ticks
    # the ticks after the admitting one name the request among their
    # slots' rids.  A step is spread over two ticks: its dispatch (and
    # its upload, when the host changed a row: here only the step after
    # the admission) under ``decode.step`` in one, the wait for its ids
    # (``decode.logits_to_host``) and the token choice
    # (``decode.sample``) at the top of the next, and the delivery of
    # what it chose (``decode.deliver``) at that tick's end, after the
    # next step's dispatch
    later = [t for t in ticks.values() if t["ts"] > admit["ts"]]
    assert later and all(str(rid) in t["args"]["rids"].split(",")
                         for t in later)
    steps = {e["args"]["id"]: e for e in ev["decode.step"]}
    assert len(steps) == 3                       # 4 tokens, 1 prefill
    assert all(s["args"]["parent"] in ticks for s in steps.values())
    assert len(ev["decode.dispatch"]) == 3 and len(ev["decode.upload"]) == 1
    for name in ("decode.upload", "decode.dispatch"):
        assert all(e["args"]["parent"] in steps for e in ev[name])
    for name in ("decode.logits_to_host", "decode.sample", "decode.deliver"):
        assert len(ev[name]) == 3
        assert all(e["args"]["parent"] in ticks for e in ev[name]), name
    dispatched = {e["args"]["parent"]: e for e in ev["decode.step"]}
    delivers = sorted(ev["decode.deliver"], key=lambda e: e["ts"])
    for deliver in delivers[:2]:    # the last has no step to run under
        step = dispatched[deliver["args"]["parent"]]
        assert step["ts"] + step["dur"] <= deliver["ts"]
    assert delivers[2]["args"]["parent"] not in dispatched
    for wait, sample in zip(
            sorted(ev["decode.logits_to_host"], key=lambda e: e["ts"]),
            sorted(ev["decode.sample"], key=lambda e: e["ts"])):
        assert wait["args"]["parent"] == sample["args"]["parent"]
        assert admit["args"]["parent"] != wait["args"]["parent"]
    after = _registry_totals(*counters)
    assert after["decode_queue_wait_seconds"] == \
        before["decode_queue_wait_seconds"] + 1
    assert after["serving_generate_first_write_lag_seconds"] == \
        before["serving_generate_first_write_lag_seconds"] + 1
    # one live slot in each of the three steps
    assert after["decode_steps_total"] == before["decode_steps_total"] + 3
    assert after["decode_active_slot_steps_total"] == \
        before["decode_active_slot_steps_total"] + 3


def test_generate_rid_reaches_the_profile_as_a_stat(toy_gen_server, tmp_path):
    """The same spans as TraceAnnotations in a jax profile: the rid is
    the event's stat, on handler and stepper thread alike."""
    prof = _capture_profile(
        tmp_path, lambda: (_generate(toy_gen_server, [1, 7], 3),
                           time.sleep(0.05)))
    (_, _, gen), = prof["serving.generate"]
    for name in ("serving.submit", "decode.admit", "decode.prefill",
                 "decode.first_token", "serving.first_write"):
        (_, _, stats), = prof[name]
        assert stats["rid"] == gen["rid"], name
    assert prof["decode.tick"] and prof["decode.logits_to_host"]


def test_idle_stepper_writes_idle_wait_spans(toy_gen_server):
    with obs.recording() as ring:
        time.sleep(0.2)
        names = {e["name"] for e in ring.events()}
    assert names == {"decode.idle_wait"}


def test_trace_endpoint_records_for_n_seconds(toy_gen_server, tmp_path,
                                              capsys):
    """GET /trace?seconds=N turns the ring on for N seconds and replies
    with its Chrome trace; `paddle stats --url --trace` writes it."""
    import urllib.error
    import urllib.request

    from paddle_tpu.cli import cmd_stats

    base = f"http://{toy_gen_server.address}"
    got = {}

    def fetch():
        with urllib.request.urlopen(f"{base}/trace?seconds=1.5",
                                    timeout=60) as r:
            got["doc"] = json.loads(r.read())

    t = threading.Thread(target=fetch)
    t.start()
    for _ in range(200):                    # until the recording is on
        if obs.GLOBAL_EVENTS.enabled:
            break
        time.sleep(0.01)
    # a second recording at the same time is refused, not interleaved
    with pytest.raises(urllib.error.HTTPError) as e409:
        urllib.request.urlopen(f"{base}/trace?seconds=0", timeout=30)
    assert e409.value.code == 409
    _generate(toy_gen_server, [1, 5, 9], 3)
    t.join(timeout=60)
    assert not t.is_alive() and not obs.GLOBAL_EVENTS.enabled
    names = {e["name"] for e in got["doc"]["traceEvents"]}
    assert {"serving.generate", "decode.admit", "decode.tick"} <= names
    assert "epoch_perf_counter_sec" in got["doc"]["otherData"]

    for bad in ("seconds=-1", "seconds=1e9", "seconds=soon"):
        with pytest.raises(urllib.error.HTTPError) as e400:
            urllib.request.urlopen(f"{base}/trace?{bad}", timeout=30)
        assert e400.value.code == 400

    out = tmp_path / "server_trace.json"
    assert cmd_stats([f"--url={base}", f"--trace={out}",
                      "--seconds=0.2"]) == 0
    capsys.readouterr()
    with open(out) as f:
        assert "traceEvents" in json.load(f)


# ---------------------------------------------------------------------------
# The skeleton's five scopes (ISSUE 51): every program that runs a block
# ---------------------------------------------------------------------------


def _toy(name):
    """The block's model at the toy size its own test file runs it at."""
    if name == "gpt2":
        from paddle_tpu.decode.model import TinyDecoderLM

        return TinyDecoderLM(seed=3)
    if name == "olmoe":
        from paddle_tpu.models.olmoe import OlmoeLM
        from tests.test_olmoe import SIZES

        return OlmoeLM(seed=5, **SIZES)
    if name == "exaone_moe":
        from paddle_tpu.models.exaone_moe import ExaoneMoeLM
        from tests.test_exaone_moe import SIZES

        return ExaoneMoeLM(seed=3, **SIZES)
    if name == "kanana_mla":
        from paddle_tpu.models.kanana_mla import KananaMlaLM
        from tests.test_kanana_mla import SIZES

        return KananaMlaLM(seed=3, **SIZES)
    if name == "phi4_flash":
        from tests.test_phi4_flash import make

        return make()
    from tests import hybrid_models

    return {"olmo_hybrid": hybrid_models.OLMO,
            "granite_hybrid": hybrid_models.GRANITE}[name].make()


# what a layer's mixer and feed-forward name of their own, under the
# skeleton's scope: (both programs, the prefill alone, the step alone)
_MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
_MECHANISMS = {
    "gpt2": ({"blk_mixer": ("attn_full",)}, {}, {}),
    "olmoe": ({"blk_mixer": ("attn_full",), "blk_mlp": _MOE}, {}, {}),
    "exaone_moe": ({"blk_mixer": ("attn_full", "attn_window"),
                    "blk_mlp": _MOE + ("moe_shared",)}, {}, {}),
    # off its kernels the step advances the slots in a loop, whose body
    # the lowered text outlines (``lin_attn_conv`` / ``lin_attn_state``
    # head its names there; compiled, they read
    # ``.../blk_mixer/lin_attn/while/body/lin_attn_conv/``)
    "olmo_hybrid": ({"blk_mixer": ("attn_full", "lin_attn")},
                    {"blk_mixer": ("lin_attn/lin_attn_conv",
                                   "lin_attn/lin_attn_scan")}, {}),
    "granite_hybrid": ({"blk_mixer": ("attn_full", "ssm/ssm_conv")},
                       {"blk_mixer": ("ssm/ssm_scan",)},
                       {"blk_mixer": ("ssm/ssm_state",)}),
    "kanana_mla": ({"blk_mixer": ("attn_latent/attn_latent_down",),
                    "blk_mlp": _MOE + ("moe_shared",)},
                   {"blk_mixer": ("attn_latent/attn_latent_expand",)},
                   {"blk_mixer": ("attn_latent/attn_latent_absorb",)}),
    "phi4_flash": ({"blk_mixer": ("attn_window", "attn_shared", "gmu",
                                  "ssm/ssm_conv")},
                   {"blk_mixer": ("ssm/ssm_scan",)},
                   {"blk_mixer": ("ssm/ssm_state",)}),
}


@pytest.mark.parametrize("name", sorted(_MECHANISMS))
def test_every_program_carries_the_skeletons_scopes(name):
    """The lowered text of a bucket's prefill and of the decode step
    holds the skeleton's scopes outermost (``blk_embed``, ``blk_mixer``,
    ``blk_mlp``, ``blk_head``; the prefill ``blk_store`` too), and what
    the block names of its own lies under them: the benchmark's readers
    match ``/scope/`` inside an ``op_name``, so an outer scope moves
    none of them, and the new ones split a program by the skeleton's
    parts."""
    import re

    from paddle_tpu.decode import model as dm

    model = _toy(name)
    S, n = 3, 11
    cache = model._cache()
    tables = np.zeros((S, model.pages_per_seq), np.int32)
    zeros = np.zeros((S,), np.int32)
    step = dm._decode_step.lower(
        model.params, *cache[:2], tables, zeros, zeros, heads=model.heads,
        page_size=model.page_size, block=model.block,
        extra=cache[2:]).as_text(debug_info=True)
    bucket = model.prefill_bucket(n)
    pages = model.allocator.alloc(model.context_pages(list(range(n)), 4))
    try:
        where = model._prompt_rows(pages, bucket, n)
    finally:
        model.allocator.free(pages)
    prefill = dm._prefill_bucket.lower(
        model.params, *cache[:2], np.zeros((bucket,), np.int32), where,
        np.int32(n), heads=model.heads, block=model.block,
        extra=cache[2:]).as_text(debug_info=True)

    both, in_prefill, in_step = _MECHANISMS[name]
    for program, text, own in (("_prefill_bucket", prefill, in_prefill),
                               ("_decode_step", step, in_step)):
        skeleton = ["blk_embed", "blk_mixer", "blk_mlp", "blk_head"]
        if program == "_prefill_bucket":
            skeleton.append("blk_store")
        names = set(re.findall(r'"jit\(%s\)/([^"]*)"' % program, text))
        assert names, program
        for scope in skeleton:
            assert any(op.startswith(scope + "/") for op in names), (
                program, scope)
        # one level, outermost: no skeleton scope lies inside another
        assert not [op for op in names
                    if re.search(r"/blk_(embed|mixer|mlp|head|store)/",
                                 "/" + op.split("/", 1)[-1])], program
        for outer in ("blk_mixer", "blk_mlp"):
            for inner in both.get(outer, ()) + own.get(outer, ()):
                # a loop's body lies a ``while/body`` deeper
                rx = re.compile(r"^%s/(.*/)?%s/" % (
                    outer, inner.replace("/", "/(.*/)?")))
                assert any(rx.match(op) for op in names), (
                    program, outer, inner)
                # and nowhere else: no mechanism outside its part
                last = inner.rsplit("/", 1)[-1]
                assert not [op for op in names
                            if "/%s/" % last in "/" + op
                            and not op.startswith(outer + "/")], (
                    program, inner)
