"""Runtime telemetry: metrics registry, trace spans, Chrome-trace export.

The instrument panel for everything the ROADMAP wants measured:

- ``metrics``   — typed counters/gauges/histograms with labels; the
  Executor and InferenceServer update the process-global ``REGISTRY``
  on every compile/step/request.  Exposed as Prometheus text on the
  server's ``GET /metrics`` and as JSON/tables via ``paddle stats``.
- ``events``    — ``span(name, **args)``, the one way the program
  writes a span: a ``jax.profiler.TraceAnnotation`` (in a jax profile,
  on the device trace's clock) and, while ``recording()`` is on, a
  Chrome-trace event with id/parent/args in the bounded
  ``GLOBAL_EVENTS`` ring (``paddle stats --trace``, ``GET /trace``);
  ``phase`` is a ``span`` whose seconds also go to the ``PhaseAccount``
  open on the thread (the decode tick's own account, flushed to the
  registry once a tick: ``measure_tick_account_overhead``).
- device-side naming — the executor's jitted step is ``jit_paddle_step``
  and every Pallas kernel has a ``name=``; ``flags trace_ops=1`` also
  wraps each op's lowering in ``jax.named_scope`` (executor.py).

``reset()`` clears recorded values (registered metric families survive,
so module-level handles stay valid) — tests call it per-case.
"""

from __future__ import annotations

import time

from paddle_tpu.observability.metrics import (  # noqa: F401
    COMPILE_TIME_BUCKETS,
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    format_snapshot,
    format_table,
    gauge,
    histogram,
    render_prometheus,
    snapshot,
)
from paddle_tpu.observability.events import (  # noqa: F401
    EventRecorder,
    GLOBAL_EVENTS,
    PhaseAccount,
    phase,
    recording,
    span,
)


def reset():
    """Clear all recorded metric values and host events."""
    REGISTRY.reset()
    GLOBAL_EVENTS.clear()


def export_chrome_trace(path: str) -> str:
    """Dump the global host-event ring as Chrome-trace JSON."""
    return GLOBAL_EVENTS.export(path)


# spans one cached Executor.run writes (executor.compile: misses only)
_RUN_SPANS = ("executor.feed", "executor.lookup", "executor.gather_state",
              "executor.step", "executor.commit_state", "executor.fetch")


def measure_step_overhead(iters: int = 2000) -> float:
    """Average wall cost (seconds) of the telemetry writes Executor.run
    adds to one *cached* step with nobody recording: the cache-hit
    counter, the feed/step histogram observes, the fetch-bytes counter,
    and the ``executor.run`` span with its six children.

    The counters go to a private registry so measuring does not pollute
    live metrics.  Asserted ≤ budget in tests
    (``test_step_overhead_within_budget``) — the hot-path ≤2% guarantee,
    measured instead of promised.
    """
    reg = MetricsRegistry()
    hits = reg.counter("overhead_probe_hits_total")
    fetched = reg.counter("overhead_probe_bytes_total")
    steps = reg.histogram("overhead_probe_seconds")
    t0 = time.perf_counter()
    for i in range(iters):
        with span("executor.run", program="fingerprint0", step=i) as run:
            for name in _RUN_SPANS:
                with span(name):
                    pass
            run.set(cached="hit")
        hits.inc(program="fingerprint0")
        steps.observe(1e-4, program="fingerprint0", stage="feed")
        steps.observe(1e-3, program="fingerprint0", cached="hit")
        fetched.inc(4096, program="fingerprint0")
    return (time.perf_counter() - t0) / iters


def measure_tick_account_overhead(iters: int = 2000) -> float:
    """Seconds the decode tick's account (``decode/session.py``:
    ``TickAccount``) adds to one tick, with nobody recording: a tick of
    every phase with one admission (a 50-row prompt in a bucket of 64
    beside 15 live slots, so every by-bucket line of the flush is
    written), written with ``phase`` under an open account and flushed
    into a private registry, less the same tick as it was written
    before the account was kept (plain ``span``s and the two counters a
    tick already incremented).  Asserted under a budget in the tests:
    the account is on in every run."""
    from paddle_tpu.decode.session import PHASE_SPANS, TickAccount

    reg = MetricsRegistry()
    account = TickAccount(
        reg, packed_rows=lambda rows: max(64, 1 << (rows - 1).bit_length()))
    inputs = reg.counter("overhead_probe_step_inputs_total")
    deliveries = reg.counter("overhead_probe_deliveries_total")
    flat = [PHASE_SPANS[label] for label in (
        "collect", "decide", "sweep", "cow", "upload", "dispatch",
        "deliver")]

    def tick(stmt, i):
        with stmt("decode.between"):
            pass
        with stmt("decode.tick", active=16, waiting=0, rids="1,2,3") as t:
            with stmt("decode.admit", rid=i, prompt_len=50,
                      bucket=64) as admit:
                with stmt("decode.prefill", rid=i, bucket=64, pad=14):
                    with stmt("decode.prefill_wait"):
                        pass
                with stmt("decode.first_token", rid=i):
                    pass
            for name in flat:
                with stmt(name):
                    pass
        return t, admit

    def per_tick(accounted):
        t0 = time.perf_counter()
        for i in range(iters):
            if accounted:
                account.phases.open()
                t, admit = tick(phase, i)
                account.admitted(admit, 15, True)
                account.inputs[0] += 1
                account.under[0] += 1
                account.flush(t.t0, 16, 16, 0, True, 0.0)
                account.phases.close()
            else:
                tick(span, i)
                inputs.inc(source="resident")
                deliveries.inc(under="step")
        return (time.perf_counter() - t0) / iters

    per_tick(True)      # both paths warm before either is timed
    before = per_tick(False)
    return per_tick(True) - before


def measure_span_overhead(iters: int = 20000) -> dict:
    """Seconds per ``span(name, a=, b=)`` with both sinks off
    (``off``), while a jax profile is being captured (``profile``) and
    with the ring on (``ring``).  The ring mode records into
    ``GLOBAL_EVENTS`` and clears it afterwards; the profile is written
    to a temporary directory and thrown away."""
    import tempfile

    from paddle_tpu.profiler import profiler

    def per_span():
        t0 = time.perf_counter()
        for i in range(iters):
            with span("overhead.probe", rid=i, kind="probe"):
                pass
        return (time.perf_counter() - t0) / iters

    out = {"off": per_span()}
    with tempfile.TemporaryDirectory(prefix="span_overhead_") as path:
        with profiler(path):
            out["profile"] = per_span()
    with recording() as ring:
        out["ring"] = per_span()
    ring.clear()
    return out
