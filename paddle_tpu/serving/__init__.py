"""HTTP model serving over a save_inference_model export — the
continuous-batching serving engine.

The 2017 reference's deployment story was the C API; this serves the
same artifact over JSON/HTTP through a real serving stack instead of a
single executor behind a lock:

- **Bucketed request coalescing** (``paddle_tpu/serving/batching.py``):
  concurrent ``/predict`` requests are merged into padded batches at
  power-of-two bucket shapes — one compiled XLA program per
  (program-fingerprint, bucket) key via the Executor compile cache, so
  steady-state traffic never re-traces.  Results are de-padded and
  scattered back to each waiter.  Models whose feeds/fetches are not
  batch-major (ragged sequences, LoD outputs, reduced fetches — decided
  from verifier shape metadata) still serve; those requests execute
  solo at their exact shape, like the pre-batching server.
- **Replica pool** (``paddle_tpu/serving/replica.py``): ``--replicas=N``
  worker clones, each with its own Scope + Executor and zero shared
  mutable state (the ``pd_machine_clone`` shape), pulling batches from
  one queue so admission, batching, and XLA dispatch overlap.

Endpoints:
  GET  /health           → {"status": "ok", "feeds": [...], "fetches":
                           [...], "batching": {...}, "generation": {...}}
  GET  /metrics          → Prometheus text exposition (0.0.4): request
                           latency histogram, in-flight gauge, status
                           counters, serving_batch_size /
                           serving_queue_wait_seconds, plus the
                           executor's compile/step metrics
  GET  /stats            → the observability registry snapshot as JSON
                           (what `paddle stats --url=...` renders)
  POST /predict          → body {"<feed>": nested-list, ...}
                           → {"outputs": [nested-list per fetch]}
                           Unknown payload keys (other than ``@len``
                           side-feeds) are a 400 naming the key.
  POST /generate         → body {"src": [int ids], "max_new_tokens": N,
                           "stream": bool, "beam": k, "temperature":
                           t, "top_k": k, "seed": s} against a
                           paged-KV decode engine (paddle_tpu/decode).
                           With ``stream`` (default true) the reply is
                           chunked ndjson — one ``{"token": t}`` line
                           per generated token as the continuous-
                           batching session emits it, then a final
                           ``{"done": true, "ids": [...],
                           "finish_reason": ...}`` line; without it,
                           one JSON object after generation finishes.
                           ``beam`` (when the engine allows it) runs
                           beam search over copy-on-write sibling
                           slots and replies non-streamed with the
                           full ``"beams"`` list best-first;
                           ``temperature``/``top_k``/``seed`` switch
                           the slot to seeded sampling (top_k/seed
                           without temperature → 400, never silently
                           greedy).  Page-pool
                           exhaustion / full admission queue
                           → 503 (admission refusal, live sequences
                           unaffected); request deadline → 504.

Graceful degradation (bounded, not unbounded thread pileup):
  - ``max_inflight``: admission cap — requests beyond it are rejected
    immediately with 503 instead of queueing forever;
  - ``request_timeout``: per-request deadline — a request that does not
    complete before it expires returns 504 (and is dropped from the
    queue without burning a dispatch if it expires while queued);
  - clients that disconnect mid-response are counted, not crashed; a
    client that abandons a ``/generate`` stream mid-flight gets its
    decode slot cancelled (pages freed) instead of generating to a
    dead socket.
  All are counted in ``serving_rejected_total{reason=...}`` on
  ``/metrics`` (overload → 503, deadline → 504, client_gone).

Self-healing & multi-tenancy (PR 19):
  - requests carry a tenant id (``X-Tenant`` header or ``"tenant"``
    payload key; absent → ``"default"``); per-tenant token buckets
    turn one tenant's burst into *their* 429 ``tenant_over_quota``
    instead of everyone's 503, and weighted-fair dequeue keeps heavy
    tenants from starving light ones;
  - replicas are supervised: a dispatch that raises or outlives its
    lease marks the replica dead, its in-flight batch is requeued
    (bounded ``attempts``; a poison request is quarantined with 503
    ``retry_exhausted``), and a fresh replica is respawned with
    backoff under a restart-rate limit;
  - sustained pressure past ``shed_watermark`` sheds lowest-weight
    tenants first; ``/health`` flips to ``"degraded"`` with reasons
    while the pool is down replicas or shedding.

Cold start (PR 20): ``--artifacts=DIR`` boots replicas from a
``paddle compile`` export — every bucket-ladder program is
deserialized from the artifact store instead of traced+compiled, with
donation restored (see ``paddle_tpu/aot``).  Warmup wall time lands in
``serving_time_to_ready_seconds{boot=aot|jit|mixed}``.

Launch:  paddle serve --model_dir=DIR [--port=N]
                      [--replicas=N] [--max_batch=N]
                      [--batch_timeout_ms=MS] [--warmup]
                      [--artifacts=DIR]
                      [--request_timeout=SECONDS] [--max_inflight=N]
                      [--tenants=SPEC] [--max_attempts=N]
                      [--replica_heartbeat_ms=MS] [--chaos=KIND@N]
"""

from __future__ import annotations

import json
import queue as queue_mod
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import parse_qs, urlsplit

import numpy as np

from paddle_tpu import framework
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import recording, span
from paddle_tpu.serving.batching import (
    DEFAULT_TENANT,
    BatchSpec,
    PendingRequest,
    QueueShed,
    RequestQueue,
    RetryExhausted,
    TenantOverQuota,
    TenantRegistry,
    bucket_ladder,
    next_bucket,
)
from paddle_tpu.serving.replica import (
    FaultInjector,
    ModelBundle,
    Replica,
    ReplicaPool,
)

__all__ = [
    "BatchSpec", "FaultInjector", "InferenceServer", "ModelBundle",
    "PendingRequest", "QueueShed", "Replica", "ReplicaPool",
    "RequestQueue", "RetryExhausted", "TenantOverQuota",
    "TenantRegistry", "bucket_ladder", "next_bucket",
]

_M_REQ_SEC = _metrics.histogram(
    "serving_request_seconds",
    "wall time per inference request, including executor dispatch")
_M_INFLIGHT = _metrics.gauge(
    "serving_inflight_requests", "requests currently being handled")
_M_RESPONSES = _metrics.counter(
    "serving_responses_total", "HTTP responses by status code")
_M_FIRST_WRITE_LAG = _metrics.histogram(
    "serving_generate_first_write_lag_seconds",
    "a streamed /generate request's first token: emitted by the decode "
    "stepper to written on the socket by the handler thread")
_M_SUBMIT_LAG = _metrics.histogram(
    "serving_generate_submit_lag_seconds",
    "a /generate request's way through the front: from the handler's "
    "entry to the decode engine holding the request (the end of "
    "`serving.submit`): body parse, tenant admission, the submit")
# GET /trace records for at most this long: the ring is bounded, the
# handler thread sleeps meanwhile
TRACE_MAX_SECONDS = 60.0
_M_REJECTED = _metrics.counter(
    "serving_rejected_total",
    "requests shed for graceful degradation, by reason "
    "(overload -> 503, deadline -> 504, client_gone -> disconnect)")


def _jsonable(o):
    """Fetch value → JSON shape; LoD outputs become
    {"data": ..., "lod": [...]} (packed rows + offset tables)."""
    from paddle_tpu.lod import LoDArray

    if isinstance(o, LoDArray):
        return {"data": np.asarray(o.data).tolist(),
                "lod": [np.asarray(l).tolist() for l in o.lod]}
    return np.asarray(o).tolist()


class InferenceServer:
    def __init__(self, model_dir: Optional[str], port: int = 0,
                 request_timeout: float = None, max_inflight: int = None,
                 replicas: int = 1, max_batch: int = 8,
                 batch_timeout_ms: float = 0.0, warmup: bool = False,
                 generator=None, place=None, tenants=None,
                 max_attempts: int = 3,
                 replica_heartbeat_ms: float = 1000.0,
                 dispatch_timeout: float = None, chaos=None,
                 shed_watermark: int = None, artifacts: str = None):
        if model_dir is None and generator is None:
            raise ValueError("need a model_dir to predict from and/or a "
                             "generator (paddle_tpu.decode."
                             "GenerationEngine) to generate with")
        self._generator = generator
        self._bundle = ModelBundle(model_dir) if model_dir else None
        self.feed_names = (self._bundle.feed_names if self._bundle else [])
        self._fetches = (self._bundle.fetch_names if self._bundle else [])
        self._feed_set = frozenset(self.feed_names)
        if self._bundle is None:
            self._spec = BatchSpec.disabled(
                "generation-only server (no --model_dir export loaded)",
                code="generation_only")
        elif max_batch > 1:
            self._spec = self._bundle.batch_spec()
        else:
            self._spec = BatchSpec.disabled(
                "coalescing off (max_batch <= 1): every request runs at "
                "its exact feed shape", code="coalescing_off")
        if isinstance(tenants, str):
            tenants = TenantRegistry.parse(tenants)
        self._tenants = tenants if tenants is not None else TenantRegistry()
        if shed_watermark is None:
            # deep enough that normal bursts never shed, shallow enough
            # that a collapsing pool rejects instead of queueing forever
            shed_watermark = max(64, 8 * max_batch)
        self.fault = (FaultInjector.from_spec(chaos)
                      if isinstance(chaos, str) else chaos)
        self._artifact_store = None
        self._aot_attached = False
        if artifacts:
            # `paddle compile` output: replicas consult the store before
            # tracing; any manifest mismatch is a loud JIT fallback
            # (aot_load_total{result=rejected_*}), never a wrong answer
            from paddle_tpu import aot as _aot

            self._artifact_store = _aot.ArtifactStore(artifacts)
            if generator is not None:
                # the decode engine builds its executors deep inside the
                # model — attach process-globally so they see the store
                _aot.attach(self._artifact_store)
                self._aot_attached = True
        self._queue = RequestQueue(max_batch=max_batch,
                                   batch_timeout=batch_timeout_ms / 1000.0,
                                   tenants=self._tenants,
                                   shed_watermark=shed_watermark)
        self._pool = (ReplicaPool(self._bundle, self._queue, self._spec,
                                  replicas=replicas, place=place,
                                  fault=self.fault,
                                  max_attempts=max_attempts,
                                  heartbeat=replica_heartbeat_ms / 1000.0,
                                  dispatch_timeout=dispatch_timeout,
                                  artifact_store=self._artifact_store)
                      if self._bundle else None)
        self._request_timeout = request_timeout
        self._max_inflight = max_inflight
        self._trace_lock = threading.Lock()   # one /trace at a time
        self._slots = (threading.BoundedSemaphore(max_inflight)
                       if max_inflight else None)
        if warmup and self._pool is not None:
            self._pool.warmup()
        if isinstance(chaos, str) and self.fault is not None:
            # spec-string chaos is the operator path (--chaos=die@1):
            # nobody else can arm it, so arm now — after warmup, so the
            # nth dispatch counts live traffic, not compile traffic
            self.fault.arm()

        server = self

        class Handler(BaseHTTPRequestHandler):
            # keep-alive: one TCP connection per load-test client, not
            # one per request (we always send Content-Length)
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):  # quiet
                pass

            def _reply(self, code, obj, ctype="application/json",
                       raw=None):
                body = raw if raw is not None else json.dumps(obj).encode()
                _M_RESPONSES.inc(code=str(code))
                try:
                    self.send_response(code)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError):
                    # load-test client hung up mid-response: count it,
                    # don't spam stderr or kill the handler thread
                    _M_REJECTED.inc(reason="client_gone")
                    self.close_connection = True

            def do_GET(self):
                if self.path == "/health":
                    reasons = server.degraded_reasons()
                    self._reply(200, {
                        "status": "degraded" if reasons else "ok",
                        "reasons": reasons,
                        "device": framework.device_record(),
                        "self_healing": server.self_healing_info(),
                        "feeds": server.feed_names,
                        "fetches": [getattr(f, "name", str(f))
                                    for f in server._fetches],
                        "batching": server.batching_info(),
                        "aot": server.aot_info(),
                        "generation": (server._generator.info()
                                       if server._generator else None)})
                elif self.path == "/metrics":
                    self._reply(
                        200, None,
                        ctype="text/plain; version=0.0.4; charset=utf-8",
                        raw=_metrics.render_prometheus().encode())
                elif self.path == "/stats":
                    self._reply(200, _metrics.snapshot())
                elif urlsplit(self.path).path == "/trace":
                    self._handle_trace(urlsplit(self.path).query)
                else:
                    self._reply(404, {"error": "unknown path"})

            def _handle_trace(self, query: str) -> None:
                """GET /trace?seconds=N: record every span of this
                process for N seconds (capped) and reply with the
                ring's Chrome-trace JSON."""
                try:
                    seconds = float(
                        parse_qs(query).get("seconds", ["1"])[0])
                except ValueError:
                    seconds = -1.0
                if not 0 <= seconds <= TRACE_MAX_SECONDS:
                    self._reply(400, {
                        "error": "'seconds' must be a number in "
                                 f"[0, {TRACE_MAX_SECONDS}]"})
                    return
                if not server._trace_lock.acquire(blocking=False):
                    self._reply(409, {"error": "a /trace recording is "
                                      "already running"})
                    return
                try:
                    with recording() as ring:
                        time.sleep(seconds)
                        doc = ring.to_chrome_trace()
                finally:
                    server._trace_lock.release()
                self._reply(200, doc)

            def do_POST(self):
                # always consume the body first: on keep-alive
                # (HTTP/1.1) an unread body would be parsed as the
                # next request line, desyncing the connection for
                # every reply sent before rfile.read — 404s and 503s
                # included
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    raw_body = self.rfile.read(n)
                except (BrokenPipeError, ConnectionResetError):
                    _M_REJECTED.inc(reason="client_gone")
                    self.close_connection = True
                    return
                if self.path == "/generate":
                    self._handle_generate(raw_body)
                    return
                if self.path != "/predict":
                    self._reply(404, {"error": "unknown path"})
                    return
                if server._slots is not None and \
                        not server._slots.acquire(blocking=False):
                    # shed load at admission: a bounded 503 beats an
                    # unbounded request pileup in the batching queue
                    _M_REJECTED.inc(reason="overload")
                    self._reply(503, {"error": "server overloaded "
                                      f"(max_inflight={server._max_inflight})"})
                    return
                with span("serving.predict"):
                    self._handle_predict(raw_body)

            def _handle_predict(self, raw_body: bytes) -> None:
                _M_INFLIGHT.inc()
                t0 = time.perf_counter()
                tenant = (self.headers.get("X-Tenant")
                          or DEFAULT_TENANT).strip() or DEFAULT_TENANT
                try:
                    payload = json.loads(raw_body or b"{}")
                    if isinstance(payload, dict) and "tenant" in payload:
                        tenant = str(payload.pop("tenant")) or tenant
                    deadline = (time.monotonic() + server._request_timeout
                                if server._request_timeout else None)
                    outs = server.predict(payload, deadline=deadline,
                                          tenant=tenant)
                    self._reply(200, {"outputs": [_jsonable(o)
                                                  for o in outs]})
                except TenantOverQuota as e:
                    _M_REJECTED.inc(reason="tenant_over_quota",
                                    tenant=e.tenant)
                    self._reply(429, {"error": str(e),
                                      "reason": "tenant_over_quota",
                                      "tenant": e.tenant})
                except QueueShed as e:
                    _M_REJECTED.inc(reason=e.reason, tenant=tenant)
                    self._reply(503, {"error": str(e),
                                      "reason": e.reason})
                except RetryExhausted as e:
                    _M_REJECTED.inc(reason="retry_exhausted",
                                    tenant=tenant)
                    self._reply(503, {"error": str(e),
                                      "reason": "retry_exhausted"})
                except TimeoutError as e:
                    _M_REJECTED.inc(reason="deadline")
                    self._reply(504, {"error": str(e)})
                except (BrokenPipeError, ConnectionResetError):
                    _M_REJECTED.inc(reason="client_gone")
                    self.close_connection = True
                except (KeyError, ValueError, TypeError) as e:
                    self._reply(400, {"error": str(e)})
                except Exception as e:  # surface, don't kill the server
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                finally:
                    dt = time.perf_counter() - t0
                    _M_INFLIGHT.dec()
                    if server._slots is not None:
                        server._slots.release()
                    _M_REQ_SEC.observe(dt, endpoint="/predict")

            # -- generation (paged-KV decode engine) ---------------------

            def _chunk(self, obj) -> None:
                data = json.dumps(obj).encode() + b"\n"
                self.wfile.write(f"{len(data):X}\r\n".encode()
                                 + data + b"\r\n")

            def _handle_generate(self, raw_body: bytes) -> None:
                self._entered = time.perf_counter()
                from paddle_tpu.decode.session import next_rid

                if server._generator is None:
                    self._reply(400, {"error": "no generation engine "
                                      "mounted (serve with --gen_config)"})
                    return
                rid = next_rid()
                with span("serving.generate", rid=rid) as gen_span:
                    self._generate(raw_body, rid, gen_span)

            def _submitted(self) -> None:
                """The engine holds this handler's request."""
                _M_SUBMIT_LAG.observe(time.perf_counter() - self._entered)

            def _generate(self, raw_body: bytes, rid: int,
                          gen_span) -> None:
                """One /generate request inside its ``serving.generate``
                span; ``rid`` goes on every span the request causes,
                here and in the decode engine."""
                from paddle_tpu.decode import AdmissionRefused

                _M_INFLIGHT.inc()
                t0 = time.perf_counter()
                tenant = (self.headers.get("X-Tenant")
                          or DEFAULT_TENANT).strip() or DEFAULT_TENANT
                try:
                    with span("serving.parse", rid=rid):
                        payload = json.loads(raw_body or b"{}")
                        if not isinstance(payload, dict):
                            raise ValueError(
                                "request body must be a JSON object")
                        if "tenant" in payload:
                            tenant = str(payload.pop("tenant")) or tenant
                        src = payload.get("src")
                        if (not isinstance(src, list) or not src
                                or not all(isinstance(t, int)
                                           for t in src)):
                            raise ValueError(
                                "'src' must be a non-empty list of int "
                                "ids")
                        unknown = set(payload) - {
                            "src", "max_new_tokens", "stream", "beam",
                            "temperature", "top_k", "seed"}
                        if unknown:
                            raise ValueError(
                                f"unknown payload key "
                                f"{sorted(unknown)[0]!r}; expected src / "
                                "max_new_tokens / stream / beam / "
                                "temperature / top_k / seed / tenant")
                    gen_span.set(tenant=tenant)
                    # same token buckets as /predict: a generation call
                    # spends one admission token for its tenant
                    server._tenants.admit(tenant)
                    budget = payload.get("max_new_tokens")
                    beam = payload.get("beam")
                    deadline = (time.monotonic() + server._request_timeout
                                if server._request_timeout else None)
                    # grace past the deadline: the session itself
                    # expires the request and reports it
                    timeout = (None if deadline is None else
                               max(0.0, deadline - time.monotonic())
                               + 30.0)
                    if beam is not None:
                        if (not isinstance(beam, int) or beam < 1
                                or isinstance(beam, bool)):
                            raise ValueError(
                                "'beam' must be a positive int")
                        with span("serving.submit", rid=rid):
                            req = server._generator.submit_beam(
                                src, beam_size=beam, max_new_tokens=budget,
                                deadline=deadline, rid=rid)
                        self._submitted()
                        ids = req.result(timeout)
                        self._reply(200, {
                            "ids": ids,
                            "beams": [{"score": s, "ids": t}
                                      for s, t in (req.beams or [])],
                            "finish_reason": req.finish_reason})
                    elif payload.get("stream", True):
                        self._stream_generate(src, budget, deadline,
                                              payload, rid)
                    else:
                        with span("serving.submit", rid=rid):
                            req = server._generator.submit(
                                src, budget, deadline=deadline,
                                temperature=payload.get("temperature"),
                                top_k=payload.get("top_k"),
                                seed=payload.get("seed"), rid=rid)
                        self._submitted()
                        ids = req.result(timeout)
                        self._reply(200, {
                            "ids": ids,
                            "finish_reason": req.finish_reason})
                except TenantOverQuota as e:
                    _M_REJECTED.inc(reason="tenant_over_quota",
                                    tenant=e.tenant)
                    self._reply(429, {"error": str(e),
                                      "reason": "tenant_over_quota",
                                      "tenant": e.tenant})
                except AdmissionRefused as e:
                    _M_REJECTED.inc(reason=e.reason)
                    self._reply(503, {"error": str(e),
                                      "reason": e.reason})
                except TimeoutError as e:
                    _M_REJECTED.inc(reason="deadline")
                    self._reply(504, {"error": str(e)})
                except (BrokenPipeError, ConnectionResetError):
                    _M_REJECTED.inc(reason="client_gone")
                    self.close_connection = True
                except (KeyError, ValueError, TypeError) as e:
                    self._reply(400, {"error": str(e)})
                except Exception as e:
                    self._reply(500, {"error": f"{type(e).__name__}: {e}"})
                finally:
                    dt = time.perf_counter() - t0
                    _M_INFLIGHT.dec()
                    _M_REQ_SEC.observe(dt, endpoint="/generate")

            def _stream_generate(self, src, budget, deadline,
                                 payload, rid) -> None:
                """Chunked ndjson: one line per token as the decode
                session emits it, then the summary line.  Admission
                refusals (503) and pre-stream deadline expiry (504)
                raise BEFORE any header is written; once tokens are
                flowing, a mid-stream expiry rides the final line as
                ``finish_reason: "deadline"`` (the status is already
                on the wire)."""
                q: queue_mod.Queue = queue_mod.Queue()
                with span("serving.submit", rid=rid):
                    req = server._generator.submit(
                        src, budget, on_token=q.put, deadline=deadline,
                        temperature=payload.get("temperature"),
                        top_k=payload.get("top_k"),
                        seed=payload.get("seed"), rid=rid)
                self._submitted()
                if deadline is not None:
                    # hold the 200 until the stream actually starts:
                    # a request that dies of its deadline before its
                    # first token must be the documented 504, not a
                    # 200 that trickles out an error line
                    while (req.first_token_at is None
                           and not req.wait(0.01)):
                        pass
                    if req.first_token_at is None and req.done:
                        if isinstance(req.error, TimeoutError):
                            raise req.error
                        if req.error is not None:
                            raise req.error
                _M_RESPONSES.inc(code="200")
                try:
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    first = True
                    while True:
                        try:
                            token = q.get(timeout=0.05)
                        except queue_mod.Empty:
                            if req.done and q.empty():
                                break
                            continue
                        if not first:
                            self._chunk({"token": token})
                            continue
                        first = False
                        with span("serving.first_write", rid=rid):
                            self._chunk({"token": token})
                        # the handler waking up behind the stepper
                        _M_FIRST_WRITE_LAG.observe(
                            time.monotonic() - req.first_token_at)
                    final = {"done": True, "ids": req.tokens,
                             "finish_reason": req.finish_reason}
                    if req.error is not None:
                        final["error"] = str(req.error)
                    self._chunk(final)
                    self.wfile.write(b"0\r\n\r\n")
                except (BrokenPipeError, ConnectionResetError):
                    # the consumer left: cancel the decode slot so its
                    # pages free now instead of generating the rest of
                    # the sequence into a dead socket
                    server._generator.cancel(req)
                    _M_REJECTED.inc(reason="client_gone")
                    self.close_connection = True

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    # -- introspection ------------------------------------------------------

    @property
    def address(self):
        host, port = self._httpd.server_address[:2]
        return f"{host}:{port}"

    @property
    def port(self):
        return self._httpd.server_address[1]

    def degraded_reasons(self) -> list:
        """Machine-readable reasons /health is ``degraded`` (empty =
        healthy): dead replicas, exhausted restart budget, active load
        shedding."""
        reasons = []
        if self._pool is not None:
            reasons.extend(self._pool.degraded_reasons())
        deg = self._queue.degradation()
        if deg.get("shedding"):
            reasons.append(f"load_shedding:{deg['shedding']}")
        return reasons

    def self_healing_info(self) -> dict:
        return {
            "pool": self._pool.info() if self._pool else None,
            "tenants": self._tenants.info(),
            "queue": self._queue.degradation(),
        }

    def aot_info(self) -> Optional[dict]:
        """Artifact-store state for /health: root, poison reason, entry
        count, per-outcome lookup results, and the pool's boot source."""
        if self._artifact_store is None:
            return None
        info = self._artifact_store.info()
        info["boot"] = (self._pool.boot_source()
                        if self._pool is not None else None)
        return info

    def batching_info(self) -> dict:
        return {
            "enabled": self._spec.batchable,
            "reason": self._spec.reason,
            "replicas": len(self._pool.replicas) if self._pool else 0,
            "max_batch": self._queue.max_batch,
            "batch_timeout_ms": self._queue.batch_timeout * 1000.0,
            "buckets": (list(bucket_ladder(self._queue.max_batch))
                        if self._spec.batchable else []),
        }

    # -- serving ------------------------------------------------------------

    def _build_feeds(self, payload: dict) -> dict:
        # the executor casts every feed to its declared dtype
        # (_convert_feed), so raw np.asarray is enough here
        feed = {}
        for name in self.feed_names:
            if name not in payload:
                raise KeyError(f"missing feed {name!r}")
        for k, v in payload.items():
            if k in self._feed_set or k.endswith("@len"):
                # lengths side-feeds ride along with declared feeds
                feed[k] = np.asarray(v)
            else:
                # a mis-keyed request must not silently drop data (and
                # must never be coalesced into someone else's bucket)
                raise ValueError(
                    f"unknown payload key {k!r}; expected feeds "
                    f"{sorted(self._feed_set)} (plus optional '@len' "
                    "side-feeds)")
        return feed

    def predict(self, payload: dict, deadline: float = None,
                tenant: str = DEFAULT_TENANT):
        """Run one request through the batching engine.  ``deadline``
        (a ``time.monotonic`` timestamp) bounds the *whole* wait —
        queueing and execution; an expired request raises TimeoutError
        (504 over HTTP) instead of stacking up behind busy replicas.
        ``tenant`` selects the admission token bucket and fair-queue
        weight (429/503 raised here as TenantOverQuota/QueueShed)."""
        if self._bundle is None:
            raise ValueError("this server mounts no inference export "
                             "(generation-only; POST /generate instead)")
        feed = self._build_feeds(payload)
        info = self._spec.classify(feed)
        if info is None:
            # model-level unbatchability carries the BatchSpec code;
            # a batchable model whose request shapes didn't line up is
            # a per-request miss
            reason = (self._spec.code if not self._spec.batchable
                      else "shape_mismatch")
            req = PendingRequest(feed, rows=1, batchable=False,
                                 deadline=deadline, solo_reason=reason,
                                 tenant=tenant)
        else:
            rows, cast = info
            req = PendingRequest(cast, rows=rows, batchable=True,
                                 deadline=deadline, tenant=tenant)
        self._queue.submit(req)
        timeout = None
        if deadline is not None:
            timeout = max(0.0, deadline - time.monotonic())
        if not req.wait(timeout):
            req.abandoned = True
            raise TimeoutError(
                "request deadline expired waiting for a serving replica")
        if req.error is not None:
            raise req.error
        return list(req.outputs)

    # -- lifecycle ----------------------------------------------------------

    def warmup(self):
        """Pre-compile the bucket ladder on every replica."""
        return self._pool.warmup() if self._pool else 0

    def pause(self):
        """Stop replicas taking new batches (drain/maintenance); queued
        requests wait (and expire against their deadlines)."""
        if self._pool:
            self._pool.pause()

    def resume(self):
        if self._pool:
            self._pool.resume()

    def stop(self):
        self._httpd.shutdown()
        if self._pool:
            self._pool.stop()
        if self._generator is not None:
            self._generator.stop()
        if self._aot_attached:
            from paddle_tpu import aot as _aot

            _aot.detach()
            self._aot_attached = False
        self._httpd.server_close()
