"""Self-healing serving tests (ISSUE 19): per-tenant admission
(token-bucket 429s, weighted-fair dequeue), supervised replicas
(injected deaths/hangs requeue their in-flight batch onto a respawned
replica; poison requests are quarantined), pressure shedding with a
degraded /health, decode step-failure containment, and mid-stream
disconnect cancellation."""

import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.distributed.retry import RetryPolicy
from paddle_tpu.serving import FaultInjector, InferenceServer
from paddle_tpu.serving.batching import (
    PendingRequest,
    QueueShed,
    RequestQueue,
    TenantOverQuota,
    TenantQuota,
    TenantRegistry,
)
from paddle_tpu.serving import replica as replica_mod


@pytest.fixture
def model_dir(tmp_path):
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.fc(input=x, size=2, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(str(tmp_path / "model"), ["x"], [y], exe)
    return str(tmp_path / "model")


def _post(addr, payload, headers=None, timeout=30):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        f"http://{addr}/predict", data=json.dumps(payload).encode(),
        headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _get_json(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=30) as r:
        return json.loads(r.read())


def _wait_for(cond, timeout=15.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- RetryPolicy.for_attempt (satellite) ------------------------------------


def test_for_attempt_backoff_and_jitter_bounds():
    p = RetryPolicy(base_delay=0.1, max_delay=1.0, multiplier=2.0,
                    jitter=0.25)
    for n in range(8):
        d = min(0.1 * 2.0 ** n, 1.0)
        for _ in range(20):
            v = p.for_attempt(n)
            assert d * 0.75 - 1e-9 <= v <= d * 1.25 + 1e-9
    exact = RetryPolicy(base_delay=0.1, max_delay=1.0, multiplier=2.0,
                        jitter=0.0)
    assert exact.for_attempt(0) == pytest.approx(0.1)
    assert exact.for_attempt(3) == pytest.approx(0.8)
    assert exact.for_attempt(10) == pytest.approx(1.0)   # capped
    assert list(exact.delays()) == [exact.for_attempt(i)
                                    for i in range(exact.max_attempts - 1)]


# -- tenant quotas ----------------------------------------------------------


def test_token_bucket_charges_and_refuses():
    reg = TenantRegistry.parse("A:10:2:1")
    reg.admit("A")
    reg.admit("A")
    with pytest.raises(TenantOverQuota) as ei:
        reg.admit("A")
    assert ei.value.tenant == "A"
    # unconfigured tenants inherit the unmetered template
    for _ in range(50):
        reg.admit("anyone-else")


def test_idle_tenant_tokens_capped_at_burst():
    q = TenantQuota("x", rate=100.0, burst=5.0)
    q.tokens = 0.0
    q._last -= 60.0           # an hour of idle would refill 6000 tokens
    assert q.available() == pytest.approx(5.0)   # never past one burst


def test_tenant_over_quota_http_429_and_metric(model_dir):
    srv = InferenceServer(model_dir, tenants="A:0.05:1")
    try:
        body = {"x": [[1.0, 2.0, 3.0, 4.0]]}
        code, _ = _post(srv.address, body, headers={"X-Tenant": "A"})
        assert code == 200
        code, doc = _post(srv.address, body, headers={"X-Tenant": "A"})
        assert code == 429
        assert doc["reason"] == "tenant_over_quota" and doc["tenant"] == "A"
        # payload key works too, and other tenants are unaffected
        code, doc = _post(srv.address, dict(body, tenant="A"))
        assert code == 429
        assert _post(srv.address, dict(body, tenant="B"))[0] == 200
        from paddle_tpu.serving import _M_REJECTED

        assert _M_REJECTED.value(reason="tenant_over_quota",
                                 tenant="A") == 2
    finally:
        srv.stop()


# -- weighted-fair dequeue (satellite property test) ------------------------


def test_weighted_fair_dequeue_converges_to_weight_ratio():
    reg = TenantRegistry.parse("A:::1,B:::2,C:::4")
    q = RequestQueue(max_batch=1, tenants=reg)
    reqs = {}
    for i in range(30):
        for tenant in ("A", "B", "C"):
            r = PendingRequest({"x": i}, rows=1, batchable=True,
                               tenant=tenant)
            q.submit(r)
            reqs.setdefault(tenant, []).append(r)
    counts = {"A": 0, "B": 0, "C": 0}
    order = []
    for _ in range(21):
        (req,) = q.take()
        counts[req.tenant] += 1
        order.append(req.tenant)
    # virtual finish times are rows/weight apart: in any saturated
    # window the dispatch share is exactly the weight ratio 1:2:4
    assert counts == {"A": 3, "B": 6, "C": 12}
    assert counts["C"] >= 3 * counts["A"]        # acceptance bound
    assert counts["A"] > 0                       # no starvation
    # an idle tenant enters at the queue's virtual NOW — no banked
    # credit lets it leapfrog the backlog's earned order
    vclock = q._vclock
    late = PendingRequest({"x": 99}, rows=1, batchable=True, tenant="D")
    q.submit(late)
    assert late._vft >= vclock


def test_single_tenant_is_plain_fifo():
    q = RequestQueue(max_batch=1)
    reqs = [PendingRequest({"i": i}, rows=1, batchable=True)
            for i in range(10)]
    for r in reqs:
        q.submit(r)
    got = [q.take()[0] for _ in range(10)]
    assert got == reqs


# -- supervised replicas ----------------------------------------------------


def test_replica_death_requeues_inflight_and_respawns(model_dir):
    fault = FaultInjector("die", nth=1)
    srv = InferenceServer(model_dir, replicas=2, replica_heartbeat_ms=50,
                          chaos=fault)
    try:
        body = {"x": [[1.0, 2.0, 3.0, 4.0]]}
        assert _post(srv.address, body)[0] == 200   # warm compile cache
        fault.arm()
        results = []

        def one():
            results.append(_post(srv.address, body))

        threads = [threading.Thread(target=one) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        # the killed dispatch's requests were requeued and completed on
        # a surviving/respawned replica — nothing was lost
        assert [code for code, _ in results] == [200] * 6
        assert fault.fired == 1
        assert replica_mod._M_DEATHS.value(cause="injected") == 1
        assert replica_mod._M_REQUEUED.value() >= 1
        assert _wait_for(lambda: len(srv._pool.replicas) == 2)
        # counted after the replica is in the pool, outside its lock
        assert _wait_for(lambda: replica_mod._M_RESTARTS.value() >= 1)
        health = _get_json(srv.address, "/health")
        assert health["status"] == "ok"
        assert health["self_healing"]["pool"]["live"] == 2
        assert health["self_healing"]["pool"]["restarts"] >= 1
    finally:
        srv.stop()


def test_poison_request_quarantined_after_max_attempts(model_dir):
    # every armed dispatch raises: the request kills a replica per
    # attempt and must be quarantined after max_attempts, not
    # redispatched forever
    fault = FaultInjector("raise", nth=1, repeat=True)
    srv = InferenceServer(model_dir, replicas=2, max_attempts=2,
                          replica_heartbeat_ms=50, chaos=fault)
    try:
        body = {"x": [[1.0, 2.0, 3.0, 4.0]]}
        assert _post(srv.address, body)[0] == 200
        fault.arm()
        code, doc = _post(srv.address, body)
        assert code == 503
        assert doc["reason"] == "retry_exhausted"
        assert "quarantined" in doc["error"]
        fault.disarm()
        assert replica_mod._M_DEATHS.value(cause="exception") == 2
        # the pool heals and keeps serving everyone else
        assert _wait_for(lambda: len(srv._pool.replicas) >= 1)
        assert _post(srv.address, body)[0] == 200
        from paddle_tpu.serving import _M_REJECTED

        assert _M_REJECTED.value(reason="retry_exhausted",
                                 tenant="default") == 1
    finally:
        srv.stop()


def test_hung_dispatch_detected_via_lease_and_request_survives(model_dir):
    fault = FaultInjector("hang", nth=1, hang_s=2.0)
    srv = InferenceServer(model_dir, replicas=1, replica_heartbeat_ms=50,
                          dispatch_timeout=0.4, chaos=fault)
    try:
        body = {"x": [[1.0, 2.0, 3.0, 4.0]]}
        assert _post(srv.address, body)[0] == 200
        fault.arm()
        t0 = time.monotonic()
        code, _ = _post(srv.address, body)
        # the supervisor swept the hung lease at ~0.4s, requeued the
        # batch, and a respawned replica finished it — well before the
        # 2s hang (and without the client ever seeing an error)
        assert code == 200
        assert time.monotonic() - t0 < 2.0
        assert replica_mod._M_DEATHS.value(cause="hang") == 1
        assert _wait_for(lambda: replica_mod._M_RESTARTS.value() >= 1)
    finally:
        srv.stop()


def test_request_level_errors_do_not_kill_the_replica(model_dir):
    srv = InferenceServer(model_dir, replicas=1)
    try:
        # wrong trailing shape -> solo dispatch fails with a
        # request-level error; the replica must survive it
        code, _ = _post(srv.address, {"x": [[1.0, 2.0]]})
        assert code in (400, 500)
        assert replica_mod._M_DEATHS.value() == 0
        assert len(srv._pool.replicas) == 1
        assert _post(srv.address, {"x": [[1.0, 2.0, 3.0, 4.0]]})[0] == 200
    finally:
        srv.stop()


def test_fault_injector_spec_parsing():
    f = FaultInjector.from_spec("die@5")
    assert (f.kind, f.nth, f.replica) == ("die", 5, None)
    f = FaultInjector.from_spec("hang@3:r1")
    assert (f.kind, f.nth, f.replica) == ("hang", 3, 1)
    f = FaultInjector.from_spec("raise")
    assert (f.kind, f.nth) == ("raise", 1)
    with pytest.raises(ValueError):
        FaultInjector.from_spec("explode@2")
    # disarmed by default: dispatches before arm() never count
    f = FaultInjector("raise", nth=1)
    f.before_dispatch(0)
    f.arm()
    with pytest.raises(RuntimeError):
        f.before_dispatch(0)


def test_chaos_spec_string_is_armed_by_the_server(model_dir):
    # --chaos=SPEC is the operator path: nobody can call arm() on it,
    # so the server must arm it itself once warmup is done
    srv = InferenceServer(model_dir, replicas=2, replica_heartbeat_ms=50,
                          warmup=True, chaos="die@1")
    try:
        assert srv.fault._armed
        assert _post(srv.address, {"x": [[1.0, 2.0, 3.0, 4.0]]})[0] == 200
        assert srv.fault.fired == 1
        assert _wait_for(
            lambda: len(srv._pool.replicas) == 2
            and srv._pool.info()["restarts"] >= 1)
    finally:
        srv.stop()


# -- pressure shedding + degraded /health -----------------------------------


def test_shedding_rejects_low_weight_tenants_first(model_dir):
    srv = InferenceServer(model_dir, tenants="hi:::4,lo:::1",
                          shed_watermark=4)
    try:
        body = {"x": [[1.0, 2.0, 3.0, 4.0]]}
        assert _post(srv.address, dict(body, tenant="hi"))[0] == 200
        srv.pause()
        junk = []
        for _ in range(4):
            r = PendingRequest(
                {"x": np.ones((1, 4), np.float32)}, rows=1,
                batchable=True, tenant="hi")
            srv._queue.submit(r)
            junk.append(r)
        # past the watermark: low-weight tenants shed, top weight rides
        code, doc = _post(srv.address, dict(body, tenant="lo"), timeout=10)
        assert code == 503 and doc["reason"] == "shed_low_weight"
        with pytest.raises(QueueShed):
            srv._queue.submit(PendingRequest(
                {"x": np.ones((1, 4), np.float32)}, rows=1,
                batchable=True, tenant="lo"))
        for _ in range(4):
            r = PendingRequest(
                {"x": np.ones((1, 4), np.float32)}, rows=1,
                batchable=True, tenant="hi")
            srv._queue.submit(r)
            junk.append(r)
        # at 2x the watermark everyone sheds — bounded collapse
        code, doc = _post(srv.address, dict(body, tenant="hi"), timeout=10)
        assert code == 503 and doc["reason"] == "queue_collapse"
        health = _get_json(srv.address, "/health")
        assert health["status"] == "degraded"
        assert any(r.startswith("load_shedding:") for r in
                   health["reasons"])
        assert health["self_healing"]["queue"]["shedding"] is not None
        for r in junk:
            r.abandoned = True
        srv.resume()
        assert _wait_for(lambda: srv._queue.depth() == 0)
        assert _post(srv.address, dict(body, tenant="lo"))[0] == 200
        assert _get_json(srv.address, "/health")["status"] == "ok"
    finally:
        srv.stop()


# -- decode step containment (satellite) ------------------------------------


class _FlakyDecode:
    """TinyDecoderLM wrapper whose decode raises for the first
    ``fail_times`` calls (then heals)."""

    def __init__(self, inner, fail_times):
        self._inner = inner
        self.fail_left = fail_times

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, *a, **kw):
        if self.fail_left > 0:
            self.fail_left -= 1
            raise RuntimeError("injected decode failure")
        return self._inner.decode(*a, **kw)


def _tiny_lm(seed):
    from paddle_tpu.decode.model import TinyDecoderLM

    return TinyDecoderLM(vocab=16, d_model=8, num_heads=2, num_layers=1,
                         num_pages=8, page_size=4, pages_per_seq=2,
                         seed=seed)


def test_decode_step_failure_requeues_once_then_completes():
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession

    model = _FlakyDecode(_tiny_lm(7), fail_times=1)
    sess = DecodeSession(model, max_slots=2)
    req = sess.submit(DecodeRequest([1, 2, 3], max_new_tokens=4))
    sess.run(max_steps=100)
    assert req.finish_reason in ("eos", "length")
    assert len(req.result(0)) > 0
    assert req.step_failures == 1
    assert model.allocator.pages_in_use == 0


def test_decode_request_failing_twice_is_quarantined_503():
    from paddle_tpu.decode.session import (AdmissionRefused, DecodeRequest,
                                           DecodeSession)

    model = _FlakyDecode(_tiny_lm(8), fail_times=10**9)
    sess = DecodeSession(model, max_slots=2)
    req = sess.submit(DecodeRequest([1, 2], max_new_tokens=4))
    sess.run(max_steps=100)       # converges: quarantined after 2 strikes
    with pytest.raises(AdmissionRefused) as ei:
        req.result(0)
    assert ei.value.reason == "step_failed"
    assert req.step_failures == 2
    assert model.allocator.pages_in_use == 0
    # the session (and its stepper, in serving) lives on for others
    model.fail_left = 0
    ok = sess.submit(DecodeRequest([1, 4], max_new_tokens=3))
    sess.run(max_steps=100)
    assert len(ok.result(0)) > 0
    assert model.allocator.pages_in_use == 0


# -- a donated program that fails has consumed its pools (ISSUE 27) ---------


class _LostAtTheWait:
    """In place of a step's ids: the dispatch went through, the wait
    for the result is where the device's failure shows.  ``ids``: what
    a step dispatched behind this one is fed, as the device would."""

    def __init__(self, ids):
        self.ids = ids

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("injected: the device halted")


def _lose_pools_on_next_call(monkeypatch, name, at="call"):
    """``decode/model.py``'s jitted program ``name`` fails on its next
    call after consuming the pools it was given, as a donated call that
    fails on the device does; later calls run.  ``at="wait"`` (the
    decode step): the call returns, as a dispatch does, and the failure
    shows when the step's ids are waited for."""
    from paddle_tpu.decode import model as dm

    real, failed = getattr(dm, name), []

    def program(*args, **kw):
        args = [a.ids if isinstance(a, _LostAtTheWait) else a for a in args]
        if not failed:
            failed.append(True)
            first = 0 if name == "_copy_pools_page" else 1
            if at == "wait":
                logits, k, v, report, ids, *more = real(*args, **kw)
                assert all(p.is_deleted() for p in args[first:first + 2])
                return (logits, k, v, report, _LostAtTheWait(ids), *more)
            for pool in args[first:first + 2]:
                pool.delete()
            raise RuntimeError("injected: the device halted")
        return real(*args, **kw)

    monkeypatch.setattr(dm, name, program)


def _paged_lm(seed):
    from paddle_tpu.decode.model import TinyDecoderLM

    return TinyDecoderLM(vocab=16, d_model=8, num_heads=2, num_layers=2,
                         num_pages=24, page_size=4, pages_per_seq=5,
                         seed=seed)


@pytest.mark.parametrize("program", [
    "_decode_step", "_decode_step:wait", "_decode_step:wait+admission",
    "_decode_step:wait+queued", "_prefill_bucket", "_prefill_chunk",
    "_copy_pools_page"])
def test_program_that_consumed_its_pools_and_failed(monkeypatch, program):
    """The pools are made anew and counted once; every seated sequence
    goes back by the strike rule and completes from a fresh prefill with
    the oracle's tokens; the prefix index is dropped, so no later hit is
    served from a page whose rows are gone; every page comes back.  A
    decode step fails at its dispatch or, ``:wait``, at the collect a
    tick later — there also with a request that arrived while the step
    was in flight and is admitted in the tick that contains it, and
    (``+queued``: two lanes, both seated) with the next step queued
    behind it on the pools it handed on: that one is dropped, never
    collected, and the pools are still made anew once."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.decode.paged_kv import PoolsLost
    from paddle_tpu.decode.prefix import PrefixCache
    from paddle_tpu.decode.session import (AdmissionRefused, BeamRequest,
                                           DecodeRequest, DecodeSession)

    program, _, at = program.partition(":")
    lm = _paged_lm(11)
    cache = PrefixCache(lm.allocator, lm.page_size)
    sess = DecodeSession(lm, max_slots=2 if at.endswith("queued") else 4,
                         prefix_cache=cache)
    shared = [1, 5, 9, 3, 7, 2, 8, 4]              # two full pages
    p_a, p_b, p_c = shared + [6], shared + [11, 12], [1, 13, 14]
    want = {tuple(p): lm.dense_greedy(p, 5) for p in (p_a, p_b, p_c)}
    rebuilds = dm._M_POOL_REBUILDS.value
    n0 = rebuilds()

    a = sess.submit(DecodeRequest(p_a, max_new_tokens=5))
    sess.run(max_steps=50)
    assert a.result(0) == want[tuple(p_a)] and cache.cached_pages == 2
    # B hits A's two pages and is seated beside C; then the fault
    b = sess.submit(DecodeRequest(p_b, max_new_tokens=5))
    c = sess.submit(DecodeRequest(p_c, max_new_tokens=5))
    # the request whose own program fails (the decode step has none): a
    # fresh prompt, one that hits the cache, and a beam, whose members
    # share the prompt's last page and split it copy-on-write
    victims = {
        "_prefill_bucket": DecodeRequest([1, 2, 3], max_new_tokens=2),
        "_prefill_chunk": DecodeRequest(shared + [15], max_new_tokens=2),
        "_copy_pools_page": BeamRequest([1, 2, 3], beam_size=2,
                                        max_new_tokens=2)}
    victim = None
    if program in victims:
        sess.step()                                # B and C are seated
        victim = sess.submit(victims[program])
    _lose_pools_on_next_call(monkeypatch, program,
                             at="wait" if at else "call")
    old = (lm.k_pool, lm.v_pool)
    late = None
    if at.endswith("admission"):
        sess.step()                     # the doomed step is in flight
        assert old[0].is_deleted() and len(sess._flights) == 1
        late = sess.submit(DecodeRequest(p_c + [2], max_new_tokens=4))
        want_late = lm.dense_greedy(p_c + [2], 4)
    if at.endswith("queued"):
        sess.step()         # the doomed step, and the next behind it
        assert old[0].is_deleted() and len(sess._flights) == 2
        assert sess._flights[1].step._pools_in[0].is_deleted()
    sess.run(max_steps=100)
    if late is not None:
        assert late.result(0) == want_late and late.step_failures == 0

    assert rebuilds() == n0 + 1
    assert all(pool.is_deleted() for pool in old)
    assert not lm.k_pool.is_deleted() and lm.k_pool.shape == old[0].shape
    assert (b.step_failures, c.step_failures) == (1, 1)
    assert b.result(0) == want[tuple(p_b)]
    assert c.result(0) == want[tuple(p_c)]
    # B's one hit was before the fault; seated again it found nothing
    # (nor did the chunk's victim: its prefill failed)
    assert cache.hits == 1, cache.stats()
    if victim is not None:
        with pytest.raises((PoolsLost, AdmissionRefused)):
            victim.result(0)
    cache.clear()
    assert lm.allocator.pages_in_use == 0


# -- mid-stream disconnect cancels the decode slot (satellite) --------------


class _SlowDecode:
    def __init__(self, inner, delay):
        self._inner = inner
        self.delay = delay

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, *a, **kw):
        time.sleep(self.delay)
        return self._inner.decode(*a, **kw)


def test_stream_disconnect_cancels_slot_and_frees_pages():
    from paddle_tpu.decode import GenerationEngine
    from paddle_tpu.decode.session import _M_CANCELLED

    model = _SlowDecode(_tiny_lm(9), delay=0.1)
    engine = GenerationEngine(model, max_slots=2, max_new_tokens=64)
    srv = InferenceServer(None, generator=engine)
    try:
        host, port = srv.address.split(":")
        body = json.dumps({"src": [1, 2], "max_new_tokens": 6}).encode()
        s = socket.create_connection((host, int(port)), timeout=10)
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                  b"Content-Type: application/json\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        buf = b""
        while b"token" not in buf:
            buf += s.recv(4096)
        # RST on close so the server's next chunk write fails fast
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        # the slot is cancelled and its pages come back without waiting
        # for the full 6-token generation to run its course
        assert _wait_for(lambda: _M_CANCELLED.value() >= 1, timeout=10)
        assert _wait_for(lambda: model.allocator.pages_in_use == 0,
                         timeout=10)
    finally:
        srv.stop()
