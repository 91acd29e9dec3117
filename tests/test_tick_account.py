"""The decode tick's own account (ISSUE 37): the statement that opens a
phase's span charges the same seconds to the session's account, the
account is flushed to the registry once a tick, and a tick that took
far longer than its kind does is counted, kept and logged.

The recording model of ``test_decode_tick_order.py`` stands in for the
device; ``PhasedLM`` writes the skeleton's three statements round its
two halves, as ``decode/model.py`` does.
"""

import gc
import json
import logging
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.decode import session as session_mod
from paddle_tpu.decode.session import (PHASE_SPANS, DecodeRequest,
                                       DecodeSession, TickAccount)
from paddle_tpu.observability import phase
from tests.test_decode_tick_order import PROMPT, RecordingLM, _OnDevice

IN_TICK = ("collect", "decide", "sweep", "admit", "cow", "upload",
           "dispatch", "deliver", "other")


class PhasedLM(RecordingLM):
    """The recording model with the skeleton's statements: the collect,
    the upload (only when something is uploaded) and the dispatch."""

    collect_s = 0.0             # what a collect sleeps: a slow device
    in_collect = None           # hook run inside the collect

    def step_dispatch(self, tokens, states, tables, lens):
        if not all(isinstance(a, _OnDevice) for a in (tokens, tables, lens)):
            with phase("decode.upload"):
                pass
        with phase("decode.dispatch"):
            return super().step_dispatch(tokens, states, tables, lens)

    def step_collect(self, step):
        with phase("decode.logits_to_host"):
            if self.collect_s:
                time.sleep(self.collect_s)
            if self.in_collect is not None:
                self.in_collect()
            return super().step_collect(step)


class _Whole:
    """A wrapper that forwards attributes and overrides ``decode``: the
    session serves it through its ``decode``, whole."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, tokens, states, tables, lens):
        return self._inner.decode(tokens, states, tables, lens)


def _counted(label=None, **labels):
    fam = obs.REGISTRY.get("decode_tick_seconds_total")
    return sum(v["value"] for v in fam.snapshot()["values"]
               if (label is None or v["labels"]["phase"] == label)
               and all(v["labels"][k] == w for k, w in labels.items()))


def _value(name, **labels):
    return obs.REGISTRY.get(name).value(**labels)


def _summed(name, **labels):
    """A family summed over its children that hold ``labels``."""
    fam = obs.REGISTRY.get(name)
    return sum(v["value"] for v in fam.snapshot()["values"]
               if all(v["labels"].get(k) == w for k, w in labels.items()))


def _slow_ticks_counted():
    return _summed("decode_slow_ticks_total")


def _spanned(events, name):
    return sum(e["dur"] for e in events if e["name"] == name) / 1e6


def _tiny():
    from paddle_tpu.decode.model import TinyDecoderLM

    return TinyDecoderLM(seed=3, num_pages=64)


def _session(kind):
    """A session and the requests that keep it ticking 50 times."""
    if kind in ("plain", "admitting"):
        lm = PhasedLM(num_pages=256, page_size=4, pages_per_seq=16)
        sess = DecodeSession(lm, max_slots=3)
        # plain: three long answers; admitting: a queue of short ones,
        # so that most ticks seat a request
        budget, n = (60, 3) if kind == "plain" else (3, 60)
        reqs = [DecodeRequest([2, 5, 7 + i % 3], max_new_tokens=budget)
                for i in range(n)]
    else:
        from paddle_tpu.decode.spec import NgramDraft

        lm = _tiny()
        kw = (dict(spec_draft=NgramDraft(), spec_k=4)
              if kind == "speculative" else {})
        sess = DecodeSession(_Whole(lm) if kind == "whole_decode" else lm,
                             max_slots=2, **kw)
        # 11 + 8 rows in 64: a chunk of 4 always has room
        reqs = [DecodeRequest(list(PROMPT), max_new_tokens=8)
                for _ in range(40)]
    for r in reqs:
        sess.submit(r)
    return sess


@pytest.mark.parametrize("kind", ["plain", "admitting", "whole_decode",
                                  "speculative"])
def test_every_phases_counter_is_the_sum_of_its_ring_spans(kind):
    sess = _session(kind)
    before = {label: _counted(label) for label in (*PHASE_SPANS, "other")}
    ticks0 = {a: _value("decode_ticks_total", admitting=a) for a in "01"}
    with obs.recording() as ring:
        for _ in range(50):
            sess.step()
        # the `between` after the last tick is open: it is in neither
        events = ring.events()
    ring.clear()
    ticks = {a: _value("decode_ticks_total", admitting=a) - ticks0[a]
             for a in "01"}
    assert sum(ticks.values()) == 50
    if kind == "plain":         # the first tick seats all three
        assert ticks == {"0": 49, "1": 1}
    elif kind == "admitting":
        assert ticks["1"] >= 15
    moved = {label: _counted(label) - before[label] for label in before}
    for label, name in PHASE_SPANS.items():
        assert moved[label] == pytest.approx(_spanned(events, name),
                                             rel=1e-6, abs=1e-12), label
    # every phase the tick of this kind runs was charged
    ran = {"collect", "decide", "sweep", "admit", "prefill", "first_token",
           "cow", "upload", "dispatch", "deliver", "between"}
    if kind == "speculative":   # its copy-on-write gate is the draft loop's
        ran.remove("cow")
    assert all(moved[label] > 0 for label in ran), moved
    # `other` is what the tick's span holds beside its top-level phases
    assert sum(moved[label] for label in IN_TICK) == pytest.approx(
        _spanned(events, "decode.tick"), rel=1e-6)
    assert moved["other"] >= 0
    assert moved["prefill"] + moved["first_token"] <= moved["admit"]


def test_admissions_a_tick_and_the_slot_seconds_they_cost():
    """Three requests seated in one tick, then two more in one tick over
    those three live slots."""
    lm = PhasedLM(num_pages=128, pages_per_seq=8)
    sess = DecodeSession(lm, max_slots=5)
    names = ("decode_tick_admissions_total",
             "decode_admit_stalled_slot_seconds_total",
             "decode_slot_seconds_total")

    def snap():
        # the stalled slot-seconds are kept by bucket since PR 51: their
        # sum over the buckets is what the family read before
        return ({n: _value(names[0], n=n) for n in ("0", "1", "2", "3",
                                                    "4+")},
                _summed(names[1]), _value(names[2]))

    seen = [0]

    def tick():
        """One tick, and the spans that ended in it (the `between`
        before it among them)."""
        sess.step()
        events = obs.GLOBAL_EVENTS.events()[seen[0]:]
        seen[0] += len(events)
        return events

    with obs.recording() as ring:
        at0 = snap()
        for i in range(3):
            sess.submit(DecodeRequest([2, 5, 7 + i], max_new_tokens=20))
        first = tick()
        at1 = snap()
        admits = [e["dur"] / 1e6 for e in first if e["name"] == "decode.admit"]
        assert len(admits) == 3
        assert {n: at1[0][n] - at0[0][n] for n in at0[0]} == {
            "0": 0, "1": 0, "2": 0, "3": 1, "4+": 0}
        # each admission held the slots seated before it still: 0, 1, 2
        assert at1[1] - at0[1] == pytest.approx(admits[1] + 2 * admits[2],
                                                rel=1e-6)
        assert at1[2] - at0[2] == pytest.approx(
            3 * _spanned(first, "decode.tick"), rel=1e-6)

        tick()                          # a plain tick between the two
        at2 = snap()
        assert at2[0]["0"] - at1[0]["0"] == 1 and at2[1] == at1[1]
        for i in range(2):
            sess.submit(DecodeRequest([3, 4, 6 + i], max_new_tokens=20))
        second = tick()
        at3 = snap()
        admits = [e["dur"] / 1e6 for e in second if e["name"] == "decode.admit"]
        assert len(admits) == 2
        assert at3[0]["2"] - at2[0]["2"] == 1
        assert at3[1] - at2[1] == pytest.approx(3 * admits[0] + 4 * admits[1],
                                                rel=1e-6)
        # the tick with the `between` before it, times the five live slots
        assert at3[2] - at2[2] == pytest.approx(
            5 * (_spanned(second, "decode.tick")
                 + _spanned(second, "decode.between")), rel=1e-6)
        # the ratio the stall share is: under the admit phase's share of the
        # tick by no more than the slots seated late
        stalled, slot = at3[1] - at2[1], at3[2] - at2[2]
        assert 0 < stalled / slot < 1
        # the steps' inputs and the deliveries ride the same flush
        assert _value("decode_step_inputs_total", source="uploaded") >= 2
    ring.clear()


def _moved(snap0, name):
    """A family's rise since ``snap0``, by its labels' values (one
    label: the value itself)."""
    was = {tuple(u["labels"].items()): u["value"]
           for u in snap0.get(name, {"values": []})["values"]}
    out = {}
    for v in obs.REGISTRY.get(name).snapshot()["values"]:
        rise = v["value"] - was.get(tuple(v["labels"].items()), 0.0)
        if rise:
            label = tuple(v["labels"][k] for k in sorted(v["labels"]))
            out[label[0] if len(label) == 1 else label] = rise
    return out


def test_an_admitting_tick_flushes_the_prefills_wait_and_its_buckets():
    """ISSUE 51: one tick of the GPT-2 toy seats a 70-row prompt (bucket
    128) and a 20-row one (bucket 64) beside one live slot; then one
    seats a prompt whose first 16 rows the prefix cache holds."""
    from paddle_tpu.decode.model import TinyDecoderLM
    from paddle_tpu.decode.prefix import PrefixCache

    lm = TinyDecoderLM(seed=3, max_len=256, num_pages=128, pages_per_seq=32)
    sess = DecodeSession(lm, max_slots=4,
                         prefix_cache=PrefixCache(lm.allocator, lm.page_size))
    long = [2 + i % 50 for i in range(70)]
    sess.submit(DecodeRequest([5, 7, 9, 11], max_new_tokens=40))
    sess.step(), sess.step()            # one slot live, nothing waiting
    fams = ("decode_admissions_total",
            "decode_admit_stalled_slot_seconds_total",
            "decode_admit_tick_rows_total")
    with obs.recording() as ring:
        snap0 = obs.snapshot()
        before = {label: _counted(label) for label in PHASE_SPANS}
        stalled0 = _summed(fams[1])
        sess.submit(DecodeRequest(long, max_new_tokens=4))
        sess.submit(DecodeRequest([60 - i for i in range(20)],
                                  max_new_tokens=4))
        sess.step()
        events = ring.events()
        moved = {label: _counted(label) - before[label]
                 for label in PHASE_SPANS}
        got = {name: _moved(snap0, name) for name in fams}
        stalled = _summed(fams[1]) - stalled0

        # a suffix over cached pages, in a tick of its own
        snap1 = obs.snapshot()
        sess.submit(DecodeRequest(long[:16] + [60, 61, 62],
                                  max_new_tokens=4))
        sess.step()
        suffix = {name: _moved(snap1, name) for name in fams}
    ring.clear()

    # the wait: the sum of its spans, a nested label beside `prefill`,
    # which holds it, and `admit`, which holds that
    waits = [e for e in events if e["name"] == "decode.prefill_wait"]
    assert len(waits) == 2
    assert 0 < moved["prefill_wait"] == pytest.approx(
        _spanned(events, "decode.prefill_wait"), rel=1e-6)
    assert moved["prefill_wait"] < moved["prefill"] <= moved["admit"]
    by_id = {e["args"]["id"]: e for e in events}
    assert {by_id[e["args"]["parent"]]["name"] for e in waits} == {
        "decode.prefill"}
    # ... and `IN_TICK` still tiles the tick without it
    assert sum(moved[label] for label in IN_TICK if label != "other") <= (
        _spanned(events, "decode.tick"))

    admits = {e["args"]["bucket"]: e["dur"] / 1e6 for e in events
              if e["name"] == "decode.admit"}
    assert sorted(admits) == [64, 128]
    assert got["decode_admissions_total"] == {"128": 1, "64": 1}
    # one slot was live before the first, two before the second: the
    # slot-seconds go by the bucket's rows, real and padding, and over
    # its children the family reads what it read before it had labels
    assert got[fams[1]] == pytest.approx(
        {("128", "real"): admits[128] * 70 / 128,
         ("128", "pad"): admits[128] * 58 / 128,
         ("64", "real"): 2 * admits[64] * 20 / 64,
         ("64", "pad"): 2 * admits[64] * 44 / 64}, rel=1e-6)
    assert stalled == pytest.approx(admits[128] + 2 * admits[64], rel=1e-6)
    # 128 + 64 rows ran; the 90 real ones end to end fit one bucket of 128
    assert got["decode_admit_tick_rows_total"] == {"run": 192, "packed": 128}

    assert suffix["decode_admissions_total"] == {"suffix": 1}
    assert list(suffix[fams[1]]) == [("suffix", "real")]
    assert suffix["decode_admit_tick_rows_total"] == {}


def test_packing_is_left_alone_where_it_would_cost_rows():
    """500 + 60 rows run 512 + 64; laid end to end they are 560, which
    the ladder rounds to 1,024: a packer would not, and the account
    counts the tick at what it ran."""
    reg = obs.MetricsRegistry()
    from paddle_tpu.decode.model import TinyDecoderLM

    lm = TinyDecoderLM(seed=3, max_len=1024, num_pages=8, pages_per_seq=128)
    assert lm.packed_prefill_rows(560) == 1024
    assert lm.packed_prefill_rows(1024 + 70) == 1024 + 128
    account = TickAccount(reg, packed_rows=lm.packed_prefill_rows)
    for n in (500, 60):
        with phase("decode.admit", prompt_len=n,
                   bucket=lm.prefill_bucket(n)) as admit:
            pass
        account.admitted(admit, 0, True)
    account.flush(0.0, 2, 2, 0, False, 0.0)
    rows = reg.get("decode_admit_tick_rows_total")
    assert (rows.value(kind="run"), rows.value(kind="packed")) == (576, 576)
    assert reg.get("decode_admissions_total").value(bucket="512") == 1
    # nobody was seated before them: nothing stood still
    assert reg.get("decode_admit_stalled_slot_seconds_total").snapshot()[
        "values"] == []


def test_four_or_more_admissions_share_the_last_bucket():
    lm = PhasedLM(num_pages=128, pages_per_seq=8)
    sess = DecodeSession(lm, max_slots=6)
    before = _value("decode_tick_admissions_total", n="4+")
    for i in range(6):
        sess.submit(DecodeRequest([2, 5, 7 + i], max_new_tokens=4))
    sess.step()
    assert _value("decode_tick_admissions_total", n="4+") - before == 1


@pytest.fixture
def low_floor(monkeypatch):
    """The slow-tick floor brought down to 50 ms, so that a test's tick
    is slow after 0.1 s and not after 0.6."""
    monkeypatch.setattr(session_mod, "SLOW_TICK_FLOOR_S", 0.05)


def _warm(sess, lm, ticks=12):
    sess.submit(DecodeRequest([2, 5, 7], max_new_tokens=60))
    for _ in range(ticks):
        sess.step()


def test_a_slow_tick_names_its_phase_is_kept_and_logged_once(
        low_floor, caplog):
    from paddle_tpu.decode.engine import GenerationEngine
    from paddle_tpu.serving import InferenceServer

    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    lm.collect_s = 0.002                    # a device of 2 ms a step
    engine = GenerationEngine(lm, max_slots=2, max_new_tokens=64)
    srv = InferenceServer(None, generator=engine)
    try:
        slow0 = _slow_ticks_counted()
        collects = []

        def stall_once():                   # the device stalls at step 15
            collects.append(time.perf_counter())
            if len(collects) == 15:
                time.sleep(0.12)

        lm.in_collect = stall_once
        with caplog.at_level(logging.WARNING, logger=session_mod.__name__):
            t_lo = time.perf_counter()
            req = engine.submit([2, 5, 7], max_new_tokens=60)
            assert len(req.result(30)) == 60
            t_hi = time.perf_counter()
        # the stalled one, and on a loaded machine maybe another
        kept = engine.session.slow_ticks
        assert _slow_ticks_counted() - slow0 == len(kept) >= 1
        rec = max(kept, key=lambda r: r["phases"].get("collect", 0.0))
        assert rec["phase"] == "collect"
        assert rec["seconds"] >= 0.12 > 0.9 * rec["seconds"] - 0.05
        assert rec["phases"]["collect"] >= 0.12
        # `at` is on the clock a load generator stamps its sends on
        assert t_lo <= rec["at"] <= collects[14] <= t_hi
        assert rec["active"] == 1 and rec["waiting"] == 0
        assert rec["in_flight"] is True and rec["admissions"] == []
        assert rec["uploaded"] is False     # a steady tick's step
        lines = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("decode.slow_tick ")]
        assert len(lines) == len(kept)      # one line a slow tick
        line = lines[kept.index(rec)]
        fields = dict(kv.split("=", 1) for kv in line.split()[1:])
        assert fields["phase"] == "collect"
        assert float(fields["at"]) == pytest.approx(rec["at"], abs=1e-5)
        assert float(fields["collect_s"]) >= 0.12
        assert fields["active"] == "1" and fields["admissions"] == "-"
        assert fields["in_flight"] == "1" and fields["uploaded"] == "0"
        assert "\n" not in line
        health = json.loads(urllib.request.urlopen(
            f"http://{srv.address}/health", timeout=30).read())
        assert health["generation"]["slow_ticks"] == json.loads(
            json.dumps(kept))
    finally:
        srv.stop()


def test_a_slow_admitting_tick_carries_its_admissions(low_floor):
    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    sess = DecodeSession(lm, max_slots=3)
    _warm(sess, lm)
    inner = lm.prefill

    def slow_prefill(prompt, pages, cached_len=0):
        time.sleep(0.06)
        return inner(prompt, pages, cached_len)

    lm.prefill = slow_prefill
    # an admitting tick has no mean yet: the floor alone judges it
    sess.submit(DecodeRequest([3, 4, 6, 8, 9], max_new_tokens=4))
    sess.submit(DecodeRequest([3, 4], max_new_tokens=4))
    sess.step()
    rec = sess.slow_ticks[-1]
    assert rec["phase"] == "admit" and rec["phases"]["prefill"] >= 0.12
    assert rec["admissions"] == [
        {"prompt_len": 5, "bucket": None, "cached_len": 0},
        {"prompt_len": 2, "bucket": None, "cached_len": 0}]
    assert rec["active"] == 1 and rec["waiting"] == 2
    assert rec["uploaded"] is True
    # it is not what admitting ticks take: the next one is judged by
    # the floor again, and a sound one sets the mean
    lm.prefill = inner
    kept = len(sess.slow_ticks)
    sess.submit(DecodeRequest([3, 4], max_new_tokens=4))
    sess.step()
    assert len(sess.slow_ticks) == kept


def test_a_tick_far_over_its_kind_but_under_the_floor_is_not_slow():
    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    sess = DecodeSession(lm, max_slots=2)
    _warm(sess, lm)
    lm.collect_s = 0.05         # 50x a recording tick, a tenth of the floor
    sess.step()
    assert sess.slow_ticks == []


def test_a_garbage_collection_inside_a_slow_tick_is_on_its_record(low_floor):
    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    sess = DecodeSession(lm, max_slots=2)
    _warm(sess, lm)
    junk = []

    def collect_garbage():
        for _ in range(20000):          # cycles for the collector to find
            a = []
            a.append(a)
            junk.append(a)
        del junk[:]
        gc.collect()
        time.sleep(0.06)

    lm.in_collect = collect_garbage
    sess.step()
    lm.in_collect = None
    rec = sess.slow_ticks[-1]
    assert rec["phase"] == "collect"
    assert 0 < rec["gc_seconds"] < rec["seconds"]
    kept = len(sess.slow_ticks)
    sess.step()                         # none in the next: not carried on
    assert len(sess.slow_ticks) == kept


def test_the_slow_tick_list_is_bounded(low_floor):
    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    sess = DecodeSession(lm, max_slots=2)
    account = sess._account
    for i in range(session_mod.SLOW_TICKS_KEPT + 4):
        account.phases.seconds[0] = 1.0 + i     # the tick's own seconds
        account.flush(float(i), 1, 1, 0, False, 0.0)
    kept = sess.slow_ticks
    assert len(kept) == session_mod.SLOW_TICKS_KEPT
    assert kept[-1]["at"] == session_mod.SLOW_TICKS_KEPT + 3
    assert kept[0]["phase"] == "other"


def test_the_slow_tick_constants_tell_a_long_prefill_from_a_stall():
    """The K-EXAONE cell's longest sound tick seats two long prompts
    (0.437 s on the chip, PERF.md PR 37) beside admitting ticks of
    ~85 ms; an engine-wide silence is 1.2 s or more."""
    account = TickAccount(obs.MetricsRegistry())

    def tick(seconds, admitted):
        account.phases.seconds[0] = seconds
        if admitted:
            account.admissions.append({"prompt_len": 4000, "bucket": 4608,
                                       "cached_len": 0})
        account.flush(0.0, 64, 64, 0, True, 0.0)

    for _ in range(100):
        tick(0.085, True)
        tick(0.018, False)
    tick(0.45, True)
    assert not account.slow_ticks
    tick(1.2, True)
    tick(1.2, False)
    assert [r["seconds"] for r in account.slow_ticks] == [1.2, 1.2]


def test_phase_without_an_open_account_is_a_span_and_no_more():
    with obs.recording() as ring:
        with phase("decode.sweep", rid=7) as p:
            time.sleep(0.001)
        events = ring.events()
    ring.clear()
    assert p.seconds >= 0.001
    assert [(e["name"], e["args"]["rid"]) for e in events] == [
        ("decode.sweep", 7)]
    assert events[0]["dur"] / 1e6 == pytest.approx(p.seconds, rel=1e-9)
    # and under an account that does not keep its name
    account = obs.PhaseAccount(["decode.tick"])
    account.open()
    try:
        with phase("serving.parse"):
            pass
        with phase("decode.tick") as t:
            pass
    finally:
        account.close()
    assert account.take() == [t.seconds]
    assert account.take() == [0.0]


def test_inc_many_is_inc_under_one_lock():
    reg = obs.MetricsRegistry()
    c = reg.counter("probe_total")
    from paddle_tpu.observability.metrics import label_key

    c.inc(2, phase="a", admitting="0")
    c.inc_many([(label_key(admitting="0", phase="a"), 3),
                (label_key(phase="b", admitting="1"), 0.5)])
    assert c.value(phase="a", admitting="0") == 5
    assert c.value(phase="b", admitting="1") == 0.5
    with pytest.raises(ValueError):
        c.inc_many([(label_key(), -1)])


def test_tick_account_overhead_is_within_budget(monkeypatch):
    """The account is on in every run, so what it adds to a tick has a
    budget, held by what it DOES: one ``perf_counter`` pair a phase
    statement and, at the flush, one write (one acquire of the lock) a
    family.  The counts are those of ONE tick of
    ``measure_tick_account_overhead``'s synthetic tick (every phase, one
    admission in a bucket, so every family is written) with the account
    open, beside the same tick as plain spans with nobody recording.
    The host's clock is printed and held only to an order of magnitude
    (9-12 us alone on this sandbox's CPU, 25.3 beside five other
    workers; 0.17% of the Cerebras cell's 15 ms tick): a reading that
    moves with the machine's load says nothing about the code."""
    from paddle_tpu.observability import events, metrics

    log = []
    clock = time.perf_counter

    def counted_clock():
        log.append("clock")
        return clock()

    with monkeypatch.context() as patch:
        def logged(cls, method, say):
            plain = getattr(cls, method)

            def wrapper(self, *a, **kw):
                log.append(say(self))
                return plain(self, *a, **kw)
            patch.setattr(cls, method, wrapper)

        logged(metrics.Counter, "inc", lambda c: "write " + c.name)
        logged(metrics.Counter, "inc_many", lambda c: "write " + c.name)
        logged(events.PhaseAccount, "open", lambda a: "open")
        patch.setattr(events.PhaseAccount, "close",
                      staticmethod(lambda: log.append("close")))
        patch.setattr(time, "perf_counter", counted_clock)
        gc.disable()        # a collection's callback reads the clock too
        try:
            # an accounted tick (the warm-up), the plain one, an accounted one
            obs.measure_tick_account_overhead(iters=1)
        finally:
            gc.enable()
    assert log.count("open") == log.count("close") == 2
    opened, closed = log.index("open"), log.index("close")
    accounted = log[opened + 1:closed]
    # between the ticks: the loops' own four readings of the clock
    plain = log[closed + 1:log.index("open", closed)]
    assert plain.count("clock") == 4, plain
    plain = [line for line in plain if line != "clock"]
    assert plain == ["write overhead_probe_step_inputs_total",
                     "write overhead_probe_deliveries_total"]
    statements = len(PHASE_SPANS) + 1           # every phase, and the tick
    assert accounted.count("clock") == 2 * statements
    writes = [line for line in accounted if line != "clock"]
    # a family, a write: every family of the account once, none twice
    assert sorted(writes) == sorted("write " + name for name in (
        "decode_tick_seconds_total", "decode_ticks_total",
        "decode_tick_admissions_total", "decode_admissions_total",
        "decode_admit_tick_rows_total",
        "decode_admit_stalled_slot_seconds_total",
        "decode_slot_seconds_total", "decode_step_inputs_total",
        "decode_deliveries_total"))

    got = min(obs.measure_tick_account_overhead(iters=500)
              for _ in range(3))
    print(f"tick account overhead {got * 1e6:.1f} us a tick "
          f"({2 * statements} clock readings, {len(writes) - len(plain)} "
          "writes more than the plain tick)")
    assert got < 250e-6, f"tick account overhead {got * 1e6:.1f} us a tick"


def test_submit_lag_is_observed_once_a_generate_request():
    from paddle_tpu.decode.engine import GenerationEngine
    from paddle_tpu.serving import InferenceServer

    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    engine = GenerationEngine(lm, max_slots=2, max_new_tokens=8)
    srv = InferenceServer(None, generator=engine)
    lag = obs.REGISTRY.get("serving_generate_submit_lag_seconds")
    first = obs.REGISTRY.get("serving_generate_first_write_lag_seconds")
    try:
        n0, f0 = lag.count(), first.count()
        for stream in (True, False):
            req = urllib.request.Request(
                f"http://{srv.address}/generate",
                data=json.dumps({"src": [2, 5, 7], "max_new_tokens": 4,
                                 "stream": stream}).encode(),
                headers={"Content-Type": "application/json"})
            assert urllib.request.urlopen(req, timeout=30).status == 200
        assert lag.count() - n0 == 2
        assert first.count() - f0 == 1          # the streamed one
        snap = lag.snapshot()["values"][0]
        assert 0 < snap["sum"] < 2.0
        # a request refused before the engine holds it observes nothing
        bad = urllib.request.Request(
            f"http://{srv.address}/generate", data=b'{"src": []}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=30)
        assert lag.count() - n0 == 2
    finally:
        srv.stop()


def test_a_between_left_open_is_no_spans_parent():
    """A session dropped while not idle leaves its ``decode.between``
    open for good: it must not pass for the parent of later spans."""
    lm = PhasedLM(num_pages=64, pages_per_seq=16)
    sess = DecodeSession(lm, max_slots=2)
    with obs.recording() as ring:
        sess.submit(DecodeRequest([2, 5, 7], max_new_tokens=9))
        sess.step(), sess.step()
        assert sess._between is not None        # open, and stays so
        with obs.span("later"):
            pass
        events = ring.events()
    ring.clear()
    by_name = {e["name"]: e for e in events}
    assert by_name["later"]["args"]["parent"] == 0
    assert by_name["decode.between"]["args"]["parent"] == 0
    tick = by_name["decode.tick"]["args"]["id"]
    assert by_name["decode.sweep"]["args"]["parent"] == tick
