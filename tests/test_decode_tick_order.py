"""The order of a decode tick over a model that steps in two halves
(ISSUE 32): collect -> decide -> sweep, admit, copy-on-write ->
dispatch -> deliver; and the step it dispatches behind the one in
flight whenever no row can change in between (ISSUE 61).

A duck-typed recording model stands in for the device: it logs every
call in order, keeps its own copy of what "the device holds" of the
step's inputs (so that a test reads what a step really ran on, uploaded
or resident), and writes each fed token into a toy K/V pool at the row
the real step would, so that a write to a page that was not the
sequence's shows.
"""

import time

import numpy as np
import pytest

from paddle_tpu.decode.paged_kv import PageAllocator
from paddle_tpu.decode.session import (AHEAD_HELD, BeamRequest,
                                       DecodeRequest, DecodeSession,
                                       _M_AHEAD_HELD, _M_DELIVERIES,
                                       _M_DISPATCHES, _M_STEP_INPUTS)

V = 13


def _next(tok, n):
    return 1 + (3 * int(tok) + int(n)) % (V - 1)


class _OnDevice:
    """What the recording model hands on as resident on its device."""

    def __init__(self, value):
        self.value = value


class _Logits:
    def __init__(self, rows):
        self._rows = rows
        self.ids = np.argmax(rows, axis=-1).astype(np.int32)

    def __array__(self, dtype=None, copy=None):
        return self._rows if dtype is None else self._rows.astype(dtype)

    def __getitem__(self, idx):
        return self._rows[idx]


class RecordingLM:
    """The session's model contract with the two halves, on numpy.  The
    next token of a lane that was fed ``tok`` at length ``n`` is
    ``_next(tok, n)``, never the EOS, unless ``script`` names another
    for that (lane, step)."""

    grows_kv = True
    supports_prefix_cache = True
    emits_probs = False
    state_specs = []
    bos_id, eos_id = 1, 0

    def __init__(self, num_pages=32, page_size=4, pages_per_seq=4):
        self.page_size, self.pages_per_seq = page_size, pages_per_seq
        self.allocator = PageAllocator(num_pages)
        self.pool = np.full((num_pages, page_size), -1, np.int64)
        self.log = []               # ("prefill"|"dispatch"|"collect", ...)
        self.steps = []             # what each dispatched step ran on
        self.in_flight = []         # dispatched, not collected: oldest first
        self.script = {}            # (lane, step number) -> token
        self.on_dispatch = None     # hook(step number)
        self.fail_collect = set()   # step numbers whose collect raises

    # -- paging -------------------------------------------------------------

    def context_pages(self, prompt, max_new_tokens):
        return max(1, -(-(len(prompt) + max_new_tokens) // self.page_size))

    def pool_table(self, pages):
        t = np.zeros((self.pages_per_seq,), np.int32)
        t[:len(pages)] = pages
        return t

    def copy_page(self, src, dst):
        assert not self.in_flight, "a page copy under a step in flight"
        self.pool[dst] = self.pool[src]

    def _rows(self, tok, n):
        rows = np.zeros((V,), np.float32)
        rows[_next(tok, n)] = 5.0
        rows[1 + _next(tok, n) % (V - 1)] = 4.0     # the runner-up
        return rows

    def prefill(self, prompt, pages, cached_len=0):
        assert not self.in_flight, "a prefill under a step in flight"
        self.log.append(("prefill", len(prompt), cached_len))
        for r in range(cached_len, len(prompt)):
            self.pool[pages[r // self.page_size], r % self.page_size] = \
                prompt[r]
        return len(prompt), [], self._rows(prompt[-1], len(prompt))

    # -- the two halves -----------------------------------------------------

    def step_dispatch(self, tokens, states, tables, lens):
        assert len(self.in_flight) < 2, "three steps in flight"
        n = len(self.steps) + 1
        if self.on_dispatch is not None:
            self.on_dispatch(n)
        uploaded = [name for name, a in (("tokens", tokens),
                                         ("tables", tables), ("lens", lens))
                    if not isinstance(a, _OnDevice)]
        if self.in_flight:      # the host has not seen that step's ids
            handed_on = self.in_flight[-1].next
            assert (tokens is handed_on["tokens"]
                    and tables is handed_on["tables"]
                    and lens is handed_on["lens"]), \
                "a step behind another is fed by what that one hands on"
        tokens, tables, lens = (
            a.value if isinstance(a, _OnDevice) else np.array(a)
            for a in (tokens, tables, lens))
        tokens = tokens.reshape(-1)
        S = tokens.shape[0]
        live = tables[:, 0] > 0
        rows = np.stack([self._rows(tokens[i], lens[i]) for i in range(S)])
        for i in range(S):
            if (i, n) in self.script:
                rows[i] = 0.0
                rows[i, self.script[i, n]] = 9.0
            page = tables[i, min(lens[i] // self.page_size,
                                 self.pages_per_seq - 1)]
            self.pool[page, lens[i] % self.page_size] = tokens[i]
        self.steps.append({"tokens": tokens.copy(), "tables": tables.copy(),
                           "lens": lens.copy(), "uploaded": uploaded,
                           "pool": self.pool.copy(),
                           "behind": bool(self.in_flight)})
        self.log.append(("dispatch", n))
        logits = _Logits(rows)
        self.in_flight.append(_Step(n, logits, {
            "tokens": _OnDevice(logits.ids.astype(np.int64)),
            "tables": _OnDevice(tables),
            "lens": _OnDevice(lens + live)}))
        return self.in_flight[-1]

    def step_collect(self, step):
        assert step is self.in_flight[0], "collected out of order"
        self.in_flight.pop(0)
        self.log.append(("collect", step.n))
        if step.n in self.fail_collect:
            self.in_flight.clear()      # what ran behind it is lost too
            raise RuntimeError("injected: the step failed on the device")
        return step.logits, []


class _Step:
    def __init__(self, n, logits, handed_on):
        self.n, self.logits, self.next = n, logits, handed_on


def _request(lm, prompt, budget, name, **kw):
    """A request whose every delivered token is logged as
    ("token", name, tok)."""
    return DecodeRequest(list(prompt), max_new_tokens=budget,
                         on_token=lambda t: lm.log.append(("token", name, t)),
                         **kw)


def _expected(prompt, n, script=None):
    """The recording model's greedy stream."""
    out, tok, length = [], prompt[-1], len(prompt)
    for j in range(n):
        nxt = _next(tok, length)
        if script and j in script:
            nxt = script[j]
        out.append(nxt)
        if nxt == RecordingLM.eos_id:
            break
        tok, length = nxt, length + (1 if j else 0)
    return out


def test_the_recording_models_own_stream():
    lm = RecordingLM()
    sess = DecodeSession(lm, max_slots=2)
    req = sess.submit(_request(lm, [2, 5, 7], 6, "a"))
    sess.run(50)
    assert req.result(0) == _expected([2, 5, 7], 6)
    assert lm.allocator.pages_in_use == 0


def test_step_k_is_delivered_between_dispatch_and_collect_of_step_k_plus_1():
    lm = RecordingLM()
    sess = DecodeSession(lm, max_slots=2)
    req = sess.submit(_request(lm, [2, 5, 7], 6, "a"))
    sess.run(50)
    assert len(req.result(0)) == 6
    at = {ev: n for n, ev in enumerate(lm.log) if ev[0] != "token"}
    tokens = [n for n, ev in enumerate(lm.log) if ev[0] == "token"]
    # token 0 is the prefill's, at the admission, before any step
    assert at["prefill", 3, 0] < tokens[0] < at["dispatch", 1]
    # token k (k >= 1) is step k's: it goes out after step k+1 has been
    # dispatched and before it is collected; the last one after its own
    # collect, with nothing in flight
    for k in range(1, 5):
        assert at["dispatch", k + 1] < tokens[k] < at["collect", k + 1], k
    assert at["collect", 5] < tokens[5] and len(lm.steps) == 5
    assert not lm.in_flight


def test_a_prefill_is_only_ever_called_with_no_step_in_flight():
    """The model asserts it; here admissions fall between steps: four
    requests on two lanes, two of them submitted while steps run."""
    lm = RecordingLM(num_pages=64)
    sess = DecodeSession(lm, max_slots=2)
    first = [sess.submit(_request(lm, [2, 3 + i], 3 + i, f"r{i}"))
             for i in range(2)]
    late = []
    lm.on_dispatch = lambda n: late.append(sess.submit(
        _request(lm, [4, 4, n], 3, f"late{n}"))) if n in (2, 3) else None
    sess.run(100)
    for r in first + late:
        assert len(r.result(0)) == r.max_new_tokens
    kinds = [ev[0] for ev in lm.log if ev[0] != "token"]
    assert kinds.count("prefill") == 4
    last = None
    for kind in kinds:      # the step before a prefill has been collected
        if kind == "prefill":
            assert last != "dispatch"
        else:
            last = kind
    assert lm.allocator.pages_in_use == 0


@pytest.mark.parametrize("ending", ["eos", "budget", "deadline"])
def test_a_slot_that_ended_at_step_k_is_null_in_step_k_plus_1(ending):
    """With a slot free no step is queued behind another, so the step
    after the one that ended a sequence is dispatched after its decide
    (the batch that is full: `test_an_ending_the_host_cannot_count_...`
    below)."""
    lm = RecordingLM()
    sess = DecodeSession(lm, max_slots=3)
    a = _request(lm, [2, 5, 7], 3 if ending == "budget" else 8, "a")
    b = _request(lm, [3, 3], 8, "b")
    sess.submit(a), sess.submit(b)
    k = 2                           # a's third token is step 2's
    if ending == "eos":
        lm.script[0, k] = lm.eos_id
    if ending == "deadline":
        def expire(n):
            if n == k:              # seen by the decide of step k
                a.deadline = time.monotonic() - 1.0
        lm.on_dispatch = expire
    sess.run(50)
    assert len(b.result(0)) == 8
    if ending == "deadline":
        with pytest.raises(TimeoutError):
            a.result(0)
        assert len(a.tokens) == 2   # the prefill's and step 1's
    else:
        assert len(a.result(0)) == 3
        assert a.finish_reason == ("eos" if ending == "eos" else "length")
    before, after = lm.steps[k - 1], lm.steps[k]
    assert before["tables"][0, 0] > 0
    assert not after["tables"][0].any() and after["lens"][0] == 1
    assert after["tables"][1, 0] > 0            # b rides on
    # the tick that cleared the lane refreshed its arrays
    assert set(after["uploaded"]) == {"tokens", "tables", "lens"}
    assert lm.allocator.pages_in_use == 0


def test_full_pages_shared_with_the_prefix_cache_are_never_written():
    """A sequence whose prompt + budget fill its pages exactly (and its
    lane's whole table), its prompt's page shared with the prefix
    cache: after it ends at step k no step writes a row of any of its
    pages, the cached page's rows stay what the prefill wrote, and the
    pages it gave back are handed out again."""
    from paddle_tpu.decode.prefix import PrefixCache

    lm = RecordingLM(num_pages=16, page_size=4, pages_per_seq=2)
    cache = PrefixCache(lm.allocator, lm.page_size)
    sess = DecodeSession(lm, max_slots=2, prefix_cache=cache)
    prompt = [2, 5, 7, 3]                       # one full page
    a = sess.submit(_request(lm, prompt, 4, "a"))   # 4 + 4 = 2 pages
    b = sess.submit(_request(lm, [3, 3], 6, "b"))
    sess.step()                                 # both seated, step 1 out
    a_pages = [int(p) for p in lm.steps[0]["tables"][0] if p]
    assert len(a_pages) == 2 and cache.cached_pages == 1
    assert lm.allocator.is_shared(a_pages[0])
    sess.run(50)
    assert len(a.result(0)) == 4 and len(b.result(0)) == 6
    # the batch was full: steps 2 and 3 each went behind the one
    # before, and nothing behind step 3, a's last (its budget was
    # counted ahead)
    assert [st["behind"] for st in lm.steps[:4]] == [False, True, True,
                                                     False]
    # a's tokens 2..4 are steps 1..3: step 3 wrote its row 6, the last
    # write of the sequence; row 7 of its last page is never written
    ended = lm.steps[2]["pool"]
    assert list(ended[a_pages[0]]) == prompt
    assert ended[a_pages[1], 3] == -1
    for later in lm.steps[3:]:
        assert not later["tables"][0].any()
        np.testing.assert_array_equal(later["pool"][a_pages], ended[a_pages])
    # the cache still serves the page, with the prefill's rows
    c = sess.submit(_request(lm, prompt + [9], 2, "c"))
    sess.run(50)
    assert len(c.result(0)) == 2 and cache.hits == 1
    assert ("prefill", 5, 4) in lm.log
    assert list(lm.pool[a_pages[0]]) == prompt
    cache.clear()
    assert lm.allocator.pages_in_use == 0


def _inputs_and_deliveries():
    return ({s: _M_STEP_INPUTS.value(source=s)
             for s in ("resident", "uploaded")},
            {u: _M_DELIVERIES.value(under=u) for u in ("step", "nothing")})


def _moved(before):
    after = _inputs_and_deliveries()
    return tuple({k: after[n][k] - before[n][k] for k in before[n]}
                 for n in range(2))


def test_steady_ticks_upload_nothing_and_deliver_under_the_next_step():
    lm = RecordingLM()
    sess = DecodeSession(lm, max_slots=2)
    before = _inputs_and_deliveries()
    req = sess.submit(_request(lm, [2, 5, 7], 9, "a"))
    sess.run(50)
    assert len(req.result(0)) == 9
    inputs, deliveries = _moved(before)
    # 8 steps: the one after the admission uploads, 7 run on what the
    # device held; every delivery but the session's last has the next
    # step in flight
    assert inputs == {"uploaded": 1, "resident": 7}
    assert deliveries == {"step": 7, "nothing": 1}
    assert lm.steps[0]["uploaded"] == ["tokens", "tables", "lens"]
    assert all(s["uploaded"] == [] for s in lm.steps[1:])


def _tiny(seed=3):
    from paddle_tpu.decode.model import TinyDecoderLM

    return TinyDecoderLM(seed=seed, num_pages=64)


PROMPT = [1, 5, 9, 3, 7, 2, 8, 4, 6, 2, 3]


@pytest.mark.parametrize("what", ["admission", "eviction", "sampled_token",
                                  "beam_selection", "speculative_tick"])
def test_a_tick_that_changed_a_row_uploads(what):
    """On the real skeleton: which ticks refresh the device's arrays."""
    from paddle_tpu.decode.spec import NgramDraft

    lm = _tiny()
    kw = (dict(spec_draft=NgramDraft(), spec_k=4)
          if what == "speculative_tick" else {})
    sess = DecodeSession(lm, max_slots=4, **kw)
    before = _inputs_and_deliveries()
    if what == "admission":
        # a runs 8 steps; b is seated two ticks in: steps 1 and 3 upload
        a = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=9))
        sess.step(), sess.step()
        b = sess.submit(DecodeRequest(list(PROMPT[:5]), max_new_tokens=7))
        sess.run(100)
        assert len(a.result(0)) == 9 and len(b.result(0)) == 7
        want = {"uploaded": 2, "resident": 6}
    elif what == "eviction":
        # b leaves after 3 steps, a after 8: steps 1 and 4 upload
        a = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=9))
        b = sess.submit(DecodeRequest(list(PROMPT[:5]), max_new_tokens=4))
        sess.run(100)
        assert len(a.result(0)) == 9 and len(b.result(0)) == 4
        want = {"uploaded": 2, "resident": 6}
    elif what == "sampled_token":
        a = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=6,
                                      temperature=0.9, seed=3))
        sess.run(100)
        steps = len(a.result(0)) - 1
        want = {"uploaded": steps, "resident": 0}
    elif what == "beam_selection":
        a = sess.submit(BeamRequest(list(PROMPT), beam_size=2,
                                    max_new_tokens=5))
        sess.run(100)
        assert a.wait(0) and a.beams
        _, deliveries = _moved(before)
        want = {"uploaded": sum(deliveries.values()), "resident": 0}
    else:
        # 11 + 8 rows in 24: every tick has room for a chunk of 4
        a = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=8))
        sess.run(100)
        assert a.result(0) == lm.dense_greedy(PROMPT, 8)
        _, deliveries = _moved(before)
        assert deliveries["nothing"] >= 1
        want = {"uploaded": sum(deliveries.values()), "resident": 0}
    inputs, deliveries = _moved(before)
    assert inputs == want
    assert sum(deliveries.values()) == sum(inputs.values())
    assert lm.allocator.pages_in_use == 0


def test_a_failure_at_the_collect_is_contained_over_the_dispatched_lanes():
    """Step 2 fails on the device: both sequences it was dispatched
    with go back (first strike), a request submitted while it was in
    flight is admitted in the same tick, and all three finish with
    their own streams."""
    lm = RecordingLM(num_pages=64)
    sess = DecodeSession(lm, max_slots=3)
    a = sess.submit(_request(lm, [2, 5, 7], 5, "a"))
    b = sess.submit(_request(lm, [3, 3], 5, "b"))
    lm.fail_collect.add(2)
    late = []
    lm.on_dispatch = lambda n: late.append(sess.submit(
        _request(lm, [4, 4, 4], 4, "c"))) if n == 2 else None
    sess.run(100)
    assert (a.step_failures, b.step_failures) == (1, 1)
    assert a.result(0) == _expected([2, 5, 7], 5)
    assert b.result(0) == _expected([3, 3], 5)
    assert late[0].result(0) == _expected([4, 4, 4], 4)
    assert late[0].step_failures == 0
    # the step after the failure ran on uploaded arrays only
    failed = lm.log.index(("collect", 2))
    nxt = next(ev for ev in lm.log[failed:] if ev[0] == "dispatch")
    assert set(lm.steps[nxt[1] - 1]["uploaded"]) == {"tokens", "tables",
                                                     "lens"}
    assert lm.allocator.pages_in_use == 0


def test_fail_all_drops_the_step_in_flight():
    lm = RecordingLM()
    sess = DecodeSession(lm, max_slots=2)
    req = sess.submit(_request(lm, [2, 5, 7], 6, "a"))
    sess.step()
    assert lm.in_flight and not sess.idle()
    sess.fail_all(RuntimeError("stopped"))
    with pytest.raises(RuntimeError):
        req.result(0)
    assert sess.idle() and lm.allocator.pages_in_use == 0
    # the model is told nothing; a session that goes on starts clean
    lm.in_flight.clear()
    again = sess.submit(_request(lm, [2, 5, 7], 3, "b"))
    sess.run(50)
    assert again.result(0) == _expected([2, 5, 7], 3)
    assert lm.steps[-1]["uploaded"] == [] and \
        set(lm.steps[-2]["uploaded"]) == {"tokens", "tables", "lens"}


def test_resident_and_uploaded_entries_are_one_compiled_step():
    """The step entered with what the device held, with three uploaded
    arrays or with a mix is the same program: once a first step has
    compiled it, no kind of tick asks jax for a compile."""
    import jax

    from paddle_tpu.decode import model as dm

    lm = _tiny()
    sess = DecodeSession(lm, max_slots=4)
    warm = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=2))
    sess.run(50)
    assert len(warm.result(0)) == 2
    requests, counting = [], [True]
    jax.monitoring.register_event_listener(
        lambda name, **kw: requests.append(name)
        if counting[0] and name.endswith("compile_requests_use_cache")
        else None)
    entries = dm._decode_step._cache_size()
    before = _inputs_and_deliveries()
    try:
        reqs = [DecodeRequest(list(PROMPT), max_new_tokens=9),
                DecodeRequest(list(PROMPT[:5]), max_new_tokens=4)]
        for r in reqs:
            sess.submit(r)
        sess.run(50)
        sampled = sess.submit(DecodeRequest(
            list(PROMPT), max_new_tokens=5, temperature=0.8, seed=1))
        sess.run(50)
        assert sampled.wait(0) and all(r.wait(0) for r in reqs)
    finally:
        counting[0] = False
    inputs, _ = _moved(before)
    assert inputs["resident"] >= 5 and inputs["uploaded"] >= 5
    assert requests == []
    assert dm._decode_step._cache_size() == entries


# ---------------------------------------------------------------------------
# a step dispatched behind the one in flight (ISSUE 61)
# ---------------------------------------------------------------------------


def _ahead():
    return ({b: _M_DISPATCHES.value(behind=b) for b in ("nothing", "step")},
            {w: _M_AHEAD_HELD.value(why=w) for w in AHEAD_HELD})


def _ahead_moved(before):
    after = _ahead()
    return tuple({k: int(after[n][k] - before[n][k]) for k in before[n]
                  if after[n][k] != before[n][k]} for n in range(2))


def _full_batch(lm, budgets=(9, 9), slots=2, **kw):
    sess = DecodeSession(lm, max_slots=slots, **kw)
    prompts = ([2, 5, 7], [3, 3], [4, 4, 4], [5, 2])
    reqs = [sess.submit(_request(lm, prompts[i], n, "abcd"[i]))
            for i, n in enumerate(budgets)]
    return sess, reqs


def test_a_full_batch_runs_step_k_plus_2_behind_step_k_plus_1():
    """Every slot live and no budget due: step k+2 is dispatched before
    step k+1 is collected, on nothing but what step k+1 hands on (the
    model asserts that, and that no third step joins them); the tokens
    of step k go out under it; the tick before a budget's end queues
    nothing."""
    lm = RecordingLM()
    before = _ahead()
    sess, (a, b) = _full_batch(lm)
    sess.run(50)
    assert a.result(0) == _expected([2, 5, 7], 9)
    assert b.result(0) == _expected([3, 3], 9)
    at = {ev: n for n, ev in enumerate(lm.log) if ev[0] != "token"}
    assert len(lm.steps) == 8
    for n in range(2, 9):       # dispatched while step n - 1 is in flight
        assert at["dispatch", n] < at["collect", n - 1], n
        assert lm.steps[n - 1]["behind"] and not lm.steps[n - 1]["uploaded"]
    assert not lm.steps[0]["behind"]
    # a's token k + 1 is step k's: out after step k + 2's dispatch (the
    # last two after the last dispatch), before step k + 1's collect
    tokens = [n for n, ev in enumerate(lm.log) if ev[:2] == ("token", "a")]
    for k in range(1, 8):
        assert at["dispatch", min(k + 2, 8)] < tokens[k] \
            < at["collect", k + 1], k
    assert at["collect", 8] < tokens[8] and len(tokens) == 9
    behind, held = _ahead_moved(before)
    assert behind == {"nothing": 1, "step": 7}
    assert held == {"budget": 1}        # the tick that collected step 7
    assert not lm.in_flight and lm.allocator.pages_in_use == 0


def test_nothing_is_seated_or_copied_while_a_step_is_in_flight():
    """Six requests over two lanes with co-prime budgets, a prefix
    cache that shares their prompts' pages: the model asserts that no
    prefill or page copy ran under a step, nor a third step."""
    from paddle_tpu.decode.prefix import PrefixCache

    lm = RecordingLM(num_pages=64)
    cache = PrefixCache(lm.allocator, lm.page_size)
    sess = DecodeSession(lm, max_slots=2, prefix_cache=cache)
    prompt = [2, 5, 7, 3]
    reqs = [sess.submit(_request(lm, prompt + [i], n, f"r{i}"))
            for i, n in enumerate((3, 7, 5, 11, 4, 6))]
    before = _ahead()
    sess.run(200)
    for i, r in enumerate(reqs):
        assert r.result(0) == _expected(prompt + [i], r.max_new_tokens)
    behind, held = _ahead_moved(before)
    assert behind["step"] >= 8 and held["budget"] >= 4
    assert cache.hits == 5
    cache.clear()
    assert lm.allocator.pages_in_use == 0


def _whole(lm):
    """``lm`` without the halves: served through ``decode``, whole."""
    class Whole:
        def __getattr__(self, name):
            return getattr(lm, name)

        def decode(self, tokens, states, tables, lens):
            return lm.step_collect(
                lm.step_dispatch(tokens, states, tables, lens))

    return Whole()


@pytest.mark.parametrize("why", [
    "free_slot", "budget", "stale", "host_choice", "beam", "draft", "cow",
    "no_halves"])
def test_nothing_is_queued_behind_a_flight_when(why):
    """One case a condition of ``_ahead_held``: the tick says which
    failed first, the step after runs behind nothing, and the streams
    are the recording model's own."""
    from paddle_tpu.decode.spec import NgramDraft

    lm = RecordingLM(num_pages=64)
    before = _ahead()
    want_held = why
    if why == "free_slot":
        sess, reqs = _full_batch(lm, slots=3)
        sess.run(50)
        steps = len(lm.steps)
        assert _ahead_moved(before) == ({"nothing": steps},
                                        {"free_slot": steps})
    elif why == "budget":
        # a's third and last token is step 2's: queued behind step 1
        # it is, and nothing behind it
        sess, reqs = _full_batch(lm, budgets=(3, 9))
        sess.step(), sess.step()
        assert [st["behind"] for st in lm.steps] == [False, True]
        assert _ahead_moved(before)[1] == {"budget": 1}
        sess.run(50)
        assert not lm.steps[2]["behind"]
        assert not lm.steps[2]["tables"][0].any()    # a is gone from step 3
    elif why == "stale":
        sess, reqs = _full_batch(lm)
        sess.step()
        assert len(lm.in_flight) == 2
        sess._inputs.retable(0, sess._inputs.tables[0].copy())
        sess.step()             # collects 1: nothing goes behind 2
        assert len(lm.in_flight) == 1
        assert _ahead_moved(before)[1] == {"stale": 1}
        sess.step()             # collects 2; step 3 takes the tables up
        assert lm.steps[2]["uploaded"] == ["tables"]
        assert [st["behind"] for st in lm.steps] == [False, True, False,
                                                     True]
        sess.run(50)
    elif why == "host_choice":
        sess = DecodeSession(lm, max_slots=2)
        reqs = [sess.submit(_request(lm, [2, 5, 7], 6, "a")),
                sess.submit(_request(lm, [3, 3], 6, "b", temperature=0.7,
                                     seed=5))]
        sess.run(50)
        behind, held = _ahead_moved(before)
        # (the last tick's first reason is a's budget)
        assert behind == {"nothing": 5}
        assert held == {"host_choice": 4, "budget": 1}
        assert reqs[0].result(0) == _expected([2, 5, 7], 6)
        assert len(reqs.pop().result(0)) == 6
    elif why == "beam":
        lm, want_held = _tiny(), "host_choice"
        sess = DecodeSession(lm, max_slots=2)
        beam = sess.submit(BeamRequest(list(PROMPT), beam_size=2,
                                       max_new_tokens=5))
        sess.run(100)
        assert beam.wait(0) and beam.beams
        behind, held = _ahead_moved(before)
        assert "step" not in behind and held["host_choice"] >= 1
        assert set(held) <= {"host_choice", "free_slot"}
        reqs = []
    elif why == "draft":
        # 13 + 3 rows fill the two pages: no tick has room for a chunk
        # of 4, each falls back to the plain step, in two halves
        lm = _tiny()
        sess = DecodeSession(lm, max_slots=1, spec_draft=NgramDraft(),
                             spec_k=4)
        prompt = list(PROMPT) + [5, 6]
        req = sess.submit(DecodeRequest(prompt, max_new_tokens=3))
        sess.run(50)
        assert req.result(0) == lm.dense_greedy(prompt, 3)
        assert _ahead_moved(before) == ({"nothing": 2}, {"draft": 2})
        reqs = []
    elif why == "cow":
        # the row after the flight's lands in a's second page, which a
        # fork holds too: wait a tick, split it with nothing in flight
        sess, reqs = _full_batch(lm)
        sess.step()
        a_pages = sess._slots[0].pages
        forked = lm.allocator.fork([a_pages[1]])
        sess.step()             # collects 1: row 5 is step 3's
        assert _ahead_moved(before)[1] == {"cow": 1}
        assert len(lm.in_flight) == 1
        sess.step()             # collects 2, copies (the model asserts
        assert sess._slots[0].pages[1] != forked[0]     # under nothing)
        assert lm.steps[2]["uploaded"] == ["tables"]
        lm.allocator.free(forked)
        sess.run(50)
    else:
        sess, reqs = _full_batch(_whole(lm))
        assert not sess._two_halves
        sess.run(50)
        assert len(lm.steps) == 8
        assert _ahead_moved(before) == ({}, {})     # no flight, no hold
        want_held = None
    for r, prompt in zip(reqs, ([2, 5, 7], [3, 3])):
        assert r.result(0) == _expected(prompt, r.max_new_tokens)
    if want_held is not None:
        assert _ahead_moved(before)[1].get(want_held, 0) >= 1
    assert lm.allocator.pages_in_use == 0


@pytest.mark.parametrize("ending", ["eos", "deadline", "cancel"])
def test_an_ending_the_host_cannot_count_is_absorbed_by_the_step_behind(
        ending):
    """Lane 0's sequence ends at the decide of step 2 with step 3
    queued behind it: step 3 computes one junk row for the lane, into
    a page that is nobody else's, its token for the lane goes to
    nobody, the other lane's stream is untouched, nothing more is
    queued, and the request that waits is seated only in the tick that
    finds nothing in flight, in the lane that came free."""
    lm = RecordingLM(num_pages=64)
    sess, (a, b) = _full_batch(lm, budgets=(8, 8))
    c = sess.submit(_request(lm, [4, 4, 4], 3, "c"))
    if ending == "eos":
        lm.script[0, 2] = lm.eos_id

    def land(n):            # at step 3's dispatch: step 2 is in flight
        if n == 3 and ending == "deadline":
            a.deadline = time.monotonic() - 1.0
        if n == 3 and ending == "cancel":
            a.cancel()
    lm.on_dispatch = land
    before = _ahead()
    sess.run(50)
    assert b.result(0) == _expected([3, 3], 8)
    assert c.result(0) == _expected([4, 4, 4], 3)
    if ending == "eos":
        assert a.result(0) == _expected([2, 5, 7], 8, script={2: lm.eos_id})
        assert a.finish_reason == "eos"
    else:
        assert a.done and a.tokens == _expected([2, 5, 7], 2)
        assert a.finish_reason == ("deadline" if ending == "deadline"
                                   else "cancelled")
    ended, junk, after = lm.steps[1], lm.steps[2], lm.steps[3]
    assert junk["behind"] and not after["behind"]
    # step 3 still ran lane 0 over a's pages, which nobody had been given
    np.testing.assert_array_equal(junk["tables"][0], ended["tables"][0])
    assert not set(junk["tables"][0]) & set(junk["tables"][1]) - {0}
    # c: prefilled after step 3's collect (the model asserts: under
    # nothing), seated in lane 0 of step 4, which uploads all three
    at = {ev: n for n, ev in enumerate(lm.log) if ev[0] != "token"}
    assert at["collect", 3] < at["prefill", 3, 0] < at["dispatch", 4]
    assert set(after["uploaded"]) == {"tokens", "tables", "lens"}
    assert after["tables"][0, 0] > 0 and after["lens"][0] == 3
    held = _ahead_moved(before)[1]
    assert held["free_slot"] >= 1
    assert lm.allocator.pages_in_use == 0


@pytest.mark.parametrize("failed", [1, 2])
def test_a_failure_at_the_collect_drops_the_step_queued_behind(failed):
    """Step ``failed`` fails on the device with the next one queued
    behind it: that one is never collected, both lanes go back once,
    and everybody finishes with their own stream."""
    lm = RecordingLM(num_pages=64)
    sess, (a, b) = _full_batch(lm, budgets=(6, 7))
    c = sess.submit(_request(lm, [4, 4, 4], 4, "c"))
    lm.fail_collect.add(failed)
    sess.run(100)
    assert ("dispatch", failed + 1) in lm.log
    assert ("collect", failed + 1) not in lm.log
    assert lm.steps[failed]["behind"]
    assert (a.step_failures, b.step_failures, c.step_failures) == (1, 1, 0)
    assert a.result(0) == _expected([2, 5, 7], 6)
    assert b.result(0) == _expected([3, 3], 7)
    assert c.result(0) == _expected([4, 4, 4], 4)
    assert set(lm.steps[failed + 1]["uploaded"]) == {"tokens", "tables",
                                                     "lens"}
    assert not sess._flights and lm.allocator.pages_in_use == 0


def test_a_step_behind_another_is_timed_from_the_landing_in_front():
    """``decode_step_seconds`` keeps meaning one step: the flight that
    was queued behind starts its clock when the one in front lands."""
    lm = RecordingLM()
    sess, _ = _full_batch(lm)
    sess.step()
    first, second = sess._flights
    assert (first.step.n, second.step.n) == (1, 2)
    dispatched_at = second.t0
    time.sleep(0.02)
    sess.step()
    assert sess._flights[0] is second
    assert second.t0 >= dispatched_at + 0.02
    assert sess._flights[1].t0 >= second.t0     # step 3: at its dispatch
    sess.fail_all(RuntimeError("stopped"))
    assert not sess._flights and sess.idle()


@pytest.mark.parametrize("dead", ["cancelled", "deadline"])
def test_a_dead_waiter_is_swept_from_the_queue_under_the_steps(dead):
    """The queue's sweep touches no row: it runs in the tick that finds
    a step still in flight too, and the next step goes behind that one
    all the same."""
    lm = RecordingLM(num_pages=64)
    sess, (a, b) = _full_batch(lm)
    sess.step()
    late = sess.submit(_request(lm, [4, 4, 4], 3, "late"))
    if dead == "cancelled":
        late.cancel()
    else:
        late.deadline = time.monotonic() - 1.0
    sess.step()
    assert late.done and late.finish_reason == dead and sess.waiting == 0
    assert [st.n for st in lm.in_flight] == [2, 3]
    sess.run(50)
    assert a.result(0) == _expected([2, 5, 7], 9)
    assert b.result(0) == _expected([3, 3], 9)
    assert ("prefill", 3, 0) not in lm.log[2:]
    assert lm.allocator.pages_in_use == 0
