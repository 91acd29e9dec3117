"""The shared base of the two hybrids (``decode/state_entry.py``): two
resources a sequence from one cache manager, the table row, admission,
what a state cannot do, the pools lost and rebuilt together, the gauges.
Every case runs over both models (``hybrid_models.py``: Olmo-Hybrid's
gated delta rule, Granite's Mamba-2), CPU, float32, toy widths; the
cases that take ``step_path`` run once more through the model's step
kernel interpreted."""

import numpy as np
import pytest

import jax

from hybrid_models import (HYBRIDS, greedy_by_reference, prompt,
                           through_the_cache)
from paddle_tpu import pallas as pk
from paddle_tpu.decode import model as dm
from paddle_tpu.decode.paged_kv import CacheManager, PoolExhausted, PoolsLost
from paddle_tpu.decode.session import (AdmissionRefused, BeamRequest,
                                       DecodeRequest, DecodeSession)
from paddle_tpu.decode.state_entry import UnsupportedOverState
from paddle_tpu.observability import metrics


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(params=list(HYBRIDS))
def hybrid(request):
    return HYBRIDS[request.param]


_MODELS = {}


@pytest.fixture
def model(hybrid):
    """One model a kind for the cases that leave it as they found it."""
    if hybrid.name not in _MODELS:
        _MODELS[hybrid.name] = hybrid.make()
    return _MODELS[hybrid.name]


@pytest.fixture(params=["loop", "kernel"])
def step_path(request):
    """How a decode step advances the states: in XLA, or by the model's
    Pallas kernel interpreted -> whether to take the kernel's sizes."""
    if request.param == "loop":
        yield False
        return
    pk.enable(True, interpret=True)
    try:
        yield True
    finally:
        pk.enable("auto", interpret=False)


# -- reuse, inactive slots ----------------------------------------------------


def test_a_reused_entry_equals_a_fresh_one(hybrid, step_path):
    """The LIFO free list hands the second sequence the first's entry
    and pages; the prefill writes the entry whole, so its logits are
    those of a fresh model."""
    first, second, tokens = prompt(90, 20), prompt(9, 21), prompt(4, 22)
    used = hybrid.make(step_path)
    through_the_cache(used, first, tokens)
    again = through_the_cache(used, second, tokens)
    fresh = through_the_cache(hybrid.make(step_path), second, tokens)
    np.testing.assert_array_equal(again, fresh)


def test_inactive_slots_touch_only_entry_0(hybrid, step_path):
    model = hybrid.make(step_path)
    ids = prompt(12, 30)
    pages = model.allocator.alloc(model.context_pages(ids, 2))
    entry = model.allocator.entry_of(pages)
    try:
        ctx, _, _ = model.prefill(ids, pages)
        before = [np.asarray(p) for p in model.extra_pools]
        tables = np.zeros((4, model.pages_per_seq), np.int32)
        tables[1] = model.pool_table(pages)
        lens = np.zeros((4,), np.int32)
        lens[1] = ctx
        model.decode(np.full((4, 1), 5, np.int64), [], tables, lens)
        after = [np.asarray(p) for p in model.extra_pools]
    finally:
        model.allocator.free(pages)
    for was, now in zip(before, after):          # states, then tails
        changed = {e for e in range(now.shape[1])
                   if not np.array_equal(was[:, e], now[:, e])}
        assert changed == {0, entry}


# -- the cache manager --------------------------------------------------------


def test_cache_manager_hands_out_pages_and_one_entry():
    cm = CacheManager(num_pages=10, state_entries=3)
    a = cm.alloc(4)
    assert cm.pages_of(a) == a[:3] and cm.entry_of(a) == a[3] - 10 == 1
    b = cm.alloc(3)
    assert cm.entry_of(b) == 2 and not cm.can_alloc(2)    # no entry left
    with pytest.raises(PoolExhausted):
        cm.alloc(2)
    assert cm.free_pages == 4                     # a refusal takes neither
    with pytest.raises(ValueError, match="forked"):
        cm.fork(a)
    cm.free(list(reversed(a)))                    # any order
    assert cm.free_entries == 1 and cm.free_pages == 7
    with pytest.raises(ValueError, match="double free"):
        cm.free([a[3]])
    assert not cm.can_alloc(9) and cm.can_alloc(8)        # pages short
    cm.free(b)
    assert cm.free_entries == 2 and cm.entries_in_use == 0


def test_table_row_is_the_page_run_then_the_entry(model):
    ids = [3, 4, 5, model.allocator.num_pages + 2]
    table = model.pool_table(ids)
    np.testing.assert_array_equal(table[:3], [3, 4, 5])
    assert not table[3:model.full_pages].any()
    assert table[model.full_pages] == 2 and len(table) == model.full_pages + 1


def _run(session, prompts, n):
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=n))
            for p in prompts]
    session.run(max_steps=800)
    return [r.result(1) for r in reqs]


def test_session_tokens_are_the_dense_oracles(hybrid):
    m = hybrid.make()
    prompts = [prompt(n, 40 + n) for n in (5, 17, 33)]
    got = _run(DecodeSession(m, max_slots=4), prompts, 4)
    assert got[:2] == [greedy_by_reference(m, p, 4) for p in prompts[:2]]
    assert len(got[2]) == 4
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0


def test_admission_waits_when_entries_run_out_and_both_come_back(hybrid):
    """Three entries usable, four slots: the fourth request waits for
    an entry, is seated when one comes back, and at the end every page
    and every entry is free."""
    m = hybrid.make(state_entries=4)
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(prompt(6, 50 + i),
                                         max_new_tokens=4 + 3 * i))
            for i in range(4)]
    session.step()
    assert session.active == 3 and session.waiting == 1
    assert m.allocator.free_entries == 0
    entries = metrics.REGISTRY.get("decode_state_entries")
    assert entries.value(state="in_use") == 3 and entries.value(
        state="free") == 0
    session.run(max_steps=200)
    assert [len(r.result(1)) for r in reqs] == [4, 7, 10, 13]
    assert m.allocator.free_entries == 3 and m.allocator.pages_in_use == 0


def test_admission_waits_when_pages_run_out_and_both_come_back(hybrid):
    m = hybrid.make(num_pages=9)                              # 8 usable
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(prompt(20, 60 + i),
                                         max_new_tokens=4))   # 3 pages
            for i in range(3)]
    session.step()
    assert session.active == 2 and session.waiting == 1
    assert m.allocator.free_entries == 2        # the waiter took no entry
    session.run(max_steps=200)
    assert all(len(r.result(1)) == 4 for r in reqs)
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0


def test_a_request_longer_than_a_sequence_is_refused_at_submit(model):
    session = DecodeSession(model, max_slots=2)
    with pytest.raises(AdmissionRefused) as e:
        session.submit(DecodeRequest(prompt(250, 1), max_new_tokens=40))
    assert e.value.reason == "too_long"


def test_what_a_state_cannot_do_yet_is_refused_by_name(model):
    session = DecodeSession(model, max_slots=2, prefix_cache=object(),
                            spec_draft=object())
    assert session.prefix_cache is None and session._spec_draft is None
    with pytest.raises(AdmissionRefused) as e:
        session.submit(BeamRequest([3, 4], beam_size=2))
    assert e.value.reason == "beam_unsupported"
    ids = model.allocator.alloc(3)
    try:
        with pytest.raises(UnsupportedOverState, match="cached"):
            model.prefill([3] * 12, ids, cached_len=8)
    finally:
        model.allocator.free(ids)
    with pytest.raises(UnsupportedOverState, match="fork"):
        model.copy_page(1, 2)
    with pytest.raises(UnsupportedOverState, match="verify"):
        model.verify_chunk(np.zeros((2, 3), np.int64), [], None, None)
    assert not (model.supports_prefix_cache or model.supports_fork
                or model.supports_verify)


def test_a_hybrid_holds_layers_of_both_kinds(hybrid):
    with pytest.raises(ValueError, match="both kinds"):
        hybrid.make(layer_types=(hybrid.recurrent,) * 3)


def test_pools_lost_rebuilds_pages_and_states_together(hybrid, monkeypatch):
    """A decode step that fails after consuming its donated buffers:
    all four are made anew, counted once, the seated sequences go back
    and complete with the oracle's tokens, and every page and entry
    comes back.  Both lanes are seated, so steps 1 and 2 are in flight
    after the first tick: the step that fails is the third, at its
    dispatch behind the second, which is dropped with it."""
    m = hybrid.make()
    session = DecodeSession(m, max_slots=2)
    prompts = [prompt(9, 70), prompt(14, 71)]
    want = [greedy_by_reference(m, p, 4) for p in prompts]
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=4))
            for p in prompts]
    session.step()
    assert len(session._flights) == 2
    real, failed = dm._decode_step, []

    def program(params, k_pool, v_pool, *args, extra, **kw):
        if not failed:
            failed.append(True)
            for pool in (k_pool, v_pool, *extra):
                pool.delete()
            raise RuntimeError("injected: the device halted")
        return real(params, k_pool, v_pool, *args, extra=extra, **kw)

    monkeypatch.setattr(dm, "_decode_step", program)
    n0 = dm._M_POOL_REBUILDS.value()
    old = m._cache()
    session.run(max_steps=300)
    assert dm._M_POOL_REBUILDS.value() == n0 + 1
    assert all(p.is_deleted() for p in old)
    assert [p.shape for p in m._cache()] == [p.shape for p in old]
    assert [r.result(1) for r in reqs] == want
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0


def test_a_failed_prefill_raises_pools_lost_and_gives_both_back(
        hybrid, monkeypatch):
    m = hybrid.make()

    def program(params, k_pool, v_pool, *args, extra, **kw):
        for pool in (k_pool, v_pool, *extra):
            pool.delete()
        raise RuntimeError("injected")

    monkeypatch.setattr(dm, "_prefill_bucket", program)
    ids = m.allocator.alloc(3)
    with pytest.raises(PoolsLost):
        m.prefill([3, 4, 5], ids)
    m.allocator.free(ids)
    assert not any(p.is_deleted() for p in m._cache())
    assert m.allocator.free_entries == 4


# -- gauges and health ----------------------------------------------------------


def test_gauges_and_health_show_both_resources(hybrid):
    from paddle_tpu.decode.engine import GenerationEngine

    m = hybrid.make()
    engine = GenerationEngine(m, max_slots=2, max_new_tokens=8)
    try:
        req = engine.submit(prompt(11, 80), max_new_tokens=8)
        assert len(req.result(60)) == 8
        info = engine.info()
        assert info["state_entries_total"] == 4
        assert info["state_entries_free"] == 4
        assert set(info["cache_rows"]) == {"full", "state"}
        assert set(info["cache_bytes"]) == {"full", "state"}
    finally:
        engine.stop()
    by_kind = metrics.REGISTRY.get("decode_cache_bytes")
    assert by_kind.value(kind="state") == 0 and by_kind.value(kind="full") == 0
    assert metrics.REGISTRY.get("decode_state_entries").value(
        state="free") == 4


def test_a_traced_step_counts_one_dispatch_a_recurrent_layer(hybrid,
                                                             step_path):
    """``pallas_dispatch_total{kernel=<the model's step kernel>}``:
    which way the states are advanced is decided once a recurrent layer
    while a step's program is traced (a slot count no other case
    traces)."""
    model = hybrid.make(step_path)
    # (the trace is cached by shape, not by the kernels' mode: a slot
    # count a path, where both paths take the same sizes)
    cache, S = model._cache(), 5 if step_path else 3

    def counts():
        return {p: pk._M_DISPATCH.value(kernel=hybrid.kernel, path=p)
                for p in ("compiled", "interpret", "reference")}

    before = counts()
    dm._decode_step.lower(
        model.params, *cache[:2], np.zeros((S, model.pages_per_seq), np.int32),
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        heads=model.heads, page_size=model.page_size, block=model.block,
        extra=cache[2:])
    moved = {p: n - before[p] for p, n in counts().items() if n != before[p]}
    assert moved == {"interpret" if step_path else "reference":
                     hybrid.types.count(hybrid.recurrent)}
