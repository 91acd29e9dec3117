"""Olmo-Hybrid behind /generate (``paddle_tpu/models/olmo_hybrid.py``):
Gated-DeltaNet layers whose recurrent state lives in a state entry a
sequence beside the K/V pages of the full layers, in one cache manager.
CPU, float32, toy widths with d_k != d_v and two periods of (linear x3,
full); the plain reference is ``perf/reference/olmo_hybrid_block.py``.
A decode step advances the states slot by slot in XLA here (off a TPU
the kernels are not dispatched); the cases that take ``step_path`` run
once more through ``pallas/gated_delta.py`` interpreted.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode import model as dm
from paddle_tpu.decode.paged_kv import CacheManager, PoolExhausted, PoolsLost
from paddle_tpu.decode.session import (AdmissionRefused, BeamRequest,
                                       DecodeRequest, DecodeSession)
from paddle_tpu.models import olmo_hybrid as oh
from paddle_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridLM,
                                           UnsupportedOverState)
from paddle_tpu.observability import metrics
from perf.reference import olmo_hybrid_block as ref

TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 2
SIZES = dict(vocab=96, d_model=32, num_heads=4, head_dim=8,
             layer_types=TYPES, intermediate_size=48,
             linear_num_key_heads=4, linear_num_value_heads=4,
             linear_key_head_dim=6, linear_value_head_dim=10,
             max_len=256, num_pages=40, page_size=8, pages_per_seq=32,
             state_entries=5, dtype="float32")
# d_v a whole tile of 8 rows: what the step kernel's fits() asks
KERNEL_SIZES = {**SIZES, "linear_value_head_dim": 16}


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return OlmoHybridLM(seed=3, **SIZES)


@pytest.fixture(params=["loop", "kernel"])
def step_path(request):
    """How a decode step advances the states -> (the sizes of a model
    that takes that path, the path ``pallas_dispatch_total`` counts)."""
    if request.param == "loop":
        yield SIZES, "reference"
        return
    pk.enable(True, interpret=True)
    try:
        yield KERNEL_SIZES, "interpret"
    finally:
        pk.enable("auto", interpret=False)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 96, n).tolist()


def _reference(m, ids, rows=None, ablate=None):
    b = m.block
    return np.asarray(ref.forward(
        m.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=m.heads, head_dim=b.head_dim, lin_heads=b.lin_heads,
        d_k=b.d_k, d_v=b.d_v, eps=b.eps, ablate=ablate, rows=rows))


def _greedy_by_reference(m, prompt, n):
    """The no-cache oracle: the reference's full forward per token
    (``dense_greedy`` runs the block's own dense forward op by op, a
    compile a shape)."""
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(np.argmax(_reference(m, ids, [len(ids) - 1])[0])))
    return ids[len(prompt):]


def _through_the_cache(m, prompt, tokens, slots=4, slot=2):
    """Prefill through the bucket's program, then the tokens teacher-
    forced through decode steps: the len(tokens) + 1 logits rows."""
    ids = m.allocator.alloc(m.context_pages(prompt, len(tokens)))
    try:
        ctx, _, last = m.prefill(prompt, ids)
        rows = [np.asarray(last)]
        tables = np.zeros((slots, m.pages_per_seq), np.int32)
        tables[slot] = m.pool_table(ids)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), m.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = m.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot]))
    finally:
        m.allocator.free(ids)
    return np.stack(rows)


# -- the recurrence -----------------------------------------------------------


def _plain_recurrence(q, k, v, g, beta, S):
    outs = []
    for t in range(q.shape[0]):
        a = np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum("hvk,hk->hv", a * S, k[t]))
        S = a * S + u[:, :, None] * k[t][:, None, :]
        outs.append(np.einsum("hvk,hk->hv", S, q[t]))
    return np.stack(outs), S


def _rows(T, seed, H=3, dk=6, dv=10):
    rng = np.random.RandomState(seed)
    q, k = rng.randn(T, H, dk), rng.randn(T, H, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, rng.randn(T, H, dv), -rng.uniform(0.001, 0.3, (T, H)),
            rng.uniform(0.0, 2.0, (T, H)), rng.randn(H, dv, dk))


@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_chunked_scan_is_the_plain_recurrence(T):
    """beta in (0, 2): the eigenvalue of a write may be negative."""
    args = _rows(T, T)
    want_o, want_S = _plain_recurrence(*args)
    o, S = oh.chunked_gated_delta(*(jnp.asarray(a, jnp.float32)
                                    for a in args))
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def test_one_token_step_is_a_row_of_the_recurrence():
    q, k, v, g, beta, S = _rows(1, 7)
    want_o, want_S = _plain_recurrence(q, k, v, g, beta, S)
    o, new = oh.step_gated_delta(*(jnp.asarray(a[0], jnp.float32)
                                   for a in (q, k, v, g, beta)),
                                 jnp.asarray(S, jnp.float32))
    np.testing.assert_allclose(o, want_o[0], atol=1e-5)
    np.testing.assert_allclose(new, want_S, atol=1e-5)


def test_padding_rows_leave_the_state_as_it_was():
    """g = 0, beta = 0 from row n on: the state after the bucket is the
    state after n rows, to the bit."""
    q, k, v, g, beta, S = (jnp.asarray(a, jnp.float32) for a in _rows(128, 3))
    n = 70
    live = (jnp.arange(128) < n)[:, None]
    _, padded = oh.chunked_gated_delta(
        q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0), S)
    _, cut = oh.chunked_gated_delta(q[:n], k[:n], v[:n], g[:n], beta[:n], S)
    np.testing.assert_allclose(padded, cut, atol=1e-6)


def test_causal_conv_taps_rows_t_minus_3_to_t():
    rng = np.random.RandomState(0)
    z, w = rng.randn(9, 5).astype(np.float32), rng.randn(4, 5).astype(
        np.float32)
    want = np.zeros_like(z)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += w[j] * z[t - 3 + j]
    np.testing.assert_allclose(oh.causal_conv(jnp.asarray(z), jnp.asarray(w)),
                               want, atol=1e-6)


# -- block == reference --------------------------------------------------------


@pytest.fixture(scope="module")
def decoded(model):
    prompt, tokens = _prompt(70, 1), _prompt(6, 2)
    got = _through_the_cache(model, prompt, tokens)
    rows = list(range(len(prompt) - 1, len(prompt) + len(tokens)))
    return prompt + tokens, rows, got


def test_prefill_then_decode_through_both_caches_match_the_reference(
        step_path):
    model = OlmoHybridLM(seed=3, **step_path[0])
    prompt, tokens = _prompt(70, 1), _prompt(6, 2)
    got = _through_the_cache(model, prompt, tokens)
    want = _reference(model, prompt + tokens,
                      list(range(len(prompt) - 1, len(prompt) + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("n", [1, 3, 8, 63, 64, 65, 130])
def test_prompt_lengths_round_a_chunk_and_a_bucket(model, n):
    prompt, tokens = _prompt(n, n), _prompt(3, n + 1)
    got = _through_the_cache(model, prompt, tokens)
    want = _reference(model, prompt + tokens,
                      list(range(n - 1, n + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_each_ablation_moves_the_logits(model, decoded, ablate):
    """What the benchmark's limits have to catch: every ablation of the
    reference, the rounded state and the rounded weights included, lies
    well outside float32 noise of the system's logits."""
    ids, rows, got = decoded
    floor = {"state_bf16": 2e-4, "fp8": 1e-2}.get(ablate, 1e-2)
    assert ref.rel_rms(got, _reference(model, ids, rows, ablate)) > floor


def test_dense_forward_is_the_reference(model):
    ids = _prompt(40, 5)
    logits, kept, _ = model._forward(jnp.asarray(ids, jnp.int32))
    assert ref.rel_rms(logits, _reference(model, ids)) < 1e-5
    assert len(kept) == len(TYPES)


# -- padding, reuse, inactive slots -------------------------------------------


def test_bucket_padding_leaves_state_and_conv_tail_untouched(model):
    """A prompt of 70 rows runs in the 128-row bucket: the entry written
    is the state and the tail after 70 rows, whatever ids fill the
    padding."""
    prompt = _prompt(70, 11)
    ids = model.allocator.alloc(model.context_pages(prompt, 0))
    entry = model.allocator.entry_of(ids)
    try:
        model.prefill(prompt, ids)
        state = np.asarray(model.state_pool[:, entry])
        tail = np.asarray(model.conv_pool[:, entry])
    finally:
        model.allocator.free(ids)
    _, kept, _ = model._forward(jnp.asarray(prompt, jnp.int32))
    lin = [k for k, t in zip(kept, TYPES) if t == LINEAR]
    d_k = model.block.d_k
    for i, (want_state, want_tail) in enumerate(lin):
        np.testing.assert_allclose(state[i][..., :d_k], want_state,
                                   atol=1e-5)
        assert not state[i][..., d_k:].any()         # the stored width
        np.testing.assert_allclose(tail[i], want_tail, atol=1e-6)


def test_a_conv_tail_of_a_short_prompt_is_zeros_before_row_0(model):
    prompt = _prompt(2, 12)
    ids = model.allocator.alloc(model.context_pages(prompt, 0))
    entry = model.allocator.entry_of(ids)
    try:
        model.prefill(prompt, ids)
        tail = np.asarray(model.conv_pool[:, entry])
    finally:
        model.allocator.free(ids)
    assert not tail[:, 0].any() and tail[:, 1:].any()


def test_a_reused_entry_equals_a_fresh_one(step_path):
    """The LIFO free list hands the second sequence the first's entry
    and pages; the prefill writes the entry whole, so its logits are
    those of a fresh model."""
    sizes = step_path[0]
    first, second, tokens = _prompt(90, 20), _prompt(9, 21), _prompt(4, 22)
    used = OlmoHybridLM(seed=3, **sizes)
    _through_the_cache(used, first, tokens)
    again = _through_the_cache(used, second, tokens)
    fresh = _through_the_cache(OlmoHybridLM(seed=3, **sizes), second, tokens)
    np.testing.assert_array_equal(again, fresh)


def test_inactive_slots_touch_only_entry_0(step_path):
    model = OlmoHybridLM(seed=3, **step_path[0])
    prompt = _prompt(12, 30)
    ids = model.allocator.alloc(model.context_pages(prompt, 2))
    entry = model.allocator.entry_of(ids)
    try:
        ctx, _, _ = model.prefill(prompt, ids)
        before = np.asarray(model.state_pool)
        tables = np.zeros((4, model.pages_per_seq), np.int32)
        tables[1] = model.pool_table(ids)
        lens = np.zeros((4,), np.int32)
        lens[1] = ctx
        model.decode(np.full((4, 1), 5, np.int64), [], tables, lens)
        after = np.asarray(model.state_pool)
    finally:
        model.allocator.free(ids)
    changed = {e for e in range(after.shape[1])
               if not np.array_equal(before[:, e], after[:, e])}
    assert changed == {0, entry}


def test_a_traced_step_counts_one_dispatch_a_linear_layer(step_path):
    """``pallas_dispatch_total{kernel="gated_delta_step"}``: which way
    the states are advanced is decided once a linear layer while a
    step's program is traced (a slot count no other case traces)."""
    sizes, path = step_path
    model = OlmoHybridLM(seed=3, **sizes)
    cache, S = model._cache(), 3

    def counts():
        return {p: pk._M_DISPATCH.value(kernel="gated_delta_step", path=p)
                for p in ("compiled", "interpret", "reference")}

    before = counts()
    dm._decode_step.lower(
        model.params, *cache[:2], np.zeros((S, model.pages_per_seq), np.int32),
        np.zeros((S,), np.int32), np.zeros((S,), np.int32),
        heads=model.heads, page_size=model.page_size, block=model.block,
        extra=cache[2:])
    moved = {p: n - before[p] for p, n in counts().items() if n != before[p]}
    assert moved == {path: TYPES.count(LINEAR)}


def test_pages_are_stored_at_whole_tiles_of_heads(model):
    """4 float32 heads are stored as 8 (``attention.storage_heads``);
    the padding heads stay zero."""
    assert model.k_pool.shape[3] == 8 and model.heads == 4
    assert not np.asarray(model.k_pool)[..., 4:, :].any()


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


def test_session_tokens_are_the_dense_oracles():
    m = OlmoHybridLM(seed=3, **SIZES)
    prompts = [_prompt(n, 40 + n) for n in (5, 17, 33)]
    got = _run(DecodeSession(m, max_slots=4), prompts, 4)
    assert got[:2] == [_greedy_by_reference(m, p, 4) for p in prompts[:2]]
    assert len(got[2]) == 4
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0


def test_admission_waits_when_entries_run_out_and_both_come_back():
    """Three entries usable, four slots: the fourth request waits for
    an entry, is seated when one comes back, and at the end every page
    and every entry is free."""
    m = OlmoHybridLM(seed=3, **{**SIZES, "state_entries": 4})
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(_prompt(6, 50 + i),
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


def test_admission_waits_when_pages_run_out_and_both_come_back():
    m = OlmoHybridLM(seed=3, **{**SIZES, "num_pages": 9})   # 8 usable
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(_prompt(20, 60 + i),
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
        session.submit(DecodeRequest(_prompt(250, 1), max_new_tokens=40))
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


def test_pools_lost_rebuilds_pages_and_states_together(monkeypatch):
    """A decode step that fails after consuming its donated buffers:
    all four are made anew, counted once, the seated sequences go back
    and complete with the oracle's tokens, and every page and entry
    comes back."""
    m = OlmoHybridLM(seed=3, **SIZES)
    session = DecodeSession(m, max_slots=2)
    prompts = [_prompt(9, 70), _prompt(14, 71)]
    want = [_greedy_by_reference(m, p, 3) for p in prompts]
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=3))
            for p in prompts]
    session.step()
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


def test_a_failed_prefill_raises_pools_lost_and_gives_both_back(monkeypatch):
    m = OlmoHybridLM(seed=3, **SIZES)

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


# -- gauges, health, scopes ---------------------------------------------------


def test_cache_rows_and_bytes_by_kind(model):
    lens = [10, 100]
    assert model.cache_rows(lens) == {"full": 110 * 2, "state": 2 * 6}
    b = model.cache_bytes(lens)
    assert b["full"] == 110 * 2 * (2 * 8 * 8 * 4)         # 8 stored heads
    entry = 6 * (4 * 10 * 128 * 4 + 3 * 4 * (2 * 6 + 10) * 4)
    assert b["state"] == 2 * entry == 2 * model.entry_bytes()


def test_gauges_and_health_show_both_resources():
    from paddle_tpu.decode.engine import GenerationEngine

    m = OlmoHybridLM(seed=3, **SIZES)
    engine = GenerationEngine(m, max_slots=2, max_new_tokens=8)
    try:
        req = engine.submit(_prompt(11, 80), max_new_tokens=8)
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


def test_named_scopes_place_the_linear_layers(model):
    S = 4
    cache = model._cache()
    text = {
        "_decode_step": dm._decode_step.lower(
            model.params, *cache[:2],
            np.zeros((S, model.pages_per_seq), np.int32),
            np.zeros((S,), np.int32), np.zeros((S,), np.int32),
            heads=model.heads, page_size=model.page_size, block=model.block,
            extra=cache[2:]).as_text(debug_info=True),
        "_prefill_bucket": dm._prefill_bucket.lower(
            model.params, *cache[:2], np.zeros((64,), np.int32),
            (np.zeros((64,), np.int32), np.int32(0)), np.int32(3),
            heads=model.heads, block=model.block,
            extra=cache[2:]).as_text(debug_info=True)}
    for scope in ("lin_attn/", "lin_attn_state/", "lin_attn_conv/",
                  "attn_full/"):
        assert scope in text["_decode_step"], scope
    for scope in ("lin_attn/", "lin_attn_scan/", "lin_attn_conv/",
                  "attn_full/"):
        assert scope in text["_prefill_bucket"], scope
    assert "lin_attn_scan/" not in text["_decode_step"]
