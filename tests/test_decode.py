"""Paged-KV decode engine (ISSUE 15).

Load-bearing guarantees:

- the host-side page allocator reuses freed pages and refuses (never
  corrupts) on exhaustion;
- the Pallas ragged paged-attention kernel matches its jnp reference;
- paged continuous-batching decode is **token-for-token identical** to
  the dense ``generation.py`` greedy oracle on the bundled NMT demo —
  ragged batchmates, slot churn, and page reuse change the schedule but
  never the tokens;
- the growing-KV transformer path matches its no-cache dense oracle;
- admission control degrades gracefully: too-long prompts and a full
  wait queue are refused (503 over HTTP), pool-busy requests queue and
  complete once pages free, deadlines 504.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid  # noqa: F401
from paddle_tpu.decode import (
    AdmissionRefused,
    DecodeRequest,
    DecodeSession,
    GenerationEngine,
    PageAllocator,
    PagedPool,
    PoolExhausted,
)


# ---------------------------------------------------------------------------
# page allocator / pool
# ---------------------------------------------------------------------------


def test_page_allocator_alloc_free_reuse():
    a = PageAllocator(8)            # pages 1..7 usable (0 reserved)
    assert a.free_pages == 7
    p1 = a.alloc(3)
    p2 = a.alloc(2)
    assert len(set(p1) | set(p2)) == 5 and 0 not in p1 + p2
    assert a.pages_in_use == 5
    a.free(p1)
    assert a.free_pages == 5
    # LIFO reuse: the just-freed pages come back first
    p3 = a.alloc(3)
    assert set(p3) == set(p1)
    a.free(p2)
    a.free(p3)
    assert a.pages_in_use == 0 and a.free_pages == 7


def test_page_allocator_exhaustion_refuses_without_partial_grab():
    a = PageAllocator(4)
    a.alloc(2)
    with pytest.raises(PoolExhausted):
        a.alloc(2)                  # only 1 free: must take none
    assert a.free_pages == 1


def test_page_allocator_rejects_double_free_and_null_page():
    a = PageAllocator(4)
    pages = a.alloc(1)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)
    with pytest.raises(ValueError):
        a.free([0])


def test_paged_pool_write_rows_and_table():
    pool = PagedPool(num_pages=6, page_size=4, feature_shape=(3,))
    pages = pool.allocator.alloc(2)
    rows = np.arange(5 * 3, dtype=np.float32).reshape(5, 3)
    pool.write_rows(pages, rows)
    got = np.asarray(pool.data)[np.asarray(pages)].reshape(8, 3)
    np.testing.assert_array_equal(got[:5], rows)
    np.testing.assert_array_equal(got[5:], 0.0)
    table = pool.page_table(pages, 4)
    assert list(table[:2]) == pages and list(table[2:]) == [0, 0]
    with pytest.raises(ValueError):
        pool.write_rows(pages, np.zeros((9, 3), np.float32))


# ---------------------------------------------------------------------------
# ragged paged-attention kernel
# ---------------------------------------------------------------------------


def test_ragged_paged_attention_kernel_matches_reference():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode import attention as A

    S, H, D, page, N, P = 5, 2, 16, 8, 12, 3
    q = jax.random.normal(jax.random.key(0), (S, H, D))
    kp = jax.random.normal(jax.random.key(1), (N, page, H, D))
    vp = jax.random.normal(jax.random.key(2), (N, page, H, D))
    rng = np.random.RandomState(0)
    pt = jnp.asarray(rng.randint(1, N, (S, P)), jnp.int32)
    # ragged lengths incl. one-page, partial-page and full-capacity
    lens = jnp.asarray([3, 8, 17, 1, 24], jnp.int32)
    ref = A.ragged_paged_attention_reference(q, kp, vp, pt, lens)
    ker = A.ragged_paged_attention(q, kp, vp, pt, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("lens", [
    [0, 1, 3, 9], [8, 16, 24, 17], [24, 24, 24, 24]],
    ids=["empty-seat-and-partial", "last-page-full", "all-columns-live"])
def test_the_steps_row_by_the_walk_matches_reference(dtype, lens):
    """The decode step's row on ungrouped heads as the chunk of one row
    after ``lens - 1`` cached rows (``ragged_paged_attention_walk``,
    ``T * G == 1``), interpreted: the reference's numbers and the
    ``(S, P)`` grid's on ragged lengths; an empty seat (``lens`` 0: no
    row cached, none its own) walks no page and writes zeros, as the
    grid does; a slot of one row, of exactly one page (8), of whole
    pages (16, 24: the last page full) and of every column."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode import attention as A

    S, H, D, page, N, P = 4, 2, 16, 8, 16, 3
    dt = jnp.dtype(dtype)
    q = jax.random.normal(jax.random.key(0), (S, H, D), dt)
    kp = jax.random.normal(jax.random.key(1), (N, page, H, D), dt)
    vp = jax.random.normal(jax.random.key(2), (N, page, H, D), dt)
    pt = jnp.asarray(np.random.RandomState(0).permutation(N - 1)[:S * P]
                     .reshape(S, P) + 1, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    ref = np.asarray(A.ragged_paged_attention_reference(
        q, kp, vp, pt, lens).astype(jnp.float32))
    grid = np.asarray(A.ragged_paged_attention(
        q, kp, vp, pt, lens, interpret=True).astype(jnp.float32))
    walk = np.asarray(A.ragged_paged_attention_walk(
        q, kp, vp, pt, lens, interpret=True).astype(jnp.float32))
    live = np.asarray(lens) > 0
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(walk[live], ref[live], atol=tol, rtol=tol)
    assert not walk[~live].any()
    # the same sums in the same order as the grid's: page by page
    np.testing.assert_allclose(walk, grid, atol=1e-6, rtol=1e-6)


def _pallas_calls(jaxpr):
    from jax._src import core

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("H, D, page, dtype, interpret, fits, grid", [
    (16, 128, 32, "bfloat16", False, True, (4,)),     # OLMoE's pages
    (16, 128, 32, "float32", False, True, (4,)),      # Cerebras'
    (16, 128, 16, "bfloat16", False, True, (4,)),     # chip_smoke's model
    (32, 128, 128, "bfloat16", False, False, (4, 3)),  # the hybrid's: 1 MB
    (16, 64, 32, "bfloat16", False, False, (4, 3)),   # heads of 64 lanes
    (4, 8, 8, "float32", False, False, (4, 3)),       # the toy step, compiled
    (4, 8, 8, "float32", True, False, (4,)),          # interpreted: every shape
    (32, 128, 128, "bfloat16", True, False, (4,)),
], ids=["olmoe", "cerebras", "smoke", "hybrid-1MB-pages", "heads-of-64",
        "toy-compiled", "toy-interpreted", "hybrid-interpreted"])
def test_paged_attention_walks_where_walk_fits_holds(
        monkeypatch, H, D, page, dtype, interpret, fits, grid):
    """Which kernel the step's ungrouped row runs follows from the
    pool's shape and dtype alone (``walk_fits``): one grid step a slot
    where the compiled walk takes the pages, a grid step a (slot, table
    column) where it refuses them; interpreted, every shape walks.  Both
    under the one name ``ragged_paged_attention`` and one dispatch
    decision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import attention as A

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", interpret)
    S, N, P = 4, 9, 3
    dt = jnp.dtype(dtype)
    assert A.walk_fits(dt, page, H, D) == fits
    sds = jax.ShapeDtypeStruct
    counter = pk._M_DISPATCH
    path = "interpret" if interpret else "compiled"
    before = counter.value(kernel="ragged_paged_attention", path=path)
    jaxpr = jax.make_jaxpr(lambda *a: A.paged_attention(*a))(
        sds((S, H, D), dt), sds((N, page, H, D), dt),
        sds((N, page, H, D), dt), sds((S, P), jnp.int32),
        sds((S,), jnp.int32))
    assert counter.value(kernel="ragged_paged_attention",
                         path=path) == before + 1
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert [(c.params["name"], c.params["grid_mapping"].grid)
            for c in calls] == [("ragged_paged_attention", grid)]


def test_dense_prefill_attention_causal_reference():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode.attention import dense_prefill_attention

    T, H, D = 6, 2, 8
    q = jax.random.normal(jax.random.key(3), (T, H, D))
    k = jax.random.normal(jax.random.key(4), (T, H, D))
    v = jax.random.normal(jax.random.key(5), (T, H, D))
    out = np.asarray(dense_prefill_attention(q, k, v, causal=True))
    # row t of the causal output only sees keys <= t: recompute per-row
    for t in range(T):
        sub = np.asarray(dense_prefill_attention(
            q[:t + 1], k[:t + 1], v[:t + 1], causal=True))
        np.testing.assert_allclose(out[t], sub[t], atol=1e-5)


# ---------------------------------------------------------------------------
# NMT demo: paged decode vs the dense generation.py greedy oracle
# ---------------------------------------------------------------------------


class _Params:
    def __init__(self):
        from paddle_tpu.executor import Scope

        self.scope = Scope()


def _make_beam_gen(max_length=7):
    from demos.seq2seq.gen_config import make_beam_gen

    return make_beam_gen(beam_size=1, max_length=max_length)


@pytest.fixture(scope="module")
def nmt_world():
    """One shared parameter scope + dense oracle + paged engine.

    The oracle's SequenceGenerator initializes the parameters (fixed
    startup seeds); the paged model reuses them BY NAME from the same
    scope — the parity below is therefore exact, not statistical.
    """
    from paddle_tpu.generation import SequenceGenerator

    params = _Params()
    oracle = SequenceGenerator(_make_beam_gen(), params)
    engine = GenerationEngine.for_seq2seq(
        _make_beam_gen(), params, num_pages=24, page_size=8,
        pages_per_seq=2, max_slots=3, max_new_tokens=7, beam_max=3)
    yield oracle, engine
    engine.stop()


def test_paged_decode_token_parity_with_dense_greedy_oracle(nmt_world):
    oracle, engine = nmt_world
    # ragged lengths, more requests than slots: forces admission churn,
    # slot reuse and page free-list reuse mid-run
    srcs = [[4, 7, 2], [3, 9, 5, 6], [2, 2, 11, 8, 1], [5, 5],
            [9, 8, 7, 6, 5, 4], [1, 12, 13]]
    want = [oracle.generate_greedy([s]) for s in srcs]

    streamed = {i: [] for i in range(len(srcs))}
    reqs = [engine.submit(s, on_token=lambda t, i=i: streamed[i].append(t))
            for i, s in enumerate(srcs)]
    got = [r.result(timeout=300) for r in reqs]
    assert got == want, "paged decode diverged from the dense oracle"
    # streaming callbacks delivered every token in order
    assert [streamed[i] for i in range(len(srcs))] == want
    # every page returned to the pool after eviction
    assert engine.model.allocator.pages_in_use == 0


def test_paged_decode_steady_state_compile_cache_hit_rate_is_one(nmt_world):
    from paddle_tpu.observability import metrics as M

    oracle, engine = nmt_world

    def counts():
        snap = M.snapshot()
        out = {}
        for name in ("executor_compile_cache_miss_total",
                     "executor_compile_cache_hit_total"):
            out[name] = sum(r["value"] for r in
                            snap.get(name, {"values": []})["values"])
        return out

    # warm: every program (prefill bucket + decode step) compiled
    engine.submit([4, 7, 2]).result(timeout=300)
    c0 = counts()
    reqs = [engine.submit(s) for s in ([3, 9, 5], [2, 6, 1, 5], [7, 7])]
    for r in reqs:
        r.result(timeout=300)
    c1 = counts()
    misses = c1["executor_compile_cache_miss_total"] \
        - c0["executor_compile_cache_miss_total"]
    hits = c1["executor_compile_cache_hit_total"] \
        - c0["executor_compile_cache_hit_total"]
    assert misses == 0, "batch-composition churn re-traced a program"
    assert hits > 0


def test_session_requeues_when_pages_busy_and_completes(nmt_world):
    oracle, engine = nmt_world
    # 3 slots but submit 5: later requests wait for pages/slots and
    # must still finish with oracle-identical tokens
    srcs = [[4, 7, 2]] * 5
    want = oracle.generate_greedy([srcs[0]])
    reqs = [engine.submit(s) for s in srcs]
    for r in reqs:
        assert r.result(timeout=300) == want


def test_admission_refusal_too_long_and_queue_full(nmt_world):
    oracle, engine = nmt_world
    # ctx capacity = pages_per_seq * page_size = 16 < feeder bucket of
    # a 17-token prompt (pads to 32)
    with pytest.raises(AdmissionRefused) as ei:
        engine.submit(list(range(2, 12)) + [2] * 7)
    assert ei.value.reason == "too_long"


def test_pool_exhaustion_is_admission_refusal_not_crash():
    """A session whose pool can hold ONE sequence: the second concurrent
    request queues (pool busy), a too-long one is refused, and live
    sequences finish unharmed."""
    from paddle_tpu.decode.model import TinyDecoderLM

    lm = TinyDecoderLM(vocab=16, d_model=8, num_heads=2, num_layers=1,
                       num_pages=3, page_size=4, pages_per_seq=2, seed=1)
    # no stepper thread here: both live submissions sit in the wait
    # queue until run(), so the cap must admit exactly those two
    sess = DecodeSession(lm, max_slots=2, max_waiting=2)
    with pytest.raises(AdmissionRefused) as ei:
        sess.submit(DecodeRequest([1] * 7, max_new_tokens=4))  # 11 > 8 rows
    assert ei.value.reason == "too_long"
    r1 = sess.submit(DecodeRequest([1, 2, 3], max_new_tokens=4))
    r2 = sess.submit(DecodeRequest([1, 4], max_new_tokens=4))
    with pytest.raises(AdmissionRefused) as ei:
        sess.submit(DecodeRequest([1, 5], max_new_tokens=4))
    assert ei.value.reason == "queue_full"
    sess.run(max_steps=100)
    assert len(r1.result(0)) > 0 and len(r2.result(0)) > 0
    assert lm.allocator.pages_in_use == 0


def test_expired_queued_requests_release_wait_capacity():
    """A dead (deadline-expired) waiter must not occupy max_waiting
    capacity while slots are busy — the sweep runs every tick, not
    only when a slot frees."""
    import time

    from paddle_tpu.decode.model import TinyDecoderLM

    lm = TinyDecoderLM(vocab=16, d_model=8, num_heads=2, num_layers=1,
                       num_pages=8, page_size=4, pages_per_seq=2, seed=3)
    sess = DecodeSession(lm, max_slots=1, max_waiting=1)
    r1 = sess.submit(DecodeRequest([1, 2], max_new_tokens=6))
    sess.step()                       # r1 takes the only slot
    expired = sess.submit(DecodeRequest(
        [1, 3], max_new_tokens=2, deadline=time.monotonic() - 1.0))
    sess.step()                       # slot still busy; sweep must run
    assert expired.done and expired.finish_reason == "deadline"
    r3 = sess.submit(DecodeRequest([1, 4], max_new_tokens=2))
    sess.run(max_steps=100)
    r1.result(0)
    r3.result(0)


# ---------------------------------------------------------------------------
# growing-KV transformer path
# ---------------------------------------------------------------------------


def test_tiny_lm_paged_decode_matches_dense_oracle():
    from paddle_tpu.decode.model import TinyDecoderLM

    lm = TinyDecoderLM(vocab=32, d_model=16, num_heads=2, num_layers=2,
                       num_pages=32, page_size=4, pages_per_seq=8, seed=0)
    prompts = [[1, 5, 9], [1, 7], [1, 3, 4, 8, 2], [1, 9, 9, 2]]
    want = [lm.dense_greedy(p, 8) for p in prompts]
    sess = DecodeSession(lm, max_slots=2)     # forces churn
    reqs = [sess.submit(DecodeRequest(p, max_new_tokens=8))
            for p in prompts]
    sess.run(max_steps=400)
    assert [r.result(0) for r in reqs] == want
    assert lm.allocator.pages_in_use == 0


# ---------------------------------------------------------------------------
# bucketed jitted prefill (ISSUE 25)
# ---------------------------------------------------------------------------


def _bucket_lm(**kw):
    """A toy LM whose prefill ladder is 64, 128, 192 (the capacity)."""
    from paddle_tpu.decode.model import TinyDecoderLM

    cfg = dict(vocab=32, d_model=16, num_heads=2, num_layers=2,
               max_len=256, num_pages=64, page_size=8, pages_per_seq=24,
               seed=0)
    cfg.update(kw)
    return TinyDecoderLM(**cfg)


def _prompt(T, vocab=32):
    return np.random.RandomState(T).randint(1, vocab, T).tolist()


def _pool_rows(pool, pages):
    """(L, len(pages) * page_size, H, dh): a sequence's rows in order."""
    a = np.asarray(pool)[:, list(pages)]
    return a.reshape(a.shape[0], -1, *a.shape[3:])


@pytest.mark.parametrize("T,bucket", [(63, 64), (64, 64), (65, 128),
                                      (129, 192)])
def test_bucketed_prefill_matches_eager_forward(T, bucket):
    """Below, at and just above a bucket edge, and in the capped top
    bucket: the last real row's logits and the K/V rows < T are the
    eager dense forward's."""
    import jax.numpy as jnp

    lm = _bucket_lm()
    assert lm.prefill_bucket(T) == bucket
    prompt = _prompt(T)
    pages = lm.allocator.alloc(lm.context_pages(prompt, 1))
    n, states, logits = lm.prefill(prompt, pages)
    want, ks, vs = lm._forward(jnp.asarray(prompt, jnp.int32))
    assert (n, states) == (T, [])
    assert logits.shape == (lm.vocab,)
    np.testing.assert_allclose(logits, np.asarray(want[-1]), atol=1e-5)
    np.testing.assert_allclose(_pool_rows(lm.k_pool, pages)[:, :T],
                               np.asarray(ks), atol=1e-5)
    np.testing.assert_allclose(_pool_rows(lm.v_pool, pages)[:, :T],
                               np.asarray(vs), atol=1e-5)


def test_padded_prefill_leaves_other_sequences_pages_untouched():
    """65 tokens on 9 pages (72 rows) run as the 128-row bucket: the 56
    rows past the sequence's pages go to the null page 0, so a live
    neighbour's pages, and every free page, are bit-identical."""
    lm = _bucket_lm()
    a_pages = lm.allocator.alloc(5)
    lm.prefill(_prompt(40), a_pages)
    b_prompt = _prompt(65)
    b_pages = lm.allocator.alloc(lm.context_pages(b_prompt, 1))
    assert len(b_pages) * lm.page_size < lm.prefill_bucket(65)
    others = [p for p in range(1, lm.allocator.num_pages)
              if p not in b_pages]
    before = [np.asarray(pool)[:, others].copy()
              for pool in (lm.k_pool, lm.v_pool)]
    assert np.abs(before[0][:, :len(a_pages)]).max() > 0
    lm.prefill(b_prompt, b_pages)
    for was, pool in zip(before, (lm.k_pool, lm.v_pool)):
        np.testing.assert_array_equal(np.asarray(pool)[:, others], was)
    assert np.abs(_pool_rows(lm.k_pool, b_pages)[:, :65]).min() > 0


def test_one_prefill_program_per_bucket_and_session_parity():
    """Prompts of different lengths AND page counts inside one bucket
    run one traced program; a session over them (and a second bucket)
    still equals the dense oracle token for token."""
    from paddle_tpu.decode import model as dm

    lm = _bucket_lm(vocab=37, seed=3)       # shapes no other test traces
    count = dm._M_PREFILL_PROGRAMS.value
    n64, n128 = count(bucket="64"), count(bucket="128")
    for T, budget in ((9, 4), (40, 30), (64, 1)):
        prompt = _prompt(T, 37)
        pages = lm.allocator.alloc(lm.context_pages(prompt, budget))
        lm.prefill(prompt, pages)
        lm.allocator.free(pages)
    assert count(bucket="64") == n64 + 1
    assert count(bucket="128") == n128
    prompts = [_prompt(T, 37) for T in (5, 33, 64, 70)]
    want = [lm.dense_greedy(p, 6) for p in prompts]
    sess = DecodeSession(lm, max_slots=2)
    reqs = [sess.submit(DecodeRequest(p, max_new_tokens=6))
            for p in prompts]
    sess.run(max_steps=400)
    assert [r.result(0) for r in reqs] == want
    assert count(bucket="64") == n64 + 1
    assert count(bucket="128") == n128 + 1
    assert lm.allocator.pages_in_use == 0


def test_prompt_over_the_top_bucket_is_refused_at_submit():
    """The top bucket is the sequence capacity, so a prompt past it is
    ``too_long`` at submit; where ``max_len`` is the smaller cap the
    model refuses on the host, before anything is traced."""
    from paddle_tpu.decode import model as dm

    def traced():
        return sum(v["value"]
                   for v in dm._M_PREFILL_PROGRAMS.snapshot()["values"])

    lm = _bucket_lm()
    before = traced()
    sess = DecodeSession(lm, max_slots=2)
    with pytest.raises(AdmissionRefused) as ei:
        sess.submit(DecodeRequest(_prompt(193), max_new_tokens=1))
    assert ei.value.reason == "too_long"
    short = _bucket_lm(max_len=100)          # 100 positions < 192 rows
    assert short.prefill_bucket(100) == 100
    pages = short.allocator.alloc(13)
    with pytest.raises(ValueError, match="101 tokens"):
        short.prefill(_prompt(101), pages)
    short.allocator.free(pages)
    # through a session it fits the pages, so it is seated, fails its
    # prefill on the host and gives its pages back
    sess = DecodeSession(short, max_slots=1)
    req = sess.submit(DecodeRequest(_prompt(101), max_new_tokens=1))
    sess.run(max_steps=4)
    assert req.finish_reason == "error"
    assert isinstance(req.error, ValueError)
    assert short.allocator.pages_in_use == 0
    assert traced() == before


def test_prefill_counters_and_span_args():
    """A 70-token prompt is 70 real and 128 computed rows, and the
    ``decode.prefill`` span says so."""
    from paddle_tpu import observability
    from paddle_tpu.decode import model as dm

    lm = _bucket_lm()
    real, padded = dm._M_PREFILL_TOKENS.value, dm._M_PREFILL_PADDED.value
    r0, p0 = real(), padded()
    sess = DecodeSession(lm, max_slots=1)
    req = sess.submit(DecodeRequest(_prompt(70), max_new_tokens=2))
    with observability.recording() as ring:
        sess.run(max_steps=8)
        spans = [e for e in ring.events() if e["name"] == "decode.prefill"]
    assert len(req.result(0)) == 2
    assert (real() - r0, padded() - p0) == (70, 128)
    assert len(spans) == 1
    assert spans[0]["args"]["bucket"] == 128
    assert spans[0]["args"]["pad"] == 58
    assert spans[0]["args"]["rid"] == req.rid


# ---------------------------------------------------------------------------
# K/V rows written and read in the donated pools in place (ISSUE 27)
# ---------------------------------------------------------------------------


def _seat(lm, T, budget):
    """Prefill a ``T``-token prompt into fresh pages -> (prompt, pages)."""
    prompt = _prompt(T)
    pages = lm.allocator.alloc(lm.context_pages(prompt, budget))
    lm.prefill(prompt, pages)
    return prompt, pages


def _rows(lm, mask, pages, first, n):
    """Mark rows ``first .. first + n - 1`` of a sequence in every layer
    of the (L, N, pg) ``mask``."""
    for t in range(first, first + n):
        mask[:, pages[t // lm.page_size], t % lm.page_size] = True


@pytest.mark.parametrize("op", ["decode", "verify", "suffix_prefill",
                                "copy_page"])
def test_programs_consume_their_pools_and_write_only_their_rows(op):
    """Each of the four programs that take the pools donates them (the
    pool objects it was given are deleted) and changes, in every layer,
    exactly the rows it appends: a slot's next row(s) in that slot's
    pages, an inactive slot's in page 0 of each layer.  A wrong layer
    offset into the whole-pool view would write another layer's rows."""
    lm = _bucket_lm(num_layers=3)
    L, N, pg = lm.layers, lm.allocator.num_pages, lm.page_size
    a_prompt, a_pages = _seat(lm, 21, 8)
    b_prompt, b_pages = _seat(lm, 40, 8)
    S = 3                                # slot 1 stays inactive
    tables = np.zeros((S, lm.pages_per_seq), np.int32)
    tables[0], tables[2] = lm.pool_table(a_pages), lm.pool_table(b_pages)
    lens = np.array([21, 1, 40], np.int64)
    write = np.zeros((L, N, pg), bool)
    if op == "suffix_prefill":
        # a third prompt over two of A's pages: rows 16..25 are its own
        c_prompt = a_prompt[:16] + _prompt(10)
        c_pages = lm.allocator.fork(a_pages[:2]) + lm.allocator.alloc(2)
        _rows(lm, write, c_pages, 16, 10)
    elif op == "copy_page":
        src, (dst,) = b_pages[1], lm.allocator.alloc(1)
        write[:, dst] = True
    else:
        k = 1 if op == "decode" else 3
        _rows(lm, write, a_pages, 21, k)
        _rows(lm, write, b_pages, 40, k)
        write[:, 0, 1:1 + k] = True      # the inactive slot's null page
    old = (lm.k_pool, lm.v_pool)
    was = [np.asarray(pool).copy() for pool in old]
    if op == "decode":
        lm.decode(np.full((S, 1), 5, np.int64), [], tables, lens)
    elif op == "verify":
        lm.verify_chunk(np.full((S, 3), 5, np.int64), [], tables, lens)
    elif op == "suffix_prefill":
        lm.prefill(c_prompt, c_pages, cached_len=16)
    else:
        lm.copy_page(src, dst)
    assert all(pool.is_deleted() for pool in old)
    for before, pool in zip(was, (lm.k_pool, lm.v_pool)):
        now = np.asarray(pool)
        assert now.shape == before.shape == (L, N, pg, lm.heads, lm.dh)
        np.testing.assert_array_equal(now[~write], before[~write])
        changed = (now[write] != before[write]).any(axis=(-1, -2))
        assert changed.all(), (op, int(changed.sum()), int(write.sum()))
        if op == "copy_page":
            np.testing.assert_array_equal(now[:, dst], before[:, src])


# ---------------------------------------------------------------------------
# serving endpoint
# ---------------------------------------------------------------------------


def _gen_post(addr, payload, timeout=300):
    req = urllib.request.Request(
        f"http://{addr}/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture(scope="module")
def gen_server(nmt_world):
    from paddle_tpu.serving import InferenceServer

    oracle, engine = nmt_world
    srv = InferenceServer(None, generator=engine)
    yield oracle, srv
    srv._httpd.shutdown()       # leave the module-scoped engine running
    srv._httpd.server_close()


def test_generate_endpoint_streams_oracle_tokens(gen_server):
    oracle, srv = gen_server
    want = oracle.generate_greedy([[4, 7, 2]])

    code, body = _gen_post(srv.address, {"src": [4, 7, 2],
                                         "stream": False})
    assert code == 200
    doc = json.loads(body)
    assert doc["ids"] == want

    code, body = _gen_post(srv.address, {"src": [4, 7, 2]})
    assert code == 200
    lines = [json.loads(x) for x in body.splitlines() if x.strip()]
    assert [x["token"] for x in lines if "token" in x] == want
    assert lines[-1]["done"] and lines[-1]["ids"] == want

    health = json.loads(urllib.request.urlopen(
        f"http://{srv.address}/health", timeout=30).read())
    assert health["generation"]["slots"] == 3

    metrics = urllib.request.urlopen(
        f"http://{srv.address}/metrics", timeout=30).read().decode()
    assert "decode_tokens_total" in metrics
    assert "decode_pages_in_use" in metrics


def test_generate_endpoint_beam_matches_oracle(gen_server):
    oracle, srv = gen_server
    src = [3, 9, 5, 6]
    want = oracle.generate([src], beam_size=2)

    code, body = _gen_post(srv.address, {"src": src, "beam": 2})
    assert code == 200
    doc = json.loads(body)
    assert doc["ids"] == want[0][1]
    got = [(b["score"], b["ids"]) for b in doc["beams"]]
    assert [t for _, t in got] == [t for _, t in want]
    for (gs, _), (ws, _) in zip(got, want):
        assert abs(gs - ws) < 1e-5


def test_generate_endpoint_rejects_bad_payloads(gen_server):
    oracle, srv = gen_server
    code, body = _gen_post(srv.address, {"src": "nope"})
    assert code == 400
    code, body = _gen_post(srv.address, {"src": [1], "nucleus": 2})
    assert code == 400 and b"nucleus" in body
    code, body = _gen_post(srv.address, {"src": [1], "beam": 0})
    assert code == 400 and b"beam" in body
    # beam wider than the engine cap -> 503 admission refusal
    code, body = _gen_post(srv.address, {"src": [1], "beam": 4})
    assert code == 503
    assert json.loads(body)["reason"] == "beam_too_wide"
    # too-long prompt -> 503 admission refusal with the reason
    code, body = _gen_post(srv.address,
                           {"src": list(range(2, 12)) + [2] * 7,
                            "stream": False})
    assert code == 503
    assert json.loads(body)["reason"] == "too_long"


def test_generate_endpoint_deadline_504():
    """An already-expired deadline surfaces as 504, not a hang."""
    from paddle_tpu.decode.model import TinyDecoderLM
    from paddle_tpu.serving import InferenceServer

    lm = TinyDecoderLM(vocab=16, d_model=8, num_heads=2, num_layers=1,
                       num_pages=8, page_size=4, pages_per_seq=2, seed=2)
    engine = GenerationEngine(lm, max_slots=1, max_new_tokens=4)
    srv = InferenceServer(None, generator=engine,
                          request_timeout=1e-6)
    try:
        code, body = _gen_post(srv.address, {"src": [1, 2],
                                             "stream": False})
        assert code == 504
        # streaming too: the 200 is held until the first token, so a
        # request that dies of its deadline pre-stream is a real 504,
        # not a 200 trickling out an error line
        code, body = _gen_post(srv.address, {"src": [1, 2]})
        assert code == 504
        # and the engine itself serves the transformer model live (the
        # default prompt_of must hand the LM its id list unwrapped)
        assert len(engine.submit([1, 2], max_new_tokens=3)
                   .result(timeout=120)) > 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# generation.py satellites: per-call beam width reuses the compiled step
# ---------------------------------------------------------------------------


def test_sequence_generator_per_call_beam_width_hits_compile_cache(
        nmt_world):
    from paddle_tpu.observability import metrics as M

    oracle, _ = nmt_world

    def misses():
        snap = M.snapshot().get("executor_compile_cache_miss_total",
                                {"values": []})
        return sum(r["value"] for r in snap["values"])

    out2 = oracle.generate([[4, 7, 2]], beam_size=2)     # compile @ k=2
    m0 = misses()
    # repeated width switches re-use the per-shape compiled steps:
    # zero new traces (the old workflow — a fresh SequenceGenerator per
    # width — rebuilt uname'd programs and re-traced every time)
    again = oracle.generate([[4, 7, 2]], beam_size=2)
    oracle.generate([[3, 9]], beam_size=2, max_length=5)
    assert misses() == m0
    assert [ids for _, ids in again] == [ids for _, ids in out2]
    g1 = oracle.generate([[4, 7, 2]], beam_size=1)
    assert misses() == m0                               # k=1 was warm too
    assert g1[0][1] == oracle.generate_greedy([[4, 7, 2]])
