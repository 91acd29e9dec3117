"""Shared-KV generation (ISSUE 18): copy-on-write page refcounts,
prefix caching, speculative decoding, and beam search over sibling
slots.

The allocator invariants are fuzzed against a pure-python model; every
decode-path test checks token parity against a dense oracle AND that
the page pool is fully recovered afterwards (the double-free /
leaked-page class of bug is the whole risk of refcounted sharing).
"""

import numpy as np
import pytest

from paddle_tpu.decode.paged_kv import PageAllocator, PagedPool, cow_split


# ---------------------------------------------------------------------------
# allocator refcount invariants (property/fuzz)
# ---------------------------------------------------------------------------


def test_alloc_fork_free_refcounts():
    a = PageAllocator(8)
    pages = a.alloc(3)
    assert all(a.refcount(p) == 1 for p in pages)
    assert a.pages_in_use == 3 and a.total_refs == 3

    forked = a.fork(pages)
    assert forked == pages                  # fork aliases, never copies
    assert a.pages_in_use == 3              # no new memory
    assert a.total_refs == 6
    assert all(a.is_shared(p) for p in pages)
    assert a.pages_shared == 3

    # first free only drops refs; pages stay allocated
    assert a.free(forked) == []
    assert a.pages_in_use == 3 and a.pages_shared == 0
    # second free actually releases
    assert sorted(a.free(pages)) == sorted(pages)
    assert a.pages_in_use == 0 and a.free_pages == 7


def test_free_unreferenced_page_raises():
    a = PageAllocator(8)
    (p,) = a.alloc(1)
    a.free([p])
    with pytest.raises(ValueError):
        a.free([p])
    with pytest.raises(ValueError):
        a.free([0])                          # reserved null page


def test_cow_split_copies_shared_only():
    a = PageAllocator(8)
    pages = a.alloc(2)
    # private page: no copy, returns None
    assert cow_split(a, list(pages), 0, []) is None

    forked = a.fork(pages)
    mine = list(pages)
    copies = []
    new = cow_split(a, mine, 1, [lambda s, d: copies.append((s, d))])
    assert new is not None and new != pages[1]
    assert mine[1] == new and copies == [(pages[1], new)]
    assert a.refcount(pages[1]) == 1         # the other holder keeps it
    assert a.refcount(new) == 1
    a.free(mine)
    a.free(forked)
    assert a.pages_in_use == 0


def test_allocator_refcount_fuzz():
    """Random admit/fork/cow-write/free against a reference model: no
    page is ever double-freed or leaked, shared pages are never
    released early, and the pool is fully recovered at the end."""
    rng = np.random.RandomState(0)
    a = PageAllocator(32)
    seqs = []                               # each: list of page ids

    def model_refs():
        refs = {}
        for s in seqs:
            for p in s:
                refs[p] = refs.get(p, 0) + 1
        return refs

    for _ in range(2000):
        op = rng.randint(4)
        if op == 0 and a.can_alloc(3):                       # admit
            seqs.append(a.alloc(int(rng.randint(1, 4))))
        elif op == 1 and seqs:                               # fork
            seqs.append(a.fork(seqs[rng.randint(len(seqs))]))
        elif op == 2 and seqs:                               # CoW write
            s = seqs[rng.randint(len(seqs))]
            i = int(rng.randint(len(s)))
            if a.is_shared(s[i]) and a.can_alloc(1):
                old = s[i]
                new = cow_split(a, s, i, [])
                assert new is not None and s[i] == new
                assert a.refcount(new) == 1
                assert a.refcount(old) == model_refs().get(old)
        elif op == 3 and seqs:                               # evict
            before = model_refs()
            s = seqs.pop(rng.randint(len(seqs)))
            freed = a.free(s)
            # only pages whose last reference this was came back
            for p in set(s):
                expected_gone = before[p] == s.count(p)
                assert (p in freed) == expected_gone
        # global invariants, every step
        refs = model_refs()
        assert a.pages_in_use == len(refs)
        assert a.total_refs == sum(refs.values())
        assert a.pages_in_use + a.free_pages == 31           # page 0 reserved
        for p, n in refs.items():
            assert a.refcount(p) == n

    for s in seqs:
        a.free(s)
    assert a.pages_in_use == 0 and a.free_pages == 31


def test_pool_copy_page_copies_rows():
    pool = PagedPool(num_pages=4, page_size=2, feature_shape=(2, 4))
    src, dst = pool.allocator.alloc(2)
    rows = np.arange(2 * 2 * 4, dtype=np.float32).reshape(2, 2, 4)
    pool.write_rows([src], rows)
    pool.copy_page(src, dst)
    np.testing.assert_array_equal(np.asarray(pool.data[dst]),
                                  np.asarray(pool.data[src]))
    np.testing.assert_array_equal(np.asarray(pool.data[src]), rows)


# ---------------------------------------------------------------------------
# LM fixtures: one tiny decoder shared per module
# ---------------------------------------------------------------------------


PROMPT = [1, 5, 9, 3, 7, 2, 8, 4, 6, 2, 3]


def _mk(seed=3, **kw):
    from paddle_tpu.decode.model import TinyDecoderLM

    kw.setdefault("num_pages", 64)
    return TinyDecoderLM(seed=seed, **kw)


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------


def test_prefix_cache_parity_hits_and_pool_recovery():
    from paddle_tpu.decode.prefix import PrefixCache
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession

    m = _mk()
    cache = PrefixCache(m.allocator, m.page_size, capacity_pages=16)
    sess = DecodeSession(m, max_slots=4, prefix_cache=cache)
    oracle = m.dense_greedy(PROMPT, 8)

    r1 = DecodeRequest(list(PROMPT), max_new_tokens=8)
    sess.submit(r1)
    sess.run(200)
    assert r1.result(5) == oracle
    assert cache.misses == 1 and cache.hits == 0
    assert cache.cached_pages == 1          # 11 tokens, ps=8 -> 1 full page

    r2 = DecodeRequest(list(PROMPT), max_new_tokens=8)
    sess.submit(r2)
    sess.run(200)
    assert r2.result(5) == oracle           # cached prefill == full prefill
    assert cache.hits == 1

    # longer prompt sharing page 0: still exact parity
    p3 = list(PROMPT[:8]) + [4, 4, 1, 3, 9, 9, 2, 5, 6]
    o3 = m.dense_greedy(p3, 6)
    r3 = DecodeRequest(list(p3), max_new_tokens=6)
    sess.submit(r3)
    sess.run(200)
    assert r3.result(5) == o3
    assert cache.hits == 2
    # all pages either free or retained by the cache — none leaked
    assert m.allocator.pages_in_use == cache.cached_pages


def test_prefix_cache_capacity_eviction():
    from paddle_tpu.decode.prefix import PrefixCache

    m = _mk()
    cache = PrefixCache(m.allocator, m.page_size, capacity_pages=2)
    rng = np.random.RandomState(5)
    for _ in range(4):                      # 4 distinct 2-page prefixes
        prompt = [int(t) for t in rng.randint(2, 40, 17)]
        pages = m.allocator.alloc(2)
        cache.insert(prompt, pages)
        m.allocator.free(pages)             # cache holds its own refs
    assert cache.cached_pages <= 2
    assert cache.stats()["evictions"] >= 2
    cache.clear()
    assert m.allocator.pages_in_use == 0


def test_prefix_insert_never_evicts_its_own_path():
    """Single-chain trie at capacity: making room for a child must not
    evict the just-walked parent — the old behavior attached the child
    to a detached subtree, leaking its page forever."""
    from paddle_tpu.decode.prefix import PrefixCache

    m = _mk()
    cache = PrefixCache(m.allocator, m.page_size, capacity_pages=1)
    prompt = [int(t) for t in np.arange(2, 2 + 16)]   # 2 full pages
    pages = m.allocator.alloc(2)
    cache.insert(prompt, pages)
    m.allocator.free(pages)
    assert cache.cached_pages == 1          # second chunk refused, not leaked
    cache.clear()
    assert cache.cached_pages == 0
    assert m.allocator.pages_in_use == 0    # nothing unreachable holds a page


def test_prefix_cache_stats_count_only_committed_admissions():
    """match() forks pages but must not count hits/tokens_saved — a
    requeued admission re-matches every retry; stats land only when the
    caller commits the outcome after the prefill ran."""
    from paddle_tpu.decode.prefix import PrefixCache

    m = _mk()
    cache = PrefixCache(m.allocator, m.page_size, capacity_pages=4)
    prompt = [int(t) for t in np.arange(2, 2 + 17)]   # 2 full pages + 1
    pages = m.allocator.alloc(3)
    cache.insert(prompt, pages)
    m.allocator.free(pages)

    forked, saved = cache.match(prompt)
    assert saved == 16 and len(forked) == 2
    assert cache.hits == 0 and cache.misses == 0
    assert cache.tokens_saved == 0          # nothing committed yet
    m.allocator.free(forked)                # admission failed -> retry later

    cache.commit_match(saved)
    assert cache.hits == 1 and cache.tokens_saved == 16
    cache.commit_match(0)
    assert cache.misses == 1
    cache.clear()
    assert m.allocator.pages_in_use == 0


def test_prefix_cache_evict_for_pages_only_drops_sole_refs():
    from paddle_tpu.decode.prefix import PrefixCache

    m = _mk(num_pages=8)
    cache = PrefixCache(m.allocator, m.page_size, capacity_pages=6)
    prompt = [int(t) for t in np.arange(2, 2 + 16)]
    pages = m.allocator.alloc(2)
    cache.insert(prompt, pages)
    # a live sequence still aliases these pages: memory-pressure
    # eviction must NOT reclaim them
    assert cache.evict_for_pages(2) == 0
    m.allocator.free(pages)                 # live sequence goes away
    assert cache.evict_for_pages(2) == 2
    assert m.allocator.pages_in_use == 0


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------


def test_spec_decode_token_identity_standalone():
    from paddle_tpu.decode import spec as spec_mod
    from paddle_tpu.decode.spec import (ModelDraft, NgramDraft,
                                        SpeculativeDecoder)

    m = _mk()
    oracle = m.dense_greedy(PROMPT, 12)
    # low-acceptance draft: prompt-lookup n-grams
    got = SpeculativeDecoder(m, NgramDraft(), k=4).generate(PROMPT, 12)
    assert got == oracle
    assert m.allocator.pages_in_use == 0
    # identity through chunks that really carried drafts, not through
    # a decoder that never proposed
    proposed = spec_mod._M_PROPOSED.value()
    assert proposed > 0
    # perfect draft (same weights): high acceptance, same tokens
    got = SpeculativeDecoder(m, ModelDraft(_mk()), k=4).generate(PROMPT, 12)
    assert got == oracle
    assert m.allocator.pages_in_use == 0
    assert spec_mod._M_PROPOSED.value() > proposed
    assert spec_mod._M_ACCEPTED.value() > 0


def test_spec_decode_token_identity_in_session():
    from paddle_tpu.decode import spec as spec_mod
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession
    from paddle_tpu.decode.spec import NgramDraft

    m = _mk(seed=5)
    prompts = [PROMPT, [2, 3, 4, 5, 6], [9, 8, 7, 1, 2, 3, 4]]
    oracles = [m.dense_greedy(p, 10) for p in prompts]
    sess = DecodeSession(m, max_slots=4, spec_draft=NgramDraft(), spec_k=4)
    reqs = [DecodeRequest(list(p), max_new_tokens=10) for p in prompts]
    for r in reqs:
        sess.submit(r)
    sess.run(500)
    for r, want in zip(reqs, oracles):
        assert r.result(5) == want
    assert m.allocator.pages_in_use == 0
    assert spec_mod._M_PROPOSED.value() > 0


def test_spec_session_refuses_sampling_and_beam():
    from paddle_tpu.decode.session import (AdmissionRefused, BeamRequest,
                                           DecodeRequest, DecodeSession)
    from paddle_tpu.decode.spec import NgramDraft

    sess = DecodeSession(_mk(), max_slots=2, spec_draft=NgramDraft())
    with pytest.raises(AdmissionRefused) as e:
        sess.submit(DecodeRequest([1, 2], max_new_tokens=4, temperature=0.7,
                                  seed=1))
    assert e.value.reason == "spec_mode"
    with pytest.raises(AdmissionRefused):
        sess.submit(BeamRequest([1, 2], beam_size=2, max_new_tokens=4))


def test_accept_greedy_rule():
    from paddle_tpu.decode.spec import accept_greedy

    # target agrees with the whole draft: all accepted + bonus token
    emitted, acc = accept_greedy([7, 8, 9], [7, 8, 9, 4])
    assert emitted == [7, 8, 9, 4] and acc == 3
    # first disagreement truncates; target's correction is emitted
    emitted, acc = accept_greedy([7, 5, 9], [7, 8, 9, 4])
    assert emitted == [7, 8] and acc == 1
    emitted, acc = accept_greedy([5, 5, 5], [7, 8, 9, 4])
    assert emitted == [7] and acc == 0


# ---------------------------------------------------------------------------
# beam search through the session
# ---------------------------------------------------------------------------


def test_lm_beam_size_one_matches_greedy():
    from paddle_tpu.decode.session import BeamRequest, DecodeSession

    m = _mk(seed=7)
    greedy = m.dense_greedy(PROMPT, 8)
    sess = DecodeSession(m, max_slots=4)
    req = BeamRequest(list(PROMPT), beam_size=1, max_new_tokens=8)
    sess.submit(req)
    sess.run(300)
    req.wait(5)
    assert req.tokens == greedy
    assert m.allocator.pages_in_use == 0


class _ShiftedLogits:
    """Delegates to a TinyDecoderLM but shifts every logit strictly
    negative — a softmax/argmax no-op, so greedy is unchanged, while
    the broken beam scoring (log(max(logits, 1e-20)) on raw logits)
    would clamp every token to one floor value."""

    def __init__(self, inner, shift=-1e4):
        self._inner = inner
        self._shift = shift

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def prefill(self, prompt, pages, **kw):
        ctx, states, logits = self._inner.prefill(prompt, pages, **kw)
        return ctx, states, np.asarray(logits) + self._shift

    def decode(self, tokens, states, tables, lens):
        logits, st = self._inner.decode(tokens, states, tables, lens)
        return np.asarray(logits) + self._shift, st


def test_lm_beam_negative_logits_matches_greedy():
    """emits_probs=False models hand the beam raw logits: the session
    must softmax them before beam_select, so beam_size=1 equals greedy
    even when every logit is negative and scores stay finite log-probs."""
    from paddle_tpu.decode.session import BeamRequest, DecodeSession

    m = _mk(seed=7)
    greedy = m.dense_greedy(PROMPT, 8)
    sess = DecodeSession(_ShiftedLogits(m), max_slots=4)
    req = BeamRequest(list(PROMPT), beam_size=1, max_new_tokens=8)
    sess.submit(req)
    sess.run(300)
    req.wait(5)
    assert req.tokens == greedy
    # proper per-token log-probs, not k * log(1e-20) floor garbage
    assert req.beams and req.beams[0][0] > 8 * np.log(1e-20) / 2
    assert m.allocator.pages_in_use == 0


def test_lm_beam_returns_sorted_beams_and_frees_pages():
    from paddle_tpu.decode.session import BeamRequest, DecodeSession

    m = _mk(seed=7)
    sess = DecodeSession(m, max_slots=4)
    req = BeamRequest(list(PROMPT), beam_size=3, max_new_tokens=8)
    sess.submit(req)
    sess.run(300)
    req.wait(5)
    assert req.beams and len(req.beams) <= 3
    scores = [s for s, _ in req.beams]
    assert scores == sorted(scores, reverse=True)
    assert req.tokens == req.beams[0][1]
    assert m.allocator.pages_in_use == 0


def test_seq2seq_beam_matches_dense_oracle():
    """CoW sibling-slot beam == the dense SequenceGenerator beam oracle,
    exactly — scores and tokens — on the NMT demo network."""
    from demos.seq2seq.gen_config import make_beam_gen
    from paddle_tpu.decode.engine import GenerationEngine
    from paddle_tpu.executor import Scope
    from paddle_tpu.generation import SequenceGenerator

    class _Params:
        def __init__(self):
            self.scope = Scope()

    params = _Params()
    oracle = SequenceGenerator(make_beam_gen(beam_size=1, max_length=7),
                               params)
    engine = GenerationEngine.for_seq2seq(
        make_beam_gen(beam_size=1, max_length=7), params, num_pages=24,
        page_size=8, pages_per_seq=2, max_slots=4, max_new_tokens=7,
        beam_max=4)
    try:
        for k in (1, 2, 3):
            for src in ([4, 7, 2], [3, 9, 5, 6]):
                want = oracle.generate([src], beam_size=k)
                req = engine.submit_beam(src, beam_size=k)
                req.wait(300)
                got = req.beams
                assert got is not None, (src, k, req.finish_reason)
                assert [t for _, t in got] == [t for _, t in want]
                for (gs, _), (ws, _) in zip(got, want):
                    assert abs(gs - ws) < 1e-5
        assert engine.model.allocator.pages_in_use == 0
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# per-slot seeded sampling
# ---------------------------------------------------------------------------


def test_sampling_params_require_temperature():
    """top_k/seed without temperature would be silently ignored (greedy
    argmax); the request constructor rejects the combination so serving
    returns a 400 instead."""
    from paddle_tpu.decode.session import DecodeRequest

    with pytest.raises(ValueError):
        DecodeRequest([1, 2], max_new_tokens=4, top_k=5)
    with pytest.raises(ValueError):
        DecodeRequest([1, 2], max_new_tokens=4, seed=7)
    r = DecodeRequest([1, 2], max_new_tokens=4, temperature=0.5,
                      top_k=5, seed=7)
    assert r.top_k == 5 and r.seed == 7


def test_sampling_seed_determinism():
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession

    m = _mk(seed=11)
    sess = DecodeSession(m, max_slots=2)

    def run(seed):
        r = DecodeRequest(list(PROMPT), max_new_tokens=8,
                          temperature=0.9, top_k=5, seed=seed)
        sess.submit(r)
        sess.run(300)
        r.wait(5)
        return list(r.tokens)

    assert run(42) == run(42)               # same seed, same tokens
    assert m.allocator.pages_in_use == 0


# ---------------------------------------------------------------------------
# the token choice on the device (ISSUE 29)
# ---------------------------------------------------------------------------

BLOCKS = ["gpt2", "olmoe"]


def _choice_lm(block, seed=3):
    """A toy model over the paged skeleton with the GPT-2 block or a
    tiny OLMoE block (float32, so that a session equals the dense
    oracle token for token)."""
    if block == "gpt2":
        return _mk(seed=seed)
    from paddle_tpu.models.olmoe import OlmoeLM

    return OlmoeLM(seed=seed, vocab=61, d_model=32, num_heads=4,
                   num_layers=2, num_experts=4, experts_per_tok=2,
                   expert_width=16, max_len=64, num_pages=64, page_size=8,
                   pages_per_seq=8, dtype="float32", eos_id=0)


def _head_columns(lm):
    """(name of the head's weight, the axis its vocabulary lies on)."""
    return ("emb", 0) if "lm_head" not in lm.params else ("lm_head", 1)


def _run_step(lm, op, slots=4, live=(1, 3)):
    """Seat a prompt in each ``live`` slot and run one decode step or
    one 3-token verify chunk over ``slots`` slots -> (the step's logits
    as the model hands them out, a function that runs the same step
    again)."""
    tables = np.zeros((slots, lm.pages_per_seq), np.int32)
    lens = np.ones((slots,), np.int64)
    for n, s in enumerate(live):
        prompt = PROMPT[:5 + 3 * n]
        pages = lm.allocator.alloc(lm.context_pages(prompt, 4))
        ctx, _, _ = lm.prefill(prompt, pages)
        tables[s], lens[s] = lm.pool_table(pages), ctx
    width = 1 if op == "decode" else 3
    tokens = np.random.RandomState(0).randint(1, lm.vocab, (slots, width))

    def step():
        call = lm.decode if op == "decode" else lm.verify_chunk
        return call(tokens.astype(np.int64), [], tables, lens)[0]

    return step


@pytest.mark.parametrize("op", ["decode", "verify"])
@pytest.mark.parametrize("block", BLOCKS)
def test_step_ids_are_the_argmax_of_the_same_steps_logits(block, op):
    """What the step chose on the device is what ``np.argmax`` chooses
    on the host from the logits of the same step, in every slot, the
    inactive ones (null table) included."""
    lm = _choice_lm(block)
    logits = _run_step(lm, op)()
    want = (4,) if op == "decode" else (4, 3)
    assert logits.ids.dtype == np.int32 and logits.ids.shape == want
    assert isinstance(logits.ids, np.ndarray)
    assert logits.shape == want + (lm.vocab,)
    host = np.asarray(logits)
    assert host.dtype == np.float32 and host.shape == logits.shape
    np.testing.assert_array_equal(logits.ids, np.argmax(host, axis=-1))
    np.testing.assert_array_equal(logits[1], host[1])


@pytest.mark.parametrize("op", ["decode", "verify"])
@pytest.mark.parametrize("block", BLOCKS)
def test_step_tie_goes_to_the_lower_index(block, op):
    """Two vocabulary entries with the same head column score the same
    to the bit: the step's choice is the lower of the two, as
    ``np.argmax``'s, whichever of them held the maximum before."""
    import jax.numpy as jnp

    lm = _choice_lm(block)
    step = _run_step(lm, op)
    first = step().ids
    name, axis = _head_columns(lm)
    head = np.array(lm.params[name])
    won = int(first[1] if op == "decode" else first[1, 0])
    twins = [t for t in (won - 2, won + 2) if 0 <= t < lm.vocab]
    for twin in twins:
        w = head.copy()
        if axis == 0:
            w[twin] = w[won]
        else:
            w[:, twin] = w[:, won]
        lm.params = {**lm.params, name: jnp.asarray(w)}
        logits = step()
        host = np.asarray(logits)
        row = host[1] if op == "decode" else host[1, 0]
        assert row[twin] == row[won] == row.max()
        got = int(logits.ids[1] if op == "decode" else logits.ids[1, 0])
        assert got == min(twin, won)
        np.testing.assert_array_equal(logits.ids, np.argmax(host, axis=-1))


def _choice_counts():
    from paddle_tpu.decode.session import _M_CHOICE

    return {w: _M_CHOICE.value(where=w) for w in ("device", "host")}


def _count_host_reads(monkeypatch):
    """-> a list that grows by one each time a step's logits are
    brought to the host."""
    from paddle_tpu.decode.model import StepLogits

    reads, real = [], StepLogits.__array__

    def spy(self, *a, **kw):
        reads.append(self.shape)
        return real(self, *a, **kw)

    monkeypatch.setattr(StepLogits, "__array__", spy)
    return reads


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("block", BLOCKS)
def test_greedy_session_takes_every_token_from_the_device(
        block, spec, monkeypatch):
    """A session whose requests are all greedy streams the dense
    oracle's tokens, counts every choice of a step as the device's and
    never brings a step's logits to the host."""
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession
    from paddle_tpu.decode.spec import NgramDraft

    lm = _choice_lm(block, seed=5)
    prompts = [PROMPT, [2, 3, 4, 5, 6], [9, 8, 7, 1, 2, 3, 4]]
    oracles = [lm.dense_greedy(p, 10) for p in prompts]
    reads = _count_host_reads(monkeypatch)
    before = _choice_counts()
    kw = dict(spec_draft=NgramDraft(), spec_k=4) if spec else {}
    sess = DecodeSession(lm, max_slots=4, **kw)
    reqs = [sess.submit(DecodeRequest(list(p), max_new_tokens=10))
            for p in prompts]
    sess.run(500)
    assert [r.result(5) for r in reqs] == oracles
    after = _choice_counts()
    assert after["host"] == before["host"]
    chosen = after["device"] - before["device"]
    # the first token of each request is the prefill's
    step_tokens = sum(len(o) - 1 for o in oracles)
    if spec:        # one choice a verified chunk, which emits 1..k tokens
        assert 0 < chosen <= step_tokens
    else:
        assert chosen == step_tokens
    assert reads == []
    assert lm.allocator.pages_in_use == 0


def _host_path(lm):
    """``lm`` behind a wrapper that hands the session its steps' logits
    as plain host arrays (shifted by nothing), without the device's
    choice: the parent's path, in which every slot chooses on the
    host."""
    return _ShiftedLogits(lm, shift=0.0)


def _mixed_batch(model):
    """Two greedy requests, one seeded sampling request and one beam
    group through one session of 6 slots -> what each request got."""
    from paddle_tpu.decode.session import (BeamRequest, DecodeRequest,
                                           DecodeSession)

    sess = DecodeSession(model, max_slots=6)
    reqs = [
        DecodeRequest(list(PROMPT), max_new_tokens=9),
        DecodeRequest(list(PROMPT[:6]), max_new_tokens=9, temperature=0.8,
                      top_k=7, seed=1234),
        BeamRequest(list(PROMPT[2:9]), beam_size=3, max_new_tokens=7),
        DecodeRequest([2, 3, 4, 5, 6], max_new_tokens=9),
    ]
    for r in reqs:
        sess.submit(r)
    sess.run(500)
    for r in reqs:
        r.wait(5)
        assert r.error is None, r.error
    return [list(r.tokens) for r in reqs], reqs[2].beams


@pytest.mark.parametrize("block", BLOCKS)
def test_mixed_batch_gives_each_request_the_host_paths_tokens(
        block, monkeypatch):
    """Greedy slots beside a sampling slot and a beam group: the greedy
    ones take the device's ids and equal the dense oracle; the sampling
    slot and the beam group read the logits and get, for the same seed,
    exactly what ``model.decode`` + the host path give them."""
    lm = _choice_lm(block, seed=7)
    want, want_beams = _mixed_batch(_host_path(lm))
    assert lm.allocator.pages_in_use == 0
    reads = _count_host_reads(monkeypatch)
    before = _choice_counts()
    got, beams = _mixed_batch(lm)
    after = _choice_counts()
    assert got == want
    assert beams == want_beams and len(beams) >= 1
    assert got[0] == lm.dense_greedy(PROMPT, 9)
    assert got[3] == lm.dense_greedy([2, 3, 4, 5, 6], 9)
    # greedy: every token but the prefill's; sampling: likewise, on the
    # host; the beam group: one choice a step, on the host
    assert after["device"] - before["device"] == 8 + 8
    assert after["host"] - before["host"] >= 8 + 1
    assert reads and all(shape == (6, lm.vocab) for shape in reads)
    assert lm.allocator.pages_in_use == 0


def test_logits_without_ids_are_chosen_from_on_the_host():
    """A model whose ``decode`` hands out bare logits is served as
    before: every choice is the host's."""
    from paddle_tpu.decode.session import DecodeRequest, DecodeSession

    lm = _mk(seed=5)
    want = lm.dense_greedy(PROMPT, 8)
    before = _choice_counts()
    sess = DecodeSession(_host_path(lm), max_slots=2)
    req = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=8))
    sess.run(300)
    assert req.result(5) == want
    after = _choice_counts()
    assert after["device"] == before["device"]
    assert after["host"] - before["host"] == 7


def test_step_logits_come_to_the_host_once_and_only_when_read():
    """``StepLogits``: the ids are on the host from the start; the
    logits are a device array until indexed or converted, and what
    comes then is the array ``np.asarray`` gives of it."""
    import jax

    lm = _mk(seed=2)
    logits = _run_step(lm, "decode")()
    assert isinstance(logits._dev, jax.Array)
    host = np.asarray(logits)
    assert host.dtype == np.float32
    assert np.asarray(logits, np.float64).dtype == np.float64
    np.testing.assert_array_equal(logits[np.asarray([3, 1])], host[[3, 1]])
    np.testing.assert_array_equal(np.argmax(logits[1]), logits.ids[1])


# ---------------------------------------------------------------------------
# the tick's new order gives the parent's tokens (ISSUE 32)
# ---------------------------------------------------------------------------

# what the parent commit (453e211) streamed for these requests; greedy
# and speculative streams have the dense oracle instead
PARENT_SAMPLED = [[56, 40, 40, 52, 31, 55, 52, 54, 19, 22],
                  [39, 57, 49, 14, 18, 56, 0],      # ends on the EOS
                  [3, 3, 3, 3, 3, 3, 3, 3, 3, 3]]
PARENT_BEAMS = [(-25.036277770996094, [53] * 7),
                (-25.228370666503906, [6] + [53] * 6),
                (-25.4178466796875, [53] * 6 + [42])]
PARENT_BESIDE_THE_BEAM = [3, 3, 3, 26, 26, 26, 26, 26, 26]
# what the parent commit of ISSUE 61 (9cc8220) streamed: five greedy
# requests with co-prime budgets over three lanes, and three over two
# lanes, the first ending on the EOS at its fourth token
PARENT_FULL_BATCH = [[8] * 7, [6] * 11, [53, 53] + [7] * 11, [58] * 5,
                     [9, 9, 9] + [22] * 6]
PARENT_EOS_QUEUED_BEHIND = [[6, 6, 6, 0], [3] * 7 + [42] * 3, [4] * 6]


def _ahead_counts():
    from paddle_tpu.decode.session import _M_AHEAD_HELD, _M_DISPATCHES

    return ({b: _M_DISPATCHES.value(behind=b) for b in ("nothing", "step")},
            {w: _M_AHEAD_HELD.value(why=w)
             for w in ("free_slot", "budget", "stale")})


def _in_flight_case(what):
    """Two greedy requests; the first is cancelled, or its deadline
    passes, while the step that computes its third token is in flight.
    It ends there, with a prefix of its oracle stream; its neighbour's
    stream is untouched and every page comes back."""
    import time

    from paddle_tpu.decode.session import DecodeRequest, DecodeSession

    lm = _mk(seed=5)
    sess = DecodeSession(lm, max_slots=2)
    a = sess.submit(DecodeRequest(list(PROMPT), max_new_tokens=9))
    b = sess.submit(DecodeRequest([2, 3, 4, 5, 6], max_new_tokens=9))
    sess.step(), sess.step()
    # the batch is full: steps 2 and 3 are in flight, 3 behind 2
    assert len(sess._flights) == 2 and len(a.tokens) == 2
    if what == "cancel":
        a.cancel()
    else:
        a.deadline = time.monotonic() - 1.0
    sess.run(100)
    assert b.result(0) == lm.dense_greedy([2, 3, 4, 5, 6], 9)
    assert a.done and a.tokens == lm.dense_greedy(PROMPT, 9)[:2]
    if what == "cancel":
        assert a.finish_reason == "cancelled"
    else:
        with pytest.raises(TimeoutError):
            a.result(0)
    assert lm.allocator.pages_in_use == 0


@pytest.mark.parametrize("case", [
    "greedy", "sampling_and_eos_mid_stream", "beam", "prefix_cache_hit",
    "speculative", "seq2seq", "cancel_in_flight", "deadline_in_flight",
    "greedy_full_batch_a_step_ahead", "eos_with_a_step_queued_behind"])
def test_the_new_tick_order_gives_the_parents_tokens(case):
    from paddle_tpu.decode.prefix import PrefixCache
    from paddle_tpu.decode.session import (BeamRequest, DecodeRequest,
                                           DecodeSession)
    from paddle_tpu.decode.spec import NgramDraft

    def run(sess, reqs):
        for r in reqs:
            sess.submit(r)
        sess.run(500)
        for r in reqs:
            assert r.wait(5) and r.error is None, r.error
        assert not sess._flights and sess.idle()
        return [list(r.tokens) for r in reqs]

    if case in ("cancel_in_flight", "deadline_in_flight"):
        return _in_flight_case(case.split("_")[0])
    if case == "seq2seq":
        # a model without the two halves: ``decode`` whole, old order
        from demos.seq2seq.gen_config import make_beam_gen
        from paddle_tpu.decode.engine import GenerationEngine
        from paddle_tpu.executor import Scope
        from paddle_tpu.generation import SequenceGenerator

        class _Params:
            scope = Scope()

        oracle = SequenceGenerator(make_beam_gen(beam_size=1, max_length=7),
                                   _Params)
        engine = GenerationEngine.for_seq2seq(
            make_beam_gen(beam_size=1, max_length=7), _Params, num_pages=24,
            page_size=8, pages_per_seq=2, max_slots=2, max_new_tokens=7)
        try:
            assert not engine.session._two_halves
            srcs = [[4, 7, 2], [3, 9, 5, 6], [2, 2, 11, 8, 1]]
            reqs = [engine.submit(s) for s in srcs]
            assert [r.result(300) for r in reqs] == \
                [oracle.generate_greedy([s]) for s in srcs]
            assert engine.model.allocator.pages_in_use == 0
        finally:
            engine.stop()
        return
    if case == "greedy_full_batch_a_step_ahead":
        lm = _mk(seed=5)
        prompts = [PROMPT, [2, 3, 4, 5, 6], [9, 8, 7, 1, 2, 3, 4],
                   PROMPT[3:], [4, 4, 2, 9]]
        budgets = [7, 11, 13, 5, 9]
        before = _ahead_counts()
        got = run(DecodeSession(lm, max_slots=3),
                  [DecodeRequest(list(p), max_new_tokens=b)
                   for p, b in zip(prompts, budgets)])
        assert got == PARENT_FULL_BATCH
        assert got == [lm.dense_greedy(p, b)
                       for p, b in zip(prompts, budgets)]
        behind, held = _ahead_counts()
        # the path was engaged, and let go at every budget's end
        assert behind["step"] - before[0]["step"] >= 8
        assert held["budget"] - before[1]["budget"] >= 2
    elif case == "eos_with_a_step_queued_behind":
        lm = _mk(seed=3)
        prompts = [[2, 3, 4, 5, 6], PROMPT, [9, 8, 7, 1, 2, 3, 4]]
        sess = DecodeSession(lm, max_slots=2)
        reqs = [DecodeRequest(list(p), max_new_tokens=b)
                for p, b in zip(prompts, (12, 10, 6))]
        for r in reqs:
            sess.submit(r)
        for _ in range(3):
            sess.step()
        # step 3, which chooses the first one's EOS, is in flight with
        # step 4 queued behind it
        assert len(sess._flights) == 2 and reqs[0].tokens == [6, 6, 6]
        got = run(sess, [])
        assert [list(r.tokens) for r in reqs] == PARENT_EOS_QUEUED_BEHIND
        assert reqs[0].finish_reason == "eos" and got == []
    elif case == "greedy":
        lm = _mk(seed=5)
        prompts = [PROMPT, [2, 3, 4, 5, 6], [9, 8, 7, 1, 2, 3, 4]]
        got = run(DecodeSession(lm, max_slots=2),     # 3 requests, 2 lanes
                  [DecodeRequest(list(p), max_new_tokens=10)
                   for p in prompts])
        assert got == [lm.dense_greedy(p, 10) for p in prompts]
    elif case == "sampling_and_eos_mid_stream":
        lm = _mk(seed=11)
        got = run(DecodeSession(lm, max_slots=4), [
            DecodeRequest(list(PROMPT), max_new_tokens=10, temperature=0.9,
                          top_k=5, seed=42),
            DecodeRequest(list(PROMPT[:7]), max_new_tokens=10,
                          temperature=1.3, seed=7),
            DecodeRequest(list(PROMPT[2:]), max_new_tokens=10)])
        assert got == PARENT_SAMPLED
        assert got[1][-1] == lm.eos_id and len(got[1]) < 10
    elif case == "beam":
        lm = _mk(seed=7)
        beam = BeamRequest(list(PROMPT[2:9]), beam_size=3, max_new_tokens=7)
        got = run(DecodeSession(lm, max_slots=6),
                  [beam, DecodeRequest(list(PROMPT), max_new_tokens=9)])
        assert got[1] == PARENT_BESIDE_THE_BEAM == lm.dense_greedy(PROMPT, 9)
        assert [t for _, t in beam.beams] == [t for _, t in PARENT_BEAMS]
        for (gs, _), (ws, _) in zip(beam.beams, PARENT_BEAMS):
            assert abs(gs - ws) < 1e-4
    elif case == "prefix_cache_hit":
        lm = _mk(seed=3)
        cache = PrefixCache(lm.allocator, lm.page_size, capacity_pages=16)
        sess = DecodeSession(lm, max_slots=4, prefix_cache=cache)
        first = run(sess, [DecodeRequest(list(PROMPT), max_new_tokens=8)])
        again = run(sess, [
            DecodeRequest(list(PROMPT), max_new_tokens=8),
            DecodeRequest(list(PROMPT[:9]) + [7, 7], max_new_tokens=8)])
        assert cache.hits == 2
        assert first[0] == again[0] == lm.dense_greedy(PROMPT, 8)
        assert again[1] == lm.dense_greedy(PROMPT[:9] + [7, 7], 8)
        cache.clear()
    else:
        lm = _mk(seed=5)
        prompts = [PROMPT, [2, 3, 4, 5, 6], [9, 8, 7, 1, 2, 3, 4]]
        # 13 tokens: the last ticks have no room for a chunk of 4 and
        # fall back to the plain step, dispatched in two halves
        got = run(DecodeSession(lm, max_slots=4, spec_draft=NgramDraft(),
                                spec_k=4),
                  [DecodeRequest(list(p), max_new_tokens=13)
                   for p in prompts])
        assert got == [lm.dense_greedy(p, 13) for p in prompts]
    assert lm.allocator.pages_in_use == 0
