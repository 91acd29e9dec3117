"""The latent-attention model (``paddle_tpu/models/kanana_mla.py``) at a
toy size on the CPU in float32, against the plain reference
(``perf/reference/kanana_mla_block.py``: expanded, no cache, no kernel):
prefill then decode through the latent pages, logits not tokens;
absorbed == expanded; every ablation of the reference moves the logits;
the kernel interpreted against its jnp reference over ragged lengths
and a null page; what a page-run model can do (a suffix over cached
rows, ``copy_page``, a verify chunk); refusals by name.  (The eight
shares' sum is ``tests/test_exaone_moe.py``'s share test, parametrised
over this model's shape.)"""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from paddle_tpu import pallas as pk  # noqa: E402
from paddle_tpu.decode.session import (  # noqa: E402
    BeamRequest, DecodeRequest, DecodeSession)
from paddle_tpu.models import kanana_mla as km  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.pallas import latent_attention as la  # noqa: E402
from perf.reference import kanana_mla_block as ref  # noqa: E402

TOL = 1e-4
# rank 128 + rope 64 = 192 numbers a row, stored at 256 lanes: rows the
# kernel takes (whole tiles), so the decode step runs it interpreted
SIZES = dict(vocab=80, d_model=32, num_heads=4, num_layers=3,
             qk_nope_head_dim=8, qk_rope_head_dim=64, v_head_dim=8,
             kv_lora_rank=128, dense_width=48, expert_width=16,
             num_experts_published=16, held_experts=(4, 4),
             experts_per_tok=3, max_len=64, num_pages=40, page_size=8,
             pages_per_seq=8, dtype="float32")
S = 4       # slots of the hand-driven steps
T_PROMPT, N_DECODED = 21, 12


@pytest.fixture(scope="module")
def model():
    return km.KananaMlaLM(seed=3, **SIZES)


@pytest.fixture()
def kernels():
    """Kernels on, interpreted (the chip's path at the toy size)."""
    state = dict(pk._STATE)
    pk.enable(True, interpret=True)
    yield
    pk._STATE.update(state)


def _reference(model, ids, ablate=None, rows=None):
    b = model.block
    return ref.forward(
        model.params, jnp.asarray(ids, jnp.int32), num_heads=model.heads,
        nope=b.nope, rope_dim=b.rope_dim, top_k=b.top_k, scale=b.scale,
        held=b.held, eps=b.eps, theta=b.theta, ablate=ablate, rows=rows)[0]


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, SIZES["vocab"], n).tolist()


def _through_the_pages(model, prompt, tokens, slot=1, cached_len=0):
    """Prefill (the suffix over cached rows when ``cached_len``), then
    ``tokens`` teacher-forced one decode step each: the len(tokens) + 1
    logits rows."""
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    try:
        if cached_len:
            model.prefill(prompt[:cached_len], pages)
        ctx, _, last = model.prefill(prompt, pages, cached_len=cached_len)
        rows = [np.asarray(last, np.float32)]
        tables = np.zeros((S, model.pages_per_seq), np.int32)
        tables[slot] = model.pool_table(pages)
        lens = np.zeros((S,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((S, 1), model.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = model.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot], np.float32))
    finally:
        model.allocator.free(pages)
    return np.stack(rows)


@pytest.fixture(scope="module")
def decoded(model):
    prompt, tokens = _prompt(T_PROMPT), _prompt(N_DECODED, seed=1)
    return prompt + tokens, _through_the_pages(model, prompt, tokens)


def _want(model, ids):
    return _reference(model, ids, rows=list(range(T_PROMPT - 1, len(ids))))


# -- against the reference ---------------------------------------------------


def test_prefill_then_decode_through_the_latent_pages_match_the_reference(
        model, decoded):
    ids, got = decoded
    assert ref.rel_rms(got, _want(model, ids)) <= TOL


def test_the_same_through_the_kernel(model, decoded, kernels):
    """The decode steps absorbed through ``latent_paged_attention``
    interpreted (counted), the same 13 rows."""
    ids, got = decoded
    before = metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="latent_paged_attention", path="interpret")
    # the jitted step was traced with the kernels off: trace it anew
    from paddle_tpu.decode import model as dm

    dm._decode_step.clear_cache()
    again = _through_the_pages(model, ids[:T_PROMPT], ids[T_PROMPT:])
    dm._decode_step.clear_cache()
    after = metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel="latent_paged_attention", path="interpret")
    assert after - before == model.layers     # one call a layer, traced once
    assert ref.rel_rms(again, _want(model, ids)) <= TOL
    assert ref.rel_rms(again, got) <= 1e-5


def test_the_whole_forward_matches_the_reference_row_for_row(model):
    ids = _prompt(30, seed=2)
    got = model._forward(jnp.asarray(ids, jnp.int32))[0]
    assert ref.rel_rms(got, _reference(model, ids)) <= TOL


@pytest.mark.parametrize("ablate", ref.ABLATIONS + ref.PRECISIONS)
def test_tolerance_catches_each_ablation(model, decoded, ablate):
    ids, got = decoded
    wrong = _reference(model, ids, ablate,
                       rows=list(range(T_PROMPT - 1, len(ids))))
    assert ref.rel_rms(got, wrong) > 20 * TOL, ablate


def test_rope_pairs_neighbouring_channels():
    """Channel 2i with 2i + 1: a rotation keeps each pair's norm and
    position 0 is the identity."""
    x = jnp.asarray(np.random.RandomState(0).randn(5, 3, 8), jnp.float32)
    y = km.rope_interleaved(x, jnp.arange(5), 1e4)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x[0]), atol=1e-6)
    pair = lambda a: np.asarray(a).reshape(5, 3, 4, 2)   # noqa: E731
    np.testing.assert_allclose(np.linalg.norm(pair(y), axis=-1),
                               np.linalg.norm(pair(x), axis=-1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.rope(x, 1e4)), atol=1e-5)


# -- absorbed == expanded -----------------------------------------------------


def test_chunk_form_is_a_function_of_the_static_shape():
    assert km.chunk_form(1) == km.chunk_form(1024) == "absorbed"
    assert km.chunk_form(1025) == km.chunk_form(7000) == "expanded"
    # the kernel's resident q block: 16 rows a sequence at 32 heads
    assert km.kernel_rows(32) == 16 and km.kernel_rows(4) == 128


@pytest.fixture()
def short_absorbed_edge(monkeypatch):
    """The rule's edge moved down to 16 rows, so that a toy suffix runs
    expanded; the suffix program is traced anew around it."""
    from paddle_tpu.decode import model as dm

    monkeypatch.setattr(km, "ABSORBED_MAX_ROWS", 16)
    dm._prefill_chunk.clear_cache()
    yield
    dm._prefill_chunk.clear_cache()


def _suffix_over_cached_rows(model, cached, rows):
    prompt, tokens = _prompt(cached + rows, 5), _prompt(3, seed=6)
    whole = _through_the_pages(model, prompt, tokens)
    over = _through_the_pages(model, prompt, tokens, cached_len=cached)
    assert ref.rel_rms(over, whole) <= 1e-5
    want = _reference(model, prompt + tokens, rows=list(
        range(len(prompt) - 1, len(prompt) + 3)))
    assert ref.rel_rms(over, want) <= TOL


@pytest.mark.parametrize("cached, rows", [(8, 9), (16, 9), (24, 21)])
def test_a_suffix_over_cached_rows_is_the_full_prefill(model, cached, rows):
    """A prefix-cache hit: the suffix prefilled over the cached pages
    (absorbed: every toy suffix is under the rule's edge) gives the rows
    the whole prompt's prefill and the same decode give."""
    assert km.chunk_form(rows) == "absorbed"
    _suffix_over_cached_rows(model, cached, rows)


def test_a_long_suffix_runs_expanded_to_the_same_rows(
        model, short_absorbed_edge):
    assert km.chunk_form(21) == "expanded"
    _suffix_over_cached_rows(model, 24, 21)


def test_verify_chunk_equals_single_steps(model):
    prompt, chunk = _prompt(11, seed=7), _prompt(4, seed=8)
    singles = _through_the_pages(model, prompt, chunk)[1:]
    pages = model.allocator.alloc(model.context_pages(prompt, len(chunk)))
    try:
        ctx, _, _ = model.prefill(prompt, pages)
        tables = np.zeros((S, model.pages_per_seq), np.int32)
        tables[2] = model.pool_table(pages)
        lens = np.zeros((S,), np.int32)
        lens[2] = ctx
        toks = np.full((S, len(chunk)), model.bos_id, np.int64)
        toks[2] = chunk
        logits, _ = model.verify_chunk(toks, [], tables, lens)
        got = np.asarray(logits[2], np.float32)
    finally:
        model.allocator.free(pages)
    assert ref.rel_rms(got, singles) <= 1e-5
    assert logits.ids.shape == (S, len(chunk))


def test_copy_page_copies_every_layers_rows(model):
    """The copy-on-write split: a fork that decodes from a copied page
    reads what the original reads."""
    prompt, tokens = _prompt(13, seed=9), _prompt(2, seed=10)
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    spare = model.allocator.alloc(1)
    try:
        ctx, _, _ = model.prefill(prompt, pages)
        model.copy_page(pages[1], spare[0])
        pool = np.asarray(model.k_pool)
        np.testing.assert_array_equal(pool[:, spare[0]], pool[:, pages[1]])
        assert pool[:, pages[1]].any()
        forked = [pages[0], spare[0]] + list(pages[2:])
        rows = []
        for run in (pages, forked):
            tables = np.zeros((S, model.pages_per_seq), np.int32)
            tables[0] = model.pool_table(run)
            lens = np.zeros((S,), np.int32)
            lens[0] = ctx
            step = np.full((S, 1), tokens[0], np.int64)
            rows.append(np.asarray(model.decode(step, [], tables, lens)[0][0]))
        np.testing.assert_array_equal(rows[0], rows[1])
    finally:
        model.allocator.free(pages)
        model.allocator.free(spare)


# -- the kernel ---------------------------------------------------------------


@pytest.mark.parametrize("T", [1, 3], ids=["step", "chunk"])
@pytest.mark.parametrize("pages_per_seq", [8, 6], ids=["fetch4", "fetch2"])
def test_kernel_matches_its_reference_over_ragged_lengths(T, pages_per_seq):
    """Lengths 0 (an inactive slot on the null table), inside a page, on
    a page's edge, across the fetch's edge and the table's whole width;
    pages_per_seq 6 takes two pages a turn (``fetch_pages``)."""
    rng = np.random.RandomState(T)
    H, W, V, pg, N = 4, 256, 128, 8, 64
    P = pages_per_seq
    full = P * pg - T
    lens = np.asarray([0, 5, 8, 4 * pg + 1, full], np.int32)
    Sx = len(lens)
    q = jnp.asarray(rng.randn(Sx, T * H, W), jnp.float32)
    pages = jnp.asarray(rng.randn(N, pg, W), jnp.float32)
    tables = rng.randint(1, N, (Sx, P)).astype(np.int32)
    tables[0] = 0                                   # the null table
    for s in range(1, Sx):                          # null past the run
        tables[s, -(-(lens[s] + T) // pg):] = 0
    kw = dict(heads=H, v_width=V, scale=0.11)
    got = la.latent_paged_attention(q, pages, jnp.asarray(tables),
                                    jnp.asarray(lens), interpret=True, **kw)
    want = la.latent_paged_attention_reference(
        q, pages, jnp.asarray(tables), jnp.asarray(lens), **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert la.fetch_pages(P) == (4 if P == 8 else 2)


def _biased_case(case, P):
    """(lens, scores (S, P * pg), k) of one case of the walk under a
    bias: each slot attends on its ``min(k, lens + 1)`` cached rows of
    largest score (a sparse latent layer's selected set, made here by
    the sort the kernel knows nothing of)."""
    rng = np.random.RandomState(len(case) + P)
    pg, k = 8, 12
    n = P * pg
    # an inactive slot, a slot with fewer rows than k (all kept), on a
    # page's edge, across the fetch's edge, the table's whole width
    lens = np.asarray([0, 6, 2 * pg - 1, 4 * pg + 1, n - 1], np.int32)
    scores = rng.randn(len(lens), n).astype(np.float32)
    if case == "late_members":
        # every member of the long slots past their first 32 rows (a
        # turn of four pages, two turns of two): the running max is
        # still where it started when the first member comes
        scores[3:, :4 * pg] -= 100.0
        lens[3] = n - 2
    elif case == "tie_at_the_edge":
        # four rows above a run of equal scores across the k-th place:
        # the eight lowest rows of the run
        scores[2:] = np.minimum(scores[2:], 0.0)
        scores[2:, 1:31:2] = 1.5
        scores[2:, 2:12:3] = 3.0
    return lens, scores, k


@pytest.mark.parametrize("pages_per_seq", [8, 6], ids=["fetch4", "fetch2"])
@pytest.mark.parametrize("case", ["ragged", "late_members",
                                  "tie_at_the_edge"])
def test_kernel_under_a_bias_attends_on_the_members_alone(case,
                                                          pages_per_seq):
    """The walk of a slot's live pages with a bias a (slot, cached row)
    pair: the kernel against its reference under the same bias, and
    both against a softmax over the member rows alone, in numpy."""
    H, W, V, pg, N, P = 4, 256, 128, 8, 64, pages_per_seq
    lens, scores, k = _biased_case(case, P)
    rng = np.random.RandomState(P)
    Sx, n = len(lens), P * pg
    seen = np.arange(n)[None, :] <= lens[:, None]
    order = np.argsort(-np.where(seen, scores, -np.inf), axis=-1,
                       kind="stable")[:, :k]        # a tie: the lower row
    members = np.zeros((Sx, n), bool)
    np.put_along_axis(members, order, True, axis=-1)
    members &= seen
    assert members.sum(-1).tolist() == np.minimum(lens + 1, k).tolist()
    if case == "late_members":
        assert not members[3:, :4 * pg].any()
    if case == "tie_at_the_edge":
        assert np.nonzero(members[4])[0].tolist() == [
            1, 2, 3, 5, 7, 8, 9, 11, 13, 15, 17, 19]
    bias = jnp.asarray(np.where(members, 0.0, -1e30), jnp.float32)
    q = jnp.asarray(rng.randn(Sx, H, W), jnp.float32)
    pages = jnp.asarray(rng.randn(N, pg, W), jnp.float32)
    tables = rng.randint(1, N, (Sx, P)).astype(np.int32)
    tables[0] = 0
    kw = dict(heads=H, v_width=V, scale=0.11)
    args = (q, pages, jnp.asarray(tables), jnp.asarray(lens))
    got = np.asarray(la.latent_paged_attention(*args, bias, interpret=True,
                                               **kw))
    want = np.asarray(la.latent_paged_attention_reference(*args, bias=bias,
                                                          **kw))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    rows = np.asarray(pages)[tables].reshape(Sx, n, W)
    for s in range(Sx):
        kept = rows[s][members[s]]
        sc = np.asarray(q[s]) @ kept.T * 0.11
        p = np.exp(sc - sc.max(-1, keepdims=True))
        alone = (p / p.sum(-1, keepdims=True)) @ kept[:, :V]
        np.testing.assert_allclose(got[s], alone, rtol=2e-5, atol=2e-5)


def test_without_a_bias_the_kernel_is_the_one_it_was():
    """``bias=None`` is a static of the trace: the call has the four
    operands it had and its kernel body no load more; its output is, to
    the bit, the output under a bias of zeros (x + 0.0 is x)."""
    rng = np.random.RandomState(0)
    H, W, V, pg, N, P = 4, 256, 128, 8, 64, 8
    lens = jnp.asarray([0, 5, 8, 4 * pg + 1, P * pg - 1], jnp.int32)
    q = jnp.asarray(rng.randn(5, H, W), jnp.float32)
    pages = jnp.asarray(rng.randn(N, pg, W), jnp.float32)
    tables = jnp.asarray(rng.randint(1, N, (5, P)), jnp.int32)
    kw = dict(heads=H, v_width=V, scale=0.11, interpret=True)
    plain = la.latent_paged_attention(q, pages, tables, lens, **kw)
    zeros = la.latent_paged_attention(
        q, pages, tables, lens, jnp.zeros((5, P * pg), jnp.float32), **kw)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zeros))

    def call(*bias):
        jaxpr = jax.make_jaxpr(lambda *a: la.latent_paged_attention(
            *a, **kw))(q, pages, tables, lens, *bias)
        eqn, = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                if e.primitive.name == "pallas_call"]
        return eqn

    bare, biased = call(), call(jnp.zeros((5, P * pg), jnp.float32))
    assert len(bare.invars) == 4 and len(biased.invars) == 5
    assert len(bare.params["jaxpr"].invars) \
        == len(biased.params["jaxpr"].invars) - 1
    # the one add of the bias' row is the whole difference
    assert str(biased.params["jaxpr"]).count(" add ") \
        == str(bare.params["jaxpr"]).count(" add ") + 1


def test_kernel_fits_whole_tiles_and_a_resident_chunk():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert la.fits(bf16, 128, 32, 640, 512)
    assert la.fits(bf16, 128, 16 * 32, 640, 512)
    assert not la.fits(bf16, 128, 17 * 32, 640, 512)    # q block too tall
    assert not la.fits(bf16, 128, 32, 576, 512)         # not whole tiles
    assert not la.fits(bf16, 8, 32, 640, 512)           # bf16 packs 16 rows
    assert la.fits(f32, 8, 4, 256, 128)
    assert not la.fits(f32, 8, 4, 256, 16)
    assert km.row_width(512, 64) == 640 and km.row_width(128, 64) == 256


def test_dispatch_counts_the_reference_where_the_row_is_not_whole_tiles():
    name = "latent_paged_attention"
    before = metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel=name, path="reference")
    assert not pk.use_latent_paged_attention(jnp.float32, 8, 4, 256, 16)
    assert metrics.REGISTRY.get("pallas_dispatch_total").value(
        kernel=name, path="reference") == before + 1


# -- behind the session -------------------------------------------------------


def _run(session, prompts, n):
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=n))
            for p in prompts]
    session.run(max_steps=500)
    return [r.result(1) for r in reqs]


def test_the_session_decodes_what_the_dense_oracle_decodes(model):
    session = DecodeSession(model, max_slots=3)
    prompts = [_prompt(9, seed=20), _prompt(17, seed=21)]
    got = _run(session, prompts, 5)
    assert got == [model.dense_greedy(p, 5) for p in prompts]
    gauge = metrics.REGISTRY.get("decode_cache_rows")
    assert gauge.value(kind="latent") == 0          # nothing left seated


def test_cache_rows_and_bytes_count_latent_rows_as_stored(model):
    assert model.cache_rows([10, 30]) == {"latent": 40 * 3}
    assert model.row_bytes == 256 * 4
    assert model.cache_bytes([10, 30]) == {"latent": 40 * 3 * 256 * 4}
    assert model.k_pool.shape == (3, 40, 8, 256)
    assert model.v_pool.size == 3                   # the placeholder


def test_a_prefix_cache_hit_and_a_beam_fork_are_taken(model):
    """What a page-run model can do the latent model does: the session
    keeps its prefix cache and admits a beam."""
    from paddle_tpu.decode.prefix import PrefixCache

    cache = PrefixCache(model.allocator, model.page_size, capacity_pages=8)
    session = DecodeSession(model, max_slots=3, prefix_cache=cache)
    assert session.prefix_cache is cache
    shared = _prompt(16, seed=30)
    first = _run(session, [shared + [5, 6, 7]], 4)[0]
    again = _run(session, [shared + [5, 6, 7]], 4)[0]
    assert cache.hits == 1                  # the suffix over cached pages
    assert first == again == model.dense_greedy(shared + [5, 6, 7], 4)
    beam = BeamRequest(_prompt(9, seed=31), beam_size=2, max_new_tokens=3)
    session.submit(beam)
    session.run(300)
    beam.wait(5)
    assert beam.beams and beam.tokens == beam.beams[0][1]
    assert model.allocator.pages_in_use == cache.cached_pages


def test_what_the_latent_model_cannot_do_is_refused_by_name(model):
    toks = np.zeros((S, km.kernel_rows(model.heads) + 1), np.int64)
    with pytest.raises(km.UnsupportedOverLatentRows, match="verify chunk"):
        model.verify_chunk(toks, [], np.zeros((S, 8), np.int32),
                           np.zeros((S,), np.int32))
    with pytest.raises(ValueError, match="cached_len"):
        model.prefill([3] * 9, [1, 2], cached_len=5)    # not whole pages
    with pytest.raises(ValueError, match="outside 1..64"):
        model.prefill_bucket(65)
    session = DecodeSession(model, max_slots=2)
    with pytest.raises(ValueError, match="outside 0..79"):
        session.submit(DecodeRequest([3, 80]))       # past the slice


def test_the_prefill_counts_its_causal_pairs(model):
    counter = metrics.REGISTRY.get("attn_latent_prefill_pairs_total")
    before = counter.value()
    pages = model.allocator.alloc(2)
    try:
        model.prefill(_prompt(11, seed=40), pages)
        assert counter.value() - before == 11 * 12 // 2
        model.prefill(_prompt(11, seed=40), pages, cached_len=8)
        assert counter.value() - before == 11 * 12 // 2   # a suffix: none
    finally:
        model.allocator.free(pages)


# -- scopes -------------------------------------------------------------------


def test_named_scopes_place_the_latent_mixer_and_the_experts(model):
    from paddle_tpu.decode import model as dm

    kw = dict(heads=model.heads, block=model.block)
    tables = np.zeros((S, model.pages_per_seq), np.int32)
    lens = np.zeros((S,), np.int32)
    pools = (model.params, model.k_pool, model.v_pool)
    texts = {
        "_decode_step": dm._decode_step.lower(
            *pools, tables, lens, np.zeros((S,), np.int32),
            page_size=model.page_size, **kw),
        "_verify_step": dm._verify_step.lower(
            *pools, tables, lens, np.zeros((S, 2), np.int32),
            page_size=model.page_size, **kw),
        "_prefill_chunk": dm._prefill_chunk.lower(
            *pools, tables[0], np.int32(8), np.zeros((20,), np.int32),
            page_size=model.page_size, **kw),
        "_prefill_bucket": dm._prefill_bucket.lower(
            *pools, np.zeros((64,), np.int32), np.zeros((64,), np.int32),
            np.int32(1), **kw)}
    absorbed = ("attn_latent_down", "attn_latent_absorb")
    expanded = ("attn_latent_down", "attn_latent_expand")
    inside = {"_decode_step": absorbed, "_verify_step": absorbed,
              "_prefill_chunk": absorbed, "_prefill_bucket": expanded}
    for program, lowered in texts.items():
        text = lowered.as_text(debug_info=True)
        for scope in inside[program]:
            assert re.search(
                rf"{program}\)/blk_mixer/attn_latent/{scope}/", text), (
                program, scope)
        for scope in ("moe_shared", "moe_router", "moe_dispatch",
                      "moe_experts", "moe_combine"):
            assert re.search(
                rf"{program}\)/blk_mlp/(while/body/)?{scope}/", text), (
                program, scope)
        other = set(absorbed + expanded) - set(inside[program])
        for scope in other:
            assert f"/{scope}/" not in text, (program, scope)
