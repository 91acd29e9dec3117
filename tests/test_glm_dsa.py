"""The sparse latent-attention model (``paddle_tpu/models/glm_dsa.py``)
at a toy size on the CPU in float32, against the plain reference
(``perf/reference/glm_dsa_block.py``: expanded, no cache, no kernel, the
full index matrix and a sort): prefill through a bucket, through chunks
and as a suffix over cached rows, then decode through the pages, logits
not tokens, at a toy ``index_topk`` smaller than the prompt so that
every case selects, and once above it (dense); the selected set itself,
row by row, a constructed tie included; the four ranks' shares add up
to the uncut layer; ``copy_page``, a prefix hit and free / realloc keep
latent and index rows together; every ablation of the reference moves
the logits; the four kernels interpreted against their jnp references
(the prefill's selection as a bias member for member against
``selection_mask``, case by case); refusals by name; the counters."""

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
from paddle_tpu.models import glm_dsa as gd  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.pallas import sparse_latent as sl  # noqa: E402
from perf.reference import glm_dsa_block as ref  # noqa: E402

TOL = 1e-4
TOPK = 16
# rank 128 + rope 64 = 192 numbers a latent row, stored at 256 lanes, an
# index row of 128: rows the decode kernels take (whole tiles)
SIZES = dict(vocab=80, d_model=32, num_heads=4, num_layers=3,
             q_lora_rank=24, kv_lora_rank=128, qk_nope_head_dim=8,
             qk_rope_head_dim=64, v_head_dim=8, index_n_heads=8,
             index_head_dim=128, index_rope_dim=64, index_topk=TOPK,
             dense_width=48, expert_width=16, num_experts_published=16,
             held_experts=(4, 4), experts_per_tok=3, max_len=128,
             num_pages=80, page_size=8, pages_per_seq=16, prefill_rows=64,
             chunk_rows=16, dtype="float32")
S = 4       # slots of the hand-driven steps
N_DECODED = 6


@pytest.fixture(scope="module")
def model():
    return gd.GlmDsaLM(seed=3, **SIZES)


@pytest.fixture(scope="module")
def dense_model():
    """``index_topk`` above every toy sequence: nothing is selected."""
    return gd.GlmDsaLM(seed=3, **{**SIZES, "index_topk": 128})


@pytest.fixture()
def kernels():
    """Kernels on, interpreted (the chip's path at the toy size); the
    programs traced with the kernels off are traced anew around it."""
    from paddle_tpu.decode import model as dm

    state = dict(pk._STATE)
    pk.enable(True, interpret=True)
    programs = (dm._decode_step, dm._prefill_bucket,
                gd._prefill_bucket_chunk)
    for p in programs:
        p.clear_cache()
    yield
    pk._STATE.update(state)
    for p in programs:
        p.clear_cache()


def _reference(model, ids, ablate=None, rows=None, sets=False, held=None):
    b = model.block
    return ref.forward(
        model.params, jnp.asarray(ids, jnp.int32), num_heads=model.heads,
        nope=b.nope, rope_dim=b.rope_dim, index_heads=b.index_heads,
        index_rope=b.index_rope, index_topk=b.index_topk, top_k=b.top_k,
        scale=b.scale, held=held or b.held, eps=b.eps, theta=b.theta,
        ablate=ablate, rows=rows, sets=sets)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, SIZES["vocab"], n).tolist()


def _through_the_pages(model, prompt, tokens, slot=1, cached_len=0, S=S):
    """Prefill (the suffix over cached rows when ``cached_len``), then
    ``tokens`` teacher-forced one decode step each, one slot of ``S``:
    the len(tokens) + 1 logits rows."""
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


def _greedy(model, prompt, got):
    """Whether ``got`` is the greedy continuation of ``prompt``: each of
    its tokens the argmax of the row the hand-driven pages give after the
    ones before it (the programs the session runs, one slot of four)."""
    rows = _through_the_pages(model, prompt, got[:-1])
    return np.argmax(rows, axis=-1).tolist() == list(got)


def _want(model, ids, n_prompt, ablate=None):
    return _reference(model, ids, ablate,
                      rows=list(range(n_prompt - 1, len(ids))))[0]


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("rows, cached", [(40, 0), (100, 0), (40, 16),
                                          (100, 24)],
                         ids=["bucket", "chunks", "suffix",
                              "suffix_in_chunks"])
def test_prefill_then_decode_through_the_pages_match_the_reference(
        model, rows, cached):
    """A 40-row prompt through its 64-row bucket; 100 rows as the 64-row
    top bucket and three chunks of 16 (the last padded); both again as a
    suffix over cached rows (a prefix hit); then 6 steps through the
    pages: every row selects (``index_topk`` 16)."""
    prompt, tokens = _prompt(rows, seed=rows), _prompt(N_DECODED, seed=1)
    assert model.prefill_bucket(100) == 64 + 3 * 16
    got = _through_the_pages(model, prompt, tokens, cached_len=cached)
    want = _want(model, prompt + tokens, rows)
    assert ref.rel_rms(got, want) <= TOL


def test_under_index_topk_nothing_is_selected(dense_model):
    """``index_topk`` 128 over a 46-row sequence: plain causal latent
    attention, Kanana's programs, and the reference with the selection
    off gives the same rows."""
    prompt, tokens = _prompt(40, seed=2), _prompt(N_DECODED, seed=1)
    got = _through_the_pages(dense_model, prompt, tokens)
    ids = prompt + tokens
    assert ref.rel_rms(got, _want(dense_model, ids, 40)) <= TOL
    assert ref.rel_rms(got, _want(dense_model, ids, 40,
                                  "dense_attention")) <= TOL


def test_the_whole_forward_matches_the_reference_row_for_row(model):
    ids = _prompt(50, seed=2)
    got = model._forward(jnp.asarray(ids, jnp.int32))[0]
    assert ref.rel_rms(got, _reference(model, ids)[0]) <= TOL


@pytest.mark.parametrize("ablate", ref.ABLATIONS + ref.PRECISIONS)
def test_tolerance_catches_each_ablation(model, ablate):
    """Every ablation the cell lists changes the toy logits by 20
    times the tolerance or more."""
    prompt, tokens = _prompt(40, seed=40), _prompt(N_DECODED, seed=1)
    got = _through_the_pages(model, prompt, tokens)
    wrong = _want(model, prompt + tokens, 40, ablate)
    assert ref.rel_rms(got, wrong) > 20 * TOL, ablate


# -- the selected set ---------------------------------------------------------


def test_the_selected_set_is_the_references_row_by_row(model):
    ids = _prompt(60, seed=5)
    got, routed = gd.chosen_sets(model, ids)
    assert routed.shape == (2, 60, 16) and (routed.sum(-1) == 3).all()
    want = np.asarray(_reference(model, ids, sets=True)[2])
    assert got.shape == want.shape == (3, 60, 60)
    np.testing.assert_array_equal(got, want)
    counts = got.sum(-1)
    np.testing.assert_array_equal(
        counts, np.broadcast_to(np.minimum(np.arange(60) + 1, TOPK),
                                counts.shape))


def test_a_tie_at_the_edge_goes_to_the_lower_row():
    """Five rows share the 3rd largest score of a query that keeps 4:
    the two lowest of them are kept, by the selection and by the
    reference's sort."""
    scores = np.asarray([[0.5, 2.0, 0.5, 9.0, 0.5, -1.0, 0.5, 0.5, 7.0, 0.1],
                         [1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0]],
                        np.float32)
    seen = np.ones_like(scores, bool)
    seen[1, 6:] = False
    mask = np.asarray(gd.selection_mask(jnp.asarray(scores),
                                        jnp.asarray(seen), 4))
    assert np.nonzero(mask[0])[0].tolist() == [0, 1, 3, 8]
    assert np.nonzero(mask[1])[0].tolist() == [0, 1, 2, 3]
    masked = np.where(seen, scores, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")[:, :4]
    assert sorted(order[0].tolist()) == [0, 1, 3, 8]
    # fewer seen rows than are kept: all of them, and no other
    few = np.asarray(gd.selection_mask(jnp.asarray(scores),
                                       jnp.asarray(seen), 8))
    assert few[1].tolist() == seen[1].tolist()


def test_kth_largest_is_exact_over_signs_and_infinities():
    x = np.asarray([[3.0, -0.0, 0.0, -2.5, np.inf, -np.inf, 1e-30, -1e-30]],
                   np.float32)
    keys = gd.sortable(jnp.asarray(x))
    order = np.argsort(np.asarray(keys)[0], kind="stable")
    assert x[0][order].tolist() == sorted(x[0].tolist())
    for k in range(1, 9):
        kth = int(gd.kth_largest(keys, k)[0])
        assert kth == sorted(np.asarray(keys)[0].tolist())[-k]


# -- the shares ---------------------------------------------------------------


def test_four_shares_and_what_is_replicated_once_are_the_uncut_layer(model):
    """Guide section 4: the 4 ranks' routed parts (each over ALL 16
    experts' router, computing its own 4) + the shared expert and
    everything replicated (attention, indexer, router) counted once =
    the layer with all 16 experts held."""
    b, lp = model.block, model.params["layers"][1]
    whole = gd.init_params(
        jax.random.key(9), vocab=80, layers=2, first_dense=1,
        dtype=jnp.float32, d=32, heads=4, nope=8, rope_dim=64, v_dim=8,
        rank=128, q_rank=24, index_heads=8, index_dim=128, dense_width=48,
        expert_width=16, shared_width=16, router_width=16,
        held=16)["layers"][1]
    x = jnp.asarray(np.random.RandomState(3).randn(30, 32), jnp.float32)
    kw = dict(first=False, num_heads=4, nope=8, rope_dim=64, index_heads=8,
              index_rope=64, index_topk=TOPK, top_k=3, scale=2.5, eps=b.eps,
              theta=b.theta)
    assert lp["w_gate"].shape[0] == 4 and whole["w_gate"].shape[0] == 16
    uncut, _, _ = ref.layer(whole, x, held=(0, 16), ablate=None, **kw)
    without_shared, _, _ = ref.layer(whole, x, held=(0, 16),
                                     ablate="shared_off", **kw)
    shared = uncut - without_shared
    parts = []
    for r in range(4):
        mine = {**whole, **{n: whole[n][4 * r:4 * r + 4]
                            for n in ("w_gate", "w_up", "w_down")}}
        parts.append(ref.layer(mine, x, held=(4 * r, 4),
                               ablate="shared_off", **kw)[0])
    # what a share holds replicated: the residual after attention
    nothing = {**whole, **{n: jnp.zeros_like(whole[n][:1])
                           for n in ("w_gate", "w_up", "w_down")}}
    replicated = ref.layer(nothing, x, held=(0, 1), ablate="shared_off",
                           **kw)[0]
    total = replicated + shared + sum(p - replicated for p in parts)
    assert ref.rel_rms(total, uncut) <= 1e-5
    assert ref.rel_rms(parts[0], uncut) > 1e-4     # a share is not the layer


# -- latent and index rows together -------------------------------------------


def test_copy_page_copies_latent_and_index_rows_of_every_layer(model):
    prompt, tokens = _prompt(21, seed=9), _prompt(2, seed=10)
    pages = model.allocator.alloc(model.context_pages(prompt, len(tokens)))
    spare = model.allocator.alloc(1)
    try:
        ctx, _, _ = model.prefill(prompt, pages)
        model.copy_page(pages[1], spare[0])
        for pool in (np.asarray(model.k_pool), np.asarray(model.v_pool)):
            np.testing.assert_array_equal(pool[:, spare[0]],
                                          pool[:, pages[1]])
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


def test_freed_pages_given_to_another_sequence_hold_its_rows_alone(model):
    """Free / realloc: a sequence seated on pages another just left
    reads what a fresh pool gives (both pools are overwritten together:
    a stale index row beside a new latent row would change the set)."""
    first, second = _prompt(45, seed=11), _prompt(30, seed=12)
    tokens = _prompt(3, seed=13)
    _through_the_pages(model, first, tokens)
    again = _through_the_pages(model, second, tokens)
    fresh = gd.GlmDsaLM(seed=3, **SIZES)
    assert ref.rel_rms(again, _through_the_pages(fresh, second, tokens)) \
        <= 1e-6


def test_a_prefix_cache_hit_and_a_beam_fork_are_taken(model):
    """One allocation seats latent and index rows, so a prefix hit
    shares both and a beam's copy-on-write split copies both."""
    from paddle_tpu.decode.prefix import PrefixCache

    cache = PrefixCache(model.allocator, model.page_size, capacity_pages=8)
    session = DecodeSession(model, max_slots=3, prefix_cache=cache)
    shared = _prompt(24, seed=30)
    first = _run(session, [shared + [5, 6, 7]], 4)[0]
    again = _run(session, [shared + [5, 6, 7]], 4)[0]
    assert cache.hits == 1                  # the suffix over cached pages
    assert first == again and _greedy(model, shared + [5, 6, 7], first)
    beam = BeamRequest(_prompt(19, seed=31), beam_size=2, max_new_tokens=3)
    session.submit(beam)
    session.run(300)
    beam.wait(5)
    assert beam.beams and beam.tokens == beam.beams[0][1]
    assert model.allocator.pages_in_use == cache.cached_pages


# -- the kernels --------------------------------------------------------------


@pytest.mark.parametrize("pages_per_seq", [8, 6], ids=["one_turn", "fetch6"])
def test_paged_index_scores_match_the_reference_over_ragged_lengths(
        pages_per_seq):
    rng = np.random.RandomState(pages_per_seq)
    J, D, pg, N, P = 8, 128, 8, 64, pages_per_seq
    lens = np.asarray([1, 5, 8, 4 * pg + 1, P * pg], np.int32)
    q = jnp.asarray(rng.randn(len(lens), J, D), jnp.float32)
    w = jnp.asarray(rng.randn(len(lens), J), jnp.float32)
    pages = jnp.asarray(rng.randn(N, pg, D), jnp.float32)
    tables = rng.randint(1, N, (len(lens), P)).astype(np.int32)
    tables[0] = 0                                   # the null table
    args = (q, w, pages, jnp.asarray(tables), jnp.asarray(lens))
    got = np.asarray(sl.paged_index_scores(*args, interpret=True))
    want = np.asarray(sl.paged_index_scores_reference(*args))
    seen = np.arange(P * pg)[None, :] < lens[:, None]
    assert (np.isneginf(got) == ~seen).all()
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-4)
    assert sl.fetch_pages(P) == P and sl.fetch_pages(200) == 25


def test_index_scores_and_selected_flash_match_their_references():
    rng = np.random.RandomState(0)
    J, D, T, n, H = 8, 128, 256, 512, 4
    q = jnp.asarray(rng.randn(J, T, D), jnp.float32)
    w = jnp.asarray(rng.randn(T, J), jnp.float32)
    k = jnp.asarray(rng.randn(n, D), jnp.float32)
    first = jnp.asarray([256], jnp.int32)
    seen = np.arange(n)[None, :] <= 256 + np.arange(T)[:, None]
    got = np.asarray(sl.index_scores(q, w, k, first, interpret=True))
    want = np.asarray(sl.index_scores_reference(q, w, k))
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-4)
    qq, kk, vv = (jnp.asarray(rng.randn(H, m, D), jnp.float32)
                  for m in (T, n, n))
    sel = (rng.rand(T, n) < 0.3) & seen
    sel[np.arange(T), 256 + np.arange(T)] = True
    bias = jnp.asarray(np.where(sel, 0.0, -1e30), jnp.float32)
    got = sl.selected_flash_attention(qq, kk, vv, bias, first, scale=0.1,
                                      interpret=True)
    want = sl.selected_attention_reference(qq, kk, vv, bias, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert sl.flash_fits(64, 8192, 8192, 256)
    assert sl.flash_fits(64, 4096, 25600, 256)
    assert not sl.flash_fits(4, 64, 64, 72)         # the toy heads
    assert sl.dense_fits(4096, 25600, 32, 128)
    assert sl.paged_fits(jnp.bfloat16, 128, 32, 128)
    assert not sl.paged_fits(jnp.bfloat16, 8, 32, 128)


@pytest.mark.parametrize("slots", [S, 16], ids=["mask", "selection_bias"])
def test_the_decode_step_selects_through_the_kernels(model, kernels, slots):
    """The toy sizes fit the decode kernels: the steps' index scores by
    ``paged_index_scores``, the selected sets as a bias (of 16 slots by
    ``selection_bias``, a row of the kernel a slot; four are no row
    block and keep ``selection_mask``) and the read by
    ``latent_paged_attention``'s walk of the slot's own pages under it,
    interpreted, counted, the reference model's rows.  Every other slot
    is empty: fewer rows than are kept."""
    prompt, tokens = _prompt(40, seed=40), _prompt(3, seed=1)
    count = metrics.REGISTRY.get("pallas_dispatch_total").value
    names = ("paged_index_scores", "selection_bias",
             "latent_paged_attention")
    before = [count(kernel=k, path="interpret") for k in names]
    got = _through_the_pages(model, prompt, tokens, S=slots)
    # one trace of the step: a layer scores once, selects once and
    # walks in both branches of the ``cond``
    assert [count(kernel=k, path="interpret") - b
            for k, b in zip(names, before)] == [
        model.layers, model.layers * (slots == 16), 2 * model.layers]
    assert ref.rel_rms(got, _want(model, prompt + tokens, 40)) <= TOL


def test_the_steps_selection_is_selection_mask_over_ragged_slots():
    """``selection_bias`` as the step calls it (a row a slot, every
    column up to the longest slot's last seen, -inf past a slot's own
    rows) against ``selection_mask`` over each slot's rows: the same
    members among a slot's rows; what a slot short of ``k`` rows keeps
    past them is the walk's to mask.  A run of equal scores lies across
    the k-th place of the long slots."""
    rng = np.random.RandomState(7)
    n, k = 2048, 100
    lens = np.asarray([0, 1, 57, 99, 100, 101, 640, 1023, 1024, 1500,
                       2047, 300, 5, 900, 1999, 77], np.int32)   # rows - 1
    scores = rng.randn(len(lens), n).astype(np.float32)
    scores[6:, 3:600:4] = 0.75
    seen = np.arange(n)[None, :] <= lens[:, None]
    masked = jnp.asarray(np.where(seen, scores, -np.inf))
    want = np.asarray(gd.selection_mask(masked, jnp.asarray(seen), k))
    bias = np.asarray(sl.selection_bias(
        masked, jnp.asarray([lens.max()], jnp.int32), k=k,
        dtype=jnp.float32, interpret=True))
    np.testing.assert_array_equal((bias == 0) & seen, want)
    np.testing.assert_array_equal(want.sum(-1), np.minimum(lens + 1, k))
    order = np.argsort(-np.asarray(masked), axis=-1, kind="stable")[:, :k]
    top = np.zeros_like(want)
    np.put_along_axis(top, order, True, axis=-1)    # ``top_k``'s members
    np.testing.assert_array_equal(top & seen, want)


# -- the prefill's selection as a kernel --------------------------------------


def _selection_case(case):
    """(scores (T, n) float32, first, k, the bias' dtype) of one case of
    ``selection_bias`` against ``selection_mask``."""
    rng = np.random.RandomState(len(case))
    T, n, first, k, dtype = 64, 1024, 0, 100, jnp.float32
    if case == "short_rows":
        # a bucket from its first row: rows 0..99 see k keys or fewer
        # and keep them all, the rows after them select; 128 rows are
        # two row blocks, the first of which never fills a chunk
        T, k = 128, 100
    elif case == "tied_run":
        first = 896                     # a chunk over 896 cached rows
    elif case == "chunk_over_nan":
        # a chunk over 200 cached rows: its rows see 201..264 keys, the
        # kernel compares the first chunk of 1,024 and skips the second
        n, first = 2048, 200
    elif case == "narrow_row_block":
        # a key width at which 64 rows do not stay resident: two grid
        # steps of 32 rows, in the pools' bfloat16
        n, first, k, dtype = 33792, 30000, 2048, jnp.bfloat16
        assert sl.selection_rows(T, n, 2) == 32
        assert sl.selection_rows(T, 25600, 2) == 64
    scores = rng.randn(T, n).astype(np.float32)
    if case == "tied_run":
        # a run of equal scores across the k-th place of every row, in
        # columns that straddle two chunks of the count: 60 above it, 40
        # places for 300 tied
        scores = np.minimum(scores, 0.0)
        scores[:, 5:905:15] = 3.0
        scores[:, 400:1000:2] = 1.5
    elif case == "signs_and_infinities":
        scores = rng.choice(
            np.asarray([0.0, -0.0, np.inf, -np.inf, 1e-30, -1e-30, 2.5,
                        -2.5], np.float32), size=(T, n))
        first, k = 500, 200            # the edge falls among the zeros
    elif case == "chunk_over_nan":
        # what ``index_scores`` leaves unwritten: the blocks of 512 keys
        # past the sight of a block of 256 query rows (keys 1,024 on: the
        # half of the compared chunk no row sees, and all of the other)
        unseen = (np.arange(n)[None, :] // 512 * 512
                  > first + np.arange(T)[:, None] // 256 * 256 + 255)
        assert unseen[:, 512:].all() and not unseen[:, :512].any()
        scores[unseen] = np.nan
    return scores, first, k, dtype


@pytest.mark.parametrize("case", [
    "random", "tied_run", "short_rows", "chunk_over_nan",
    "signs_and_infinities", "narrow_row_block"])
def test_selection_bias_is_selection_mask_member_for_member(case):
    scores, first, k, dtype = _selection_case(case)
    T, n = scores.shape
    seen = np.arange(n)[None, :] <= first + np.arange(T)[:, None]
    want = np.asarray(gd.selection_mask(jnp.asarray(scores),
                                        jnp.asarray(seen), k))
    bias = sl.selection_bias(jnp.asarray(scores),
                             jnp.asarray([first], jnp.int32), k=k,
                             dtype=dtype, interpret=True)
    assert bias.dtype == dtype and bias.shape == (T, n)
    got = np.asarray(bias.astype(jnp.float32))
    np.testing.assert_array_equal(got == 0, want)
    assert (got[~want] <= -1e29).all()
    np.testing.assert_array_equal(
        want.sum(-1), np.minimum(first + np.arange(T) + 1, k))


def test_which_shapes_the_selection_kernel_takes():
    assert sl.selection_fits(8192, 8192, jnp.bfloat16)
    assert sl.selection_fits(4096, 25600, jnp.bfloat16)
    assert sl.selection_fits(32, 128, jnp.float32)
    assert not sl.selection_fits(16, 64, jnp.float32)    # the toy chunk
    assert not sl.selection_fits(8, 128, jnp.float32)
    assert sl.selection_rows(4096, 25600, 2) == 64
    assert sl.selection_rows(4096, 28672, 4) == 32
    assert sl.selection_rows(48, 1024, 2) == 16
    # past half of VMEM_LIMIT at 16 rows no block is resident
    assert not sl.selection_fits(4096, 1 << 18, jnp.bfloat16)


@pytest.mark.parametrize("rows, first", [(128, 0), (32, 96)],
                         ids=["prompt", "chunk"])
def test_select_through_the_kernel_is_select_through_the_reference(
        model, rows, first):
    """``GlmDsaBlock.select`` of a toy prompt (both prefill kernels) and
    of a toy chunk over cached rows (32 rows: the scores by the jnp
    reference, the selection by the kernel), against the path with the
    kernels off.  Whole-number queries and keys and head weights in
    quarters: the scores are exact in any order of the sum, so the two
    paths score alike to the bit, and tie often."""
    block, n = model.block, 128
    rng = np.random.RandomState(rows)
    q = jnp.asarray(rng.randint(-2, 3, (rows, block.index_heads,
                                        block.index_dim)), jnp.float32)
    w = jnp.asarray(rng.randint(-4, 5, (rows, block.index_heads)) / 4.0,
                    jnp.float32)
    keys = jnp.asarray(rng.randint(-2, 3, (n, block.index_dim)), jnp.float32)
    count = metrics.REGISTRY.get("pallas_dispatch_total").value
    state = dict(pk._STATE)
    try:
        pk.enable(False)
        want = np.asarray(block.select(q, w, keys, jnp.int32(first)))
        pk.enable(True, interpret=True)
        before = count(kernel="selection_bias", path="interpret")
        got = np.asarray(block.select(q, w, keys, jnp.int32(first)))
        assert count(kernel="selection_bias",
                     path="interpret") - before == 1
    finally:
        pk._STATE.update(state)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        (got == 0).sum(-1), np.minimum(first + np.arange(rows) + 1, TOPK))


# -- behind the session -------------------------------------------------------


def _run(session, prompts, n):
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=n))
            for p in prompts]
    session.run(max_steps=500)
    return [r.result(1) for r in reqs]


def test_the_session_decodes_what_the_hand_driven_pages_decode(model):
    """Behind the session, a prompt in chunks among them; every step
    reads ``index_topk`` rows a slot a layer and no more."""
    scored = metrics.REGISTRY.get("attn_index_rows_scored_total")
    selected = metrics.REGISTRY.get("attn_index_rows_selected_total")
    before = scored.value(), selected.value()
    session = DecodeSession(model, max_slots=2)
    prompts = [_prompt(19, seed=20), _prompt(90, seed=21)]
    got = _run(session, prompts, 5)
    after = scored.value(), selected.value()
    assert all(_greedy(model, p, g) for p, g in zip(prompts, got))
    assert [len(g) for g in got] == [5, 5]
    gauge = metrics.REGISTRY.get("decode_cache_rows")
    assert gauge.value(kind="latent") == gauge.value(kind="index") == 0
    # a slot's steps score its rows, its own among them: 4 steps each
    # that deliver a token (a step behind a sequence's last may ride)
    rows = [n + i for n in (19, 90) for i in range(1, 5)]
    steps = (after[1] - before[1]) / TOPK
    assert steps == int(steps) and len(rows) <= steps <= len(rows) + 2
    assert sum(rows) <= after[0] - before[0] <= sum(rows) + 2 * 95


def test_cache_rows_and_bytes_count_both_kinds_as_stored(model):
    assert model.cache_rows([10, 30]) == {"latent": 120, "index": 120}
    assert model.row_bytes == 256 * 4 and model.index_row_bytes == 128 * 4
    assert model.cache_bytes([10, 30]) == {"latent": 120 * 256 * 4,
                                           "index": 120 * 128 * 4}
    assert model.k_pool.shape == (3, 80, 8, 256)
    assert model.v_pool.shape == (3, 80, 8, 128)


def test_what_the_sparse_model_cannot_do_is_refused_by_name(model):
    toks = np.zeros((S, 2), np.int64)
    with pytest.raises(gd.UnsupportedOverSelectedRows, match="verify chunk"):
        model.verify_chunk(toks, [], np.zeros((S, 16), np.int32),
                           np.zeros((S,), np.int32))
    with pytest.raises(ValueError, match="cached_len"):
        model.prefill([3] * 9, [1, 2], cached_len=5)    # not whole pages
    with pytest.raises(ValueError, match="outside 1..128"):
        model.prefill_bucket(129)
    with pytest.raises(ValueError, match="whole pages"):
        gd.GlmDsaLM(**{**SIZES, "chunk_rows": 12})


def test_the_prefill_counts_the_pairs_it_scored(model):
    counter = metrics.REGISTRY.get("attn_index_prefill_pairs_total")
    before = counter.value()
    pages = model.allocator.alloc(model.context_pages([2] * 100, 0))
    try:
        model.prefill(_prompt(11, seed=40), pages)      # a 64-row bucket
        assert counter.value() - before == 11 * 12 // 2
        model.prefill(_prompt(100, seed=41), pages)     # bucket + chunks
        assert counter.value() - before == 66 + 100 * 101 // 2
    finally:
        model.allocator.free(pages)


# -- scopes -------------------------------------------------------------------


def test_named_scopes_place_the_indexer_the_selection_and_the_read(model):
    from paddle_tpu.decode import model as dm

    kw = dict(heads=model.heads, block=model.block)
    tables = np.zeros((S, model.pages_per_seq), np.int32)
    pools = (model.params, model.k_pool, model.v_pool)
    texts = {
        "_decode_step": dm._decode_step.lower(
            *pools, tables, np.zeros((S,), np.int32),
            np.zeros((S,), np.int32), page_size=model.page_size, **kw),
        "_prefill_bucket_chunk": gd._prefill_bucket_chunk.lower(
            *pools, tables[0], np.int32(64), np.zeros((16,), np.int32),
            np.int32(9), page_size=model.page_size, extent=16, **kw),
        "_prefill_bucket": dm._prefill_bucket.lower(
            *pools, np.zeros((64,), np.int32), np.zeros((64,), np.int32),
            np.int32(1), **kw)}
    for program, lowered in texts.items():
        text = lowered.as_text(debug_info=True)
        for scope in ("attn_latent_down", "attn_index", "attn_index_select",
                      "attn_sparse"):
            assert re.search(
                rf"{program}\)/blk_mixer/attn_latent/(cond/[a-z_0-9]+/)?"
                rf"{scope}/", text), (program, scope)
        for scope in ("moe_shared", "moe_router", "moe_experts"):
            assert re.search(
                rf"{program}\)/blk_mlp/(while/body/)?{scope}/", text), (
                program, scope)
