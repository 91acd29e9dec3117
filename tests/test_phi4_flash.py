"""Phi-4-mini-flash-reasoning behind /generate
(``paddle_tpu/models/phi4_flash.py``): a decoder-hybrid-decoder whose
cross layers read ANOTHER layer's page run, whose GMUs read an
activation an earlier layer hands on, and whose prefill stops half-way
down; Mamba-1 states in a state entry, window layers on rings,
differential attention on packed pages.  CPU, float32, toy widths that
keep the structure (eight layers: three Mamba-1, two windows of 8 rows
on rings of 3 pages of 4, the full layer, a GMU, a cross layer); the
plain reference is ``perf/reference/phi4_flash_block.py``.  The cases
that take ``step_path`` run once more through the step's Pallas kernels
interpreted (``s6_step``, ``conv_step``, the grouped paged kernel on
heads-major pages)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode import model as dm
from paddle_tpu.decode.attention import (
    ragged_paged_attention_gqa,
    ragged_paged_attention_gqa_reference,
)
from paddle_tpu.decode.session import (
    AdmissionRefused,
    BeamRequest,
    DecodeRequest,
    DecodeSession,
)
from paddle_tpu.decode.state_entry import UnsupportedOverState
from paddle_tpu.models import phi4_flash as pf
from paddle_tpu.models.phi4_flash import CROSS, FULL, GMU, MAMBA, WINDOW
from paddle_tpu.observability import metrics
from paddle_tpu.pallas import s6_step as s6
from perf.reference import phi4_flash_block as ref

# d_inner 128 (a row of lanes) and a state of 8 (a tile of rows): what
# the step kernel's fits() asks; heads of 8 on half as many K/V heads
SIZES = dict(vocab=96, d_model=32, num_heads=4, num_kv_heads=2,
             num_layers=8, intermediate_size=48, sliding_window=8,
             mamba_d_state=8, mamba_expand=4, mamba_dt_rank=2, max_len=128,
             num_pages=120, page_size=4, pages_per_seq=32, state_entries=5,
             dtype="float32")
REF = dict(num_heads=4, head_dim=8, window=8)


def make(seed=3, **over):
    return pf.Phi4FlashLM(seed=seed, **{**SIZES, **over})


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 96, n).tolist()


def reference(m, ids, rows=None, ablate=None, **kw):
    return ref.forward(m.params, jnp.asarray(ids, jnp.int32), **REF,
                       rows=rows, ablate=ablate, **kw)


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return make()


@pytest.fixture(params=["xla", "kernel"])
def step_path(request):
    if request.param == "xla":
        yield
        return
    pk.enable(True, interpret=True)
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        yield
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()


def through_the_cache(m, ids, tokens, slots=4, slot=2):
    """Prefill through the bucket's program, then the tokens teacher-
    forced through decode steps -> (the len(tokens) + 1 logits rows, the
    sequence's ids: pages and entry, freed)."""
    pages = m.allocator.alloc(m.context_pages(ids, len(tokens)))
    try:
        ctx, _, last = m.prefill(ids, pages)
        rows = [np.asarray(last)]
        tables = np.zeros((slots, m.pages_per_seq), np.int32)
        tables[slot] = m.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), m.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = m.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot]))
    finally:
        m.allocator.free(pages)
    return np.stack(rows), pages


# -- which layer is which -----------------------------------------------------


def test_the_published_models_layers_are_the_issues_table():
    kinds = pf.layer_kinds(32)
    assert [i for i, k in enumerate(kinds) if k == MAMBA] == list(
        range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == WINDOW] == list(
        range(1, 16, 2))
    assert kinds.index(FULL) == 17 and kinds.count(FULL) == 1
    assert [i for i, k in enumerate(kinds) if k == GMU] == list(
        range(18, 31, 2))
    assert [i for i, k in enumerate(kinds) if k == CROSS] == list(
        range(19, 32, 2))
    assert kinds == tuple(
        {"mamba": MAMBA, "window": WINDOW, "full": FULL, "gmu": GMU,
         "cross": CROSS}[ref.kind_of(i, 32)] for i in range(32))
    assert pf.lam0(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    for bad in (6, 30):
        with pytest.raises(ValueError, match="periods of four"):
            pf.layer_kinds(bad)
    with pytest.raises(ValueError, match="mb_per_layer 2"):
        pf.layer_kinds(32, 3)


def test_a_table_row_is_the_run_the_rings_and_the_entry(model):
    b = model.block
    assert (model.rings, model.ring_pages, model.full_pages) == (2, 3, 32)
    assert model.pages_per_seq == 32 + 2 * 3 + 1 and b.entry_at == 38
    assert [b.layer(i).ring_at for i in (1, 3)] == [32, 35]
    assert b.owner == 5 and b.layer(4).hands_memory
    assert not b.layer(2).hands_memory
    # a reservation: the run's pages, the rings', one entry
    assert model.context_pages(prompt(21), 6) == 7 + 6 + 1
    ids = model.allocator.alloc(14)
    try:
        t = model.pool_table(ids)
        assert list(t[:7]) == ids[:7] and not t[7:32].any()
        assert list(t[32:38]) == ids[7:13]
        assert t[38] == model.allocator.entry_of(ids) > 0
    finally:
        model.allocator.free(ids)
    # the cross layer and the GMU own no column, no pool, no entry
    assert model.k_pool.shape == (1, 120, 1, 4, 16)
    assert model.state_pool.shape == (3, 5, 8, 128)


# -- the recurrence -----------------------------------------------------------


def _s6_inputs(T, C=128, N=8, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    return (f(T, C), jax.nn.softplus(f(T, C) - 2.0),
            -jnp.exp(f(N, C) * 0.3), f(T, N), f(T, N), f(N, C) * 0.1)


def _row_by_row(x, dt, A, B, C, S):
    ys = []
    for t in range(x.shape[0]):
        y, S = pf.step_s6(x[t], dt[t], A, B[t], C[t], S)
        ys.append(y)
    return jnp.stack(ys), S


@pytest.mark.parametrize("T, unroll", [(1, 16), (16, 16), (37, 16), (37, 5),
                                       (48, 8), (50, 64)])
def test_the_unrolled_scan_is_the_row_by_row_loop(T, unroll):
    """Loop bodies that do and do not divide the prompt."""
    args = _s6_inputs(T)
    y, S = pf.scan_s6(*args, unroll=unroll)
    want_y, want_S = _row_by_row(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-6)


def test_a_row_with_no_step_leaves_the_state_as_it_was():
    x, dt, A, B, C, S = _s6_inputs(9)
    dt = dt.at[4:].set(0.0)                 # a bucket's padding
    _, got = pf.scan_s6(x, dt, A, B, C, S)
    _, want = pf.scan_s6(x[:4], dt[:4], A, B[:4], C[:4], S)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("C, N, entries", [(128, 8, 4), (256, 16, 7)])
def test_the_step_kernel_is_the_step_on_the_gathered_entries(C, N, entries):
    rng = np.random.RandomState(1)
    S = 5
    pool = jnp.asarray(rng.randn(entries, N, C), jnp.float32)
    at = jnp.asarray([2, 0, 3, 0, 1], jnp.int32)       # two slots idle
    x, dt, A, B, Cc, _ = _s6_inputs(S, C, N, seed=2)
    assert s6.fits(pool.dtype, N, C)
    y, out = s6.s6_step(pool, at, dt, x * dt, A, B, Cc, interpret=True)
    want_y, new = pf.step_s6(x, dt, A, B, Cc, pool[at])
    live = np.asarray(at) > 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(at)[live]],
                               np.asarray(new)[live], rtol=1e-5, atol=1e-6)
    # an entry no slot addresses is untouched
    untouched = sorted(set(range(1, entries)) - set(np.asarray(at).tolist()))
    np.testing.assert_array_equal(np.asarray(out)[untouched],
                                  np.asarray(pool)[untouched])


def test_the_step_kernel_fits_whole_tiles_of_float32():
    assert s6.fits(jnp.float32, 16, 5120)
    assert s6.channel_block(16, 5120) == 5120
    assert s6.channel_block(128, 5120) == 2560      # 1.5 MB a block
    assert not s6.fits(jnp.bfloat16, 16, 5120)
    assert not s6.fits(jnp.float32, 16, 5100)       # no whole lanes
    assert not s6.fits(jnp.float32, 12, 5120)       # no whole tile of rows


# -- differential attention on the packed pages -------------------------------


@pytest.mark.parametrize("li", [1, 5, 7], ids=["window", "full", "cross"])
def test_the_packed_route_is_the_four_softmax_form(model, li):
    """Query heads widened to the stored row's lanes against K/V pairs
    stored side by side give ``a_i`` whole: the layer's mixer equals the
    reference's four softmax products a pair, query pair p on K/V pair
    p // 2, and does NOT equal plain grouped heads' pairing (query head
    h on K head h // 2)."""
    T = 24
    lb, lp = model.block.layer(li), model.params["layers"][li]
    rng = np.random.RandomState(li)
    x = jnp.asarray(rng.randn(T, 32), jnp.float32)
    kept = [None] * li
    kv = None
    if li == 7:
        # a cross layer reads what the full layer kept
        _, kept[5] = model.block.layer(5).prompt_mixer(
            model.params["layers"][5], x * 0.5, jnp.arange(T), 4, None)
        k, v = kept[5]
        kv = (k.reshape(T, 2, 8), v.reshape(T, 2, 8))
    got, _ = lb.prompt_mixer(lp, x, jnp.arange(T), 4, None, kept, None)
    u = ref.norm(x, lp["w_in"], lp["b_in"], eps=1e-5)
    kind = ref.kind_of(li, 8)

    def want(ablate=None):
        m, _ = ref.attention_mixer(lp, u, kv, idx=li, kind=kind, heads=4,
                                   head_dim=8, window=8, eps=1e-5,
                                   ablate=ablate)
        return x + m

    np.testing.assert_allclose(got, want(), rtol=1e-4, atol=1e-5)
    assert ref.rel_rms(got - x, want("plain_gqa_pairing") - x) > 0.1


def test_the_grouped_kernel_takes_pages_with_their_heads_outside():
    rng = np.random.RandomState(0)
    S, T, Hq, Hkv, D, N, pg, P = 3, 1, 8, 2, 16, 9, 4, 4
    q = jnp.asarray(rng.randn(S, T, Hq, D), jnp.float32)
    k = jnp.asarray(rng.randn(N, Hkv, pg, D), jnp.float32)
    v = jnp.asarray(rng.randn(N, Hkv, pg, D), jnp.float32)
    tables = jnp.asarray(rng.randint(1, N, (S, P)), jnp.int32)
    lens = jnp.asarray([0, 7, 13], jnp.int32)
    want = ragged_paged_attention_gqa_reference(
        q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), tables, lens)
    np.testing.assert_allclose(
        ragged_paged_attention_gqa_reference(q, k, v, tables, lens,
                                             heads_major=True), want)
    got = ragged_paged_attention_gqa(q, k, v, tables, lens, interpret=True,
                                     heads_major=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# -- through the caches -------------------------------------------------------


def _ring_dispatches():
    counter = metrics.REGISTRY.get("pallas_dispatch_total")
    return {p: counter.value(kernel="ring_paged_attention", path=p)
            for p in ("compiled", "interpret", "reference")}


def test_the_decode_step_reads_its_rings_by_the_window_kernel():
    """Pages of 8 rows are pages the paged kernels fit (the toy's 4 are
    not): 21 prompt rows and 30 steps, twice round a ring of three
    pages under a window of 16, with the kernels on (interpreted) and
    off give the same logits at the grouped kernel's tolerance, and a
    traced step decides once a window layer: the kernel for these
    heads-major pages, the gathered reference with the kernels off."""
    sizes = dict(page_size=8, sliding_window=16, pages_per_seq=16)
    ids, tokens = prompt(21, 11), prompt(30, 12)
    jax.clear_caches()
    before = _ring_dispatches()
    plain = make(**sizes)
    assert (plain.rings, plain.ring_pages) == (2, 3)
    want, _ = through_the_cache(plain, ids, tokens)
    off = _ring_dispatches()
    assert off["reference"] - before["reference"] == plain.rings
    pk.enable(True, interpret=True)
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        got, _ = through_the_cache(make(**sizes), ids, tokens)
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()
    on = _ring_dispatches()
    assert {p: on[p] - off[p] for p in on} == {
        "compiled": 0, "interpret": plain.rings, "reference": 0}
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert ref.rel_rms(got, want) < 2e-5


def test_dense_forward_is_the_reference(model):
    ids = prompt(37)
    logits, _, _ = model._forward(jnp.asarray(ids, jnp.int32))
    assert ref.rel_rms(logits, reference(model, ids)) < 1e-5


@pytest.mark.parametrize("T", [5, 21, 40])
def test_prefill_then_steps_through_the_caches_match_the_reference(
        step_path, T):
    """40 + 16 rows wrap the rings (12 rows of 3 pages, a window of 8)
    and run past the window in the run."""
    m = make()
    ids, tokens = prompt(T, T), prompt(16, 100 + T)
    got, _ = through_the_cache(m, ids, tokens)
    want = reference(m, ids + tokens, rows=list(range(T - 1, T + 16)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert ref.rel_rms(got, want) < 1e-4


def test_the_entry_after_steps_is_the_entry_one_prefill_leaves(step_path):
    m = make()
    ids, tokens = prompt(21, 7), prompt(8, 8)
    pages = m.allocator.alloc(m.context_pages(ids, len(tokens)))
    entry = m.allocator.entry_of(pages)
    m.allocator.free(pages)
    through_the_cache(m, ids, tokens)
    stepped = np.asarray(m.state_pool[:, entry])
    tails = np.asarray(m.conv_pool[:, entry])
    pages = m.allocator.alloc(m.context_pages(ids + tokens, 0))
    try:
        assert m.allocator.entry_of(pages) == entry     # reused, no reset
        m.prefill(ids + tokens, pages)
    finally:
        m.allocator.free(pages)
    np.testing.assert_allclose(stepped, m.state_pool[:, entry], rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(tails, m.conv_pool[:, entry], rtol=1e-5,
                               atol=1e-6)
    _, states = reference(m, ids + tokens, rows=[0], states=True)
    np.testing.assert_allclose(stepped, jnp.swapaxes(states, 1, 2),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("n", [1, 3, 8, 21, 63, 64, 65, 100])
def test_the_half_way_prefill_is_the_all_rows_prefill(model, n):
    """The bucket's program runs layers from the full one on down on row
    ``n - 1`` alone; every layer on every row gives the same last row's
    logits and the same caches."""
    ids = prompt(n, n)
    bucket = model.prefill_bucket(n)
    toks = np.zeros((bucket,), np.int32)
    toks[:n] = ids
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    try:
        where = model._prompt_rows(pages, bucket, n)
        fresh = tuple(jnp.zeros_like(p) for p in model._cache())
        logits, *half = dm._prefill_bucket(
            model.params, *fresh[:2], toks, where, np.int32(n),
            heads=model.heads, block=model.block, extra=fresh[2:])
        half = (*half[:2], *half[3])
        live = jnp.arange(bucket) < n
        x, kept, _ = dm._dense_blocks(model.block, model.params,
                                      jnp.asarray(toks), model.heads, live)
        assert x.shape == (bucket, 32)
        # the layer that hands its y on kept every row's, not one
        assert kept[4][2].shape == (bucket, 128)
        whole = model.block.store_prompts(
            tuple(jnp.zeros_like(p) for p in model._cache()), kept, where)
    finally:
        model.allocator.free(pages)
    np.testing.assert_allclose(
        logits, model.block.head(model.params, x[n - 1]), rtol=1e-4,
        atol=1e-5)
    for a, b in zip(half, whole):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# -- what a prompt leaves in the pools, page by page ---------------------------


def _rowwise_store(m, cache, rows, states, pages, bucket, n):
    """The store as it was before it went page by page, the oracle:
    every bucket row of every layer that owns K/V scattered a (row,
    head) at a time (``write_rows``), a window layer's rows before its
    ring to the null page with the padding.  A bucket that ``max_len``
    cut inside a page it takes with zero rows after it up to the page:
    rows that ``lens`` hides, whatever they hold."""
    table = m.pool_table(pages)
    pg, R = m.page_size, m.ring_pages
    rows = [tuple(jnp.pad(r, ((0, -r.shape[0] % pg), (0, 0), (0, 0)))
                  for r in kv) for kv in rows]
    bucket = rows[0][0].shape[0]
    at = np.arange(bucket)
    page_of = at // pg
    last = (n - 1) // pg
    in_ring = (page_of > last - R) & (page_of <= last)
    flat = np.zeros((m.rings + 1, bucket), np.int32)
    for i in range(m.rings):
        col = m.full_pages + i * R
        flat[i] = np.where(in_ring, table[col:col + R][page_of % R],
                           0) * pg + at % pg
    flat[m.rings] = np.where(
        page_of < m.full_pages,
        table[np.minimum(page_of, m.full_pages - 1)], 0) * pg + at % pg
    entry = table[m.block.entry_at]
    k_pool, v_pool, state_pool, conv_pool = cache

    def stored(pool, which):
        stack = jnp.stack([kv[which] for kv in rows])
        return pf.write_rows(pool, jnp.asarray(flat.reshape(-1)),
                             stack.reshape((-1,) + stack.shape[2:]))

    return (stored(k_pool, 0), stored(v_pool, 1),
            state_pool.at[:, entry].set(jnp.stack([s for s, _ in states])),
            conv_pool.at[:, entry].set(jnp.stack(
                [t for _, t in states]).reshape(
                    (len(states),) + conv_pool.shape[2:])))


# (what the case is, the model's sizes beside SIZES, prompt lengths);
# rings of 3 pages of 4 rows unless the sizes say otherwise
_STORES = [
    ("shorter than a page", {}, [1, 3]),
    ("shorter than a ring", {}, [4, 5, 8, 11]),
    ("exactly a ring", {}, [12]),
    # last % ring_pages of every value, a page's first and last row
    ("wraps the ring", {}, [13, 16, 17, 20, 21, 24, 25, 63, 64]),
    ("the second bucket", {}, [65, 100, 127, 128]),
    ("a bucket of fewer pages than a ring",
     dict(page_size=32, sliding_window=64, pages_per_seq=4, num_pages=40),
     [1, 31, 33, 64, 65, 128]),
    # perf/configs/phi-4-mini-flash-reasoning.json's ``rehearse`` sizes
    ("the rehearsal model's top bucket",
     dict(pages_per_seq=16, max_len=64, num_pages=160, mamba_expand=2),
     [50, 61, 64]),
    ("a top bucket max_len cut inside a page",
     dict(max_len=50), [45, 48, 49, 50]),
]


@pytest.mark.parametrize(
    "sizes,n", [pytest.param(sizes, n, id=f"{name}-{n}")
                for name, sizes, ns in _STORES for n in ns])
def test_the_page_wise_store_leaves_what_the_row_wise_store_left(sizes, n):
    """On every page the sequence holds (its run's and its rings') and
    on its state entry the pools after ``store_prompts`` are the pools
    after the (row, head) scatter of every bucket row; every other
    sequence's pages and entries are as they were.  The null page is
    anybody's."""
    m = make(**sizes)
    rng = np.random.RandomState(n)
    bucket = m.prefill_bucket(n)
    other = m.allocator.alloc(m.context_pages(prompt(9), 8))
    pages = m.allocator.alloc(m.context_pages(prompt(n), 0))
    where = m._prompt_rows(pages, bucket, n)
    assert where[0].shape == (m.rings + 1, bucket)
    cache = tuple(jnp.asarray(rng.standard_normal(p.shape), p.dtype)
                  for p in m._cache())
    H, D = cache[0].shape[2], cache[0].shape[4]

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    rows = [(normal(bucket, H, D), normal(bucket, H, D))
            for _ in range(m.rings + 1)]
    states = [(normal(*cache[2].shape[2:]), normal(*cache[3].shape[2:]))
              for _ in range(m.linear_layers)]
    owned, lin, kept = iter(rows), iter(states), []
    for li, kind in enumerate(m.block.layer_types):
        if kind == WINDOW:
            kept.append(m.block.layer(li)._ring_of(*next(owned),
                                                   jnp.int32(n)))
            assert kept[-1][0].shape[0] == min(
                m.ring_pages, -(-bucket // m.page_size)) * m.page_size
        else:
            kept.append(next(owned) if kind == FULL else
                        next(lin) if kind == MAMBA else None)
    got = m.block.store_prompts(cache, kept, where)
    want = _rowwise_store(m, cache, rows, states, pages, bucket, n)
    run, rings = m._split(pages)
    mine = sorted(set(run) | set(rings))
    for new, old, was in zip(got[:2], want[:2], cache[:2]):
        np.testing.assert_array_equal(new[0, mine], old[0, mine])
        others = np.setdiff1d(np.arange(1, new.shape[1]), mine)
        np.testing.assert_array_equal(new[0, others], was[0, others])
    entry = m.allocator.entry_of(pages)
    for new, old, was in zip(got[2:], want[2:], cache[2:]):
        np.testing.assert_array_equal(new, old)
        rest = np.setdiff1d(np.arange(new.shape[1]), [entry])
        np.testing.assert_array_equal(new[:, rest], was[:, rest])
    # the rows before the ring go nowhere: of a window layer the new
    # store names no more pages than the ring has, where the old one
    # named a row a bucket row
    assert np.count_nonzero(where[0][:m.rings]) <= m.rings * m.ring_pages


@pytest.mark.parametrize("n,new,want", [
    # 64-row bucket = 16 pages of 4; two rings of 3 pages
    (3, 0, {"run": 1, "ring": 2, "null": 15 + 4}),
    (21, 3, {"run": 6, "ring": 6, "null": 10}),
    (64, 8, {"run": 16, "ring": 6, "null": 0}),
    (65, 0, {"run": 17, "ring": 6, "null": 15}),
])
def test_the_store_counts_its_pages_by_where_they_went(model, n, new, want):
    """``decode_prefill_stored_pages_total``: a prefill's pages (a K
    page and its V page one) by ``kind``: the sequence's run, its rings,
    the null page (a bucket's pages past the sequence's, a ring's past
    a short prompt's)."""
    pages_total = metrics.REGISTRY.get("decode_prefill_stored_pages_total")

    def counted():
        return {k: pages_total.value(kind=k) for k in want}

    ids = prompt(n, 2)
    pages = model.allocator.alloc(model.context_pages(ids, new))
    before = counted()
    try:
        model.prefill(ids, pages)
    finally:
        model.allocator.free(pages)
    after = counted()
    assert {k: after[k] - before[k] for k in want} == want


def _updates_under(jaxpr, scope, out=None):
    """[(primitive, the operand's shape, numbers a single update
    writes)] of every scatter and dynamic_update_slice whose name stack
    holds ``scope``, sub-jaxprs and all."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if scope in str(eqn.source_info.name_stack):
            if name.startswith("scatter"):
                upd = eqn.invars[2].aval.shape
                dims = eqn.params["dimension_numbers"].update_window_dims
                out.append((name, eqn.invars[0].aval.shape,
                            int(np.prod([upd[d] for d in dims]))))
            elif name == "dynamic_update_slice":
                out.append((name, eqn.invars[0].aval.shape,
                            int(np.prod(eqn.invars[1].aval.shape))))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _updates_under(sub, scope, out)
    return out


@pytest.mark.parametrize("bucket", [64, 128])
def test_no_update_of_the_bucket_programs_store_is_less_than_a_page(
        model, bucket):
    """Under ``blk_store`` of a bucket's program every update into a
    pool writes a whole page (heads x rows x lanes numbers) or more (a
    state entry's layers): TWO scatters into pools of pages, one a
    pool, each over the page axis with a page as its window."""
    m, cache = model, model._cache()
    jaxpr = jax.make_jaxpr(
        lambda *a: dm._prefill_bucket(
            *a, heads=m.heads, block=m.block, extra=cache[2:]))(
        m.params, *cache[:2], np.zeros((bucket,), np.int32),
        (np.zeros((m.rings + 1, bucket), np.int32), np.int32(0)),
        np.int32(9))
    updates = _updates_under(jaxpr.jaxpr, "blk_store")
    page = int(np.prod(cache[0].shape[2:]))
    assert len(updates) == 4 and all(size >= page for *_, size in updates)
    pools = [u for u in updates if u[1] == cache[0].shape[1:]]
    assert [size for *_, size in pools] == [page, page]
    assert {name for name, *_ in pools} == {"scatter"}


def test_the_bucket_program_keeps_one_row_from_the_full_layer_on(model):
    """In the jaxpr of the 64-row bucket the cross layer's and the
    GMU's projections have ONE row; the self-decoder's have 64."""
    toks = np.zeros((64,), np.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: dm._dense_blocks(model.block, p, t, 4,
                                      jnp.arange(64) < 9, jnp.int32(8))[0]
    )(model.params, toks)
    text = str(jaxpr)
    # the feed-forward's gate: 32 -> 48, a layer; 6 layers on 64 rows
    # (0-5: the full layer's own feed-forward runs on the one row), 2 on 1
    assert text.count("f32[64,48] = dot_general") == 2 * 5
    assert text.count("f32[1,48] = dot_general") == 2 * 3
    assert "f32[64,96]" not in text         # the head: one row


def test_a_cross_layer_writes_no_page():
    """A decode step changes ONE row of the run (its one owner's) and
    one row of each ring: the cross layer and the GMU leave no trace
    anywhere in the pools.  And after a prefill and 9 steps the only
    pages with a row in them are the sequence's own."""
    m = make()
    ids = prompt(21, 3)
    pages = m.allocator.alloc(m.context_pages(ids, 9))
    try:
        ctx, _, _ = m.prefill(ids, pages)
        run, rings = m._split(pages)
        assert len(run) == 8 and len(rings) == 6
        tables = np.zeros((4, m.pages_per_seq), np.int32)
        tables[2] = m.pool_table(pages)
        lens = np.zeros((4,), np.int32)
        lens[2] = ctx
        for _ in range(9):
            before = np.asarray(m.k_pool), np.asarray(m.v_pool)
            m.decode(np.full((4, 1), 7, np.int64), [], tables, lens)
            for old, pool in zip(before, (m.k_pool, m.v_pool)):
                changed = (np.asarray(pool) != old)[0].any(axis=(1, 3))
                changed[0] = False          # idle slots scribble on page 0
                where = np.argwhere(changed)            # (page, row)
                at = int(lens[2])
                assert sorted(map(tuple, where)) == sorted(
                    [(run[at // 4], at % 4)]
                    + [(ring[(at // 4) % 3], at % 4)
                       for ring in (rings[:3], rings[3:])])
            lens[2] += 1
    finally:
        m.allocator.free(pages)
    written = np.flatnonzero(np.asarray(m.k_pool).any(axis=(0, 2, 3, 4)))
    assert set(written) <= set(run) | set(rings) | {0}


def test_rings_wrap_and_the_run_does_not(model):
    lens = [5, 21, 100]
    assert model.cache_rows(lens) == {
        "full": 126, "window": (5 + 12 + 12) * 2, "state": 3 * 3}
    b = model.cache_bytes(lens)
    row = 2 * 2 * 8 * 4                     # K and V, 2 heads of 8, float32
    assert b["full"] == 126 * row and b["window"] == 58 * row
    assert b["state"] == 3 * model.entry_bytes()
    assert model.entry_bytes() == 3 * (8 * 128 * 4 + 3 * 128 * 4)
    # at the published widths a cached row of the run is 5,120 B: ONE
    # layer's K and V in bfloat16, whatever reads it
    assert 2 * 20 * 64 * 2 == 5120


# -- the reservation, and what is refused -------------------------------------


def _run(session, prompts, n):
    reqs = [session.submit(DecodeRequest(p, max_new_tokens=n))
            for p in prompts]
    session.run(max_steps=800)
    return [r.result(1) for r in reqs]


def test_session_tokens_are_the_references(step_path):
    m = make()
    prompts = [prompt(n, 40 + n) for n in (5, 17, 33)]
    got = _run(DecodeSession(m, max_slots=4), prompts, 5)
    for p, toks in zip(prompts[:2], got):
        ids = list(p)
        for t in toks:
            assert t == int(np.argmax(reference(m, ids, [len(ids) - 1])[0]))
            ids.append(t)
    assert len(got[2]) == 5
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0


def test_admission_reserves_all_three_or_none():
    """Pages for two sequences' runs and rings, entries for four: the
    third waits, takes neither a page nor an entry, and is seated when a
    sequence ends; at the end every page and entry is free."""
    m = make(num_pages=20)                   # 19 usable; a request: 2+6
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(prompt(4, 60 + i),
                                         max_new_tokens=3 + i))
            for i in range(3)]
    session.step()
    assert session.active == 2 and session.waiting == 1
    assert m.allocator.free_entries == 2 and m.allocator.pages_in_use == 16
    session.run(max_steps=200)
    assert [len(r.result(1)) for r in reqs] == [3, 4, 5]
    assert m.allocator.free_entries == 4 and m.allocator.pages_in_use == 0
    m = make(state_entries=3)                # two usable entries
    session = DecodeSession(m, max_slots=4)
    reqs = [session.submit(DecodeRequest(prompt(4, 70 + i),
                                         max_new_tokens=3))
            for i in range(3)]
    session.step()
    assert session.active == 2 and m.allocator.free_entries == 0
    assert m.allocator.pages_in_use == 16    # the waiter took no page
    session.run(max_steps=200)
    assert all(len(r.result(1)) == 3 for r in reqs)


def test_what_the_caches_cannot_do_is_refused_by_name(model):
    session = DecodeSession(model, max_slots=2, prefix_cache=object(),
                            spec_draft=object())
    assert session.prefix_cache is None and session._spec_draft is None
    with pytest.raises(AdmissionRefused) as e:
        session.submit(BeamRequest([3, 4], beam_size=2))
    assert e.value.reason == "beam_unsupported"
    with pytest.raises(AdmissionRefused) as e:
        session.submit(DecodeRequest(prompt(120, 1), max_new_tokens=40))
    assert e.value.reason == "too_long"
    ids = model.allocator.alloc(model.context_pages([3] * 12, 0))
    try:
        with pytest.raises(UnsupportedOverState, match="cached"):
            model.prefill([3] * 12, ids, cached_len=8)
    finally:
        model.allocator.free(ids)
    with pytest.raises(UnsupportedOverState, match="fork"):
        model.copy_page(1, 2)
    with pytest.raises(UnsupportedOverState, match="verify"):
        model.verify_chunk(np.zeros((2, 3), np.int64), [], None, None)
    # a chunk of rows a sequence reaches no layer's mixer either
    with pytest.raises(UnsupportedOverState, match="chunk of rows"):
        model.block.layer(1).mixer(None, jnp.zeros((2, 3, 32)), None, (),
                                   1, None, 4)
    assert not (model.supports_prefix_cache or model.supports_fork
                or model.supports_verify)


# -- scopes and counters ------------------------------------------------------


def _lowered(m, slots=4, bucket=64):
    cache = m._cache()

    def text(program, *args, **kw):
        return program.trace(*args, **kw).lower().as_text(debug_info=True)

    return {
        "_decode_step": text(
            dm._decode_step, m.params, *cache[:2],
            np.zeros((slots, m.pages_per_seq), np.int32),
            np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
            heads=m.heads, page_size=m.page_size, block=m.block,
            extra=cache[2:]),
        "_prefill_bucket": text(
            dm._prefill_bucket, m.params, *cache[:2],
            np.zeros((bucket,), np.int32),
            (np.zeros((m.rings + 1, bucket), np.int32), np.int32(0)),
            np.int32(3), heads=m.heads, block=m.block, extra=cache[2:])}


def test_named_scopes_place_the_layers(model):
    text = _lowered(model)
    for scope in ("ssm/", "ssm/ssm_state/", "ssm/ssm_conv/", "attn_window/",
                  "attn_shared/", "gmu/"):
        assert scope in text["_decode_step"], scope
    for scope in ("ssm/", "ssm/ssm_scan/", "ssm/ssm_conv/", "attn_window/",
                  "attn_shared/", "gmu/"):
        assert scope in text["_prefill_bucket"], scope
    assert "ssm_scan/" not in text["_decode_step"]
    assert "ssm_state/" not in text["_prefill_bucket"]


def test_the_counters_tell_the_two_decoders_rows_and_the_runs_reads(
        monkeypatch):
    """The cross-decoder's rows are the shape the layers below the run's
    owner were handed as the bucket was traced: one where the prefill
    stops half-way down; every bucket row, and the counter says so,
    where the program is never told the last row."""
    rows = metrics.REGISTRY.get("decode_prefill_rows_total")
    reads = metrics.REGISTRY.get("decode_shared_run_reads_total")

    def counted():
        return rows.value(part="self"), rows.value(part="cross"), \
            reads.value()

    before = counted()
    m = make()
    assert m.shared_readers == 2            # the owner and one cross layer
    through_the_cache(m, prompt(21, 5), prompt(3, 6))
    # the bucket's rows, one row below the owner, 3 steps x 2 readers
    assert np.subtract(counted(), before).tolist() == [64, 1, 3 * 2]
    whole = dm._dense_blocks
    monkeypatch.setattr(
        dm, "_dense_blocks",
        lambda block, params, tokens, heads, live, last=None:
        whole(block, params, tokens, heads, live))
    monkeypatch.setattr(pf, "_CROSS_ROWS", {})
    before = counted()
    dm._prefill_bucket.clear_cache()
    try:
        through_the_cache(make(), prompt(21, 5), prompt(1, 6))
    finally:
        dm._prefill_bucket.clear_cache()
    assert np.subtract(counted(), before).tolist() == [64, 64, 1 * 2]
