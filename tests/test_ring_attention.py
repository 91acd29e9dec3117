"""A window layer's rings read where they lie: the grouped chunk kernel
under a window mask (``decode/attention.py:ring_paged_attention``,
interpreted on the CPU) against ``ring_window_attention`` on a gathered
copy of every ring, its oracle; the guard that skips a column against
the oracle's own mask; and the dispatcher's rule."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode import attention as A
from paddle_tpu.observability import metrics

# (window, page, ring pages): K-EXAONE's, Phi-4-mini-flash's, and a page
# that is no whole window's divisor's twin (four pages a window of five)
GEOMETRIES = [(128, 128, 2), (512, 128, 5), (256, 64, 5)]
HKV, D = 2, 16
POISON = 1          # a page id no ring column may read as live


def edges(window, page, R):
    """Positions at every edge, mixed across one call's slots: the first
    row; a ring not yet full, on a page's first and last row; the last
    row before the window is whole and the first after; every residue of
    the newest page's ring column after two wraps, at a page's first row,
    its last and one inside."""
    pos = [0, 1, page - 1, page, window - 1, window, window + page - 1]
    for i in range(R):
        at = (2 * R + i) * page
        pos += [at, at + page // 2 + i, at + page - 1]
    return pos


def pools(rng, S, R, page, heads_major, dtype=jnp.float32):
    """Two pools of S rings' pages beside a poison page and a null one,
    and the rings' tables (S, R) over distinct pages in no order."""
    N = S * R + 2
    shape = (N, HKV, page, D) if heads_major else (N, page, HKV, D)
    k = rng.randn(*shape).astype(np.float32)
    v = rng.randn(*shape).astype(np.float32)
    tables = rng.permutation(np.arange(2, N)).reshape(S, R).astype(np.int32)
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype), tables


def oracle(q, k, v, tables, lens, window, page, heads_major):
    k_ring, v_ring = k[tables], v[tables]
    if heads_major:
        k_ring, v_ring = jnp.swapaxes(k_ring, 2, 3), jnp.swapaxes(v_ring, 2, 3)
    T = q.shape[1]
    pos = jnp.asarray(lens)[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    return A.ring_window_attention(q, k_ring, v_ring, pos, window, page)


def unseen_columns(lens, T, window, page, R):
    """(S, R) bool from the ORACLE's arithmetic, written out again here:
    a column none of the chunk's rows sees a key of."""
    pos = np.asarray(lens)[:, None] + np.arange(T)[None]          # (S, T)
    r = np.arange(R)[None, None, :, None]
    lane = np.arange(page)[None, None, None, :]
    newest = (pos // page)[:, :, None, None]
    k_pos = (newest - (newest - r) % R) * page + lane          # (S, T, R, pg)
    back = pos[:, :, None, None] - k_pos
    seen = (k_pos >= 0) & (back >= 0) & (back < window)
    return ~seen.any(axis=(1, 3))


@pytest.mark.parametrize("geometry", GEOMETRIES,
                         ids=lambda g: "w%d-pg%d-r%d" % g)
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("heads_major", [False, True],
                         ids=["row-major", "heads-major"])
def test_ring_kernel_is_the_gathered_reference_at_every_edge(
        heads_major, G, geometry):
    """A decode step's row a slot.  Every column no row sees (the ring
    has not reached it; it holds the oldest page and the window ends on
    that page's edge) names a page of NaNs in the kernel's table: read
    as live, or read at all and multiplied by a weight of zero, it would
    show."""
    window, page, R = geometry
    rng = np.random.RandomState(window + page + R + G)
    lens = np.asarray(edges(window, page, R), np.int32)
    S = len(lens)
    q = jnp.asarray(rng.randn(S, 1, HKV * G, D), jnp.float32)
    k, v, tables = pools(rng, S, R, page, heads_major)
    want = oracle(q, k, v, tables, lens, window, page, heads_major)
    unseen = unseen_columns(lens, 1, window, page, R)
    assert unseen[0].sum() == R - 1 and unseen.any(axis=1).sum() > R
    k, v = k.at[POISON].set(jnp.nan), v.at[POISON].set(jnp.nan)
    got = A.ring_paged_attention(
        q, k, v, np.where(unseen, POISON, tables), lens, window,
        interpret=True, heads_major=heads_major)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("geometry", GEOMETRIES + [(8, 8, 2)],
                         ids=lambda g: "w%d-pg%d-r%d" % g)
@pytest.mark.parametrize("T", [2, 5, 8])
@pytest.mark.parametrize("heads_major", [False, True],
                         ids=["row-major", "heads-major"])
def test_ring_kernel_takes_a_chunk_of_rows_across_a_pages_edge(
        heads_major, T, geometry):
    """A chunk of up to a page's rows a slot (a suffix prefill, the
    verify): each row's mask is reckoned from its own position, so a
    column that a later row of the chunk has begun to overwrite still
    reads as the old page for an earlier one."""
    window, page, R = geometry
    rng = np.random.RandomState(window + page + R + T)
    lens = np.asarray(
        [0, page - 1, page - T + 1, window - 2, 2 * R * page - 1,
         (2 * R + 1) * page - T // 2, (2 * R + 2) * page - T,
         (3 * R - 1) * page + page // 2], np.int32)
    S = len(lens)
    q = jnp.asarray(rng.randn(S, T, HKV * 4, D), jnp.float32)
    k, v, tables = pools(rng, S, R, page, heads_major)
    want = oracle(q, k, v, tables, lens, window, page, heads_major)
    unseen = unseen_columns(lens, T, window, page, R)
    k, v = k.at[POISON].set(jnp.nan), v.at[POISON].set(jnp.nan)
    got = A.ring_paged_attention(
        q, k, v, np.where(unseen, POISON, tables), lens, window,
        interpret=True, heads_major=heads_major)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("geometry", GEOMETRIES + [(8, 4, 3), (8, 8, 2)],
                         ids=lambda g: "w%d-pg%d-r%d" % g)
@pytest.mark.parametrize("T", [1, 2, "page"])
def test_the_skipped_columns_are_exactly_those_no_row_sees(T, geometry):
    """``ring_column_seen`` (the kernel's page guard and its index
    map's) at every position of three times round the ring, against the
    oracle's mask reduced over a chunk's rows and a page's lanes."""
    window, page, R = geometry
    T = page if T == "page" else T
    lens = np.arange(3 * R * page + 2, dtype=np.int32)
    got = np.stack([np.asarray(A.ring_column_seen(lens, T, r, page, R,
                                                  window))
                    for r in range(R)], axis=1)
    want = ~unseen_columns(lens, T, window, page, R)
    np.testing.assert_array_equal(got, want)
    # a full ring's step skips a column only where the window ends on a
    # page's edge, and never two
    if T == 1:
        full = lens >= R * page
        skipped = (~got[full]).sum(axis=1)
        assert skipped.max() <= 1
        ends = (lens[full] + 1 - window) % page == 0
        np.testing.assert_array_equal(skipped == 1, ends)


def test_ring_kernel_reads_bf16_pages_at_the_width_they_are_stored_in():
    """bfloat16 pages and queries, as served: read at the width they
    are stored in and widened in VMEM; the scores, the max, the
    normaliser and the accumulator are float32, the output bfloat16."""
    window, page, R = 512, 128, 5
    rng = np.random.RandomState(7)
    lens = np.asarray(edges(window, page, R)[::3], np.int32)
    S = len(lens)
    q = jnp.asarray(rng.randn(S, 1, HKV * 4, D), jnp.bfloat16)
    k, v, tables = pools(rng, S, R, page, True, jnp.bfloat16)
    want = oracle(q, k, v, tables, lens, window, page, True)
    got = A.ring_paged_attention(q, k, v, tables, lens, window,
                                 interpret=True, heads_major=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("heads_major, mode, path", [
    (True, "on", "interpret"), (False, "on", "reference"),
    (True, "off", "reference"), (True, "auto", "reference")])
def test_the_dispatcher_takes_the_kernel_for_heads_major_pages_alone(
        heads_major, mode, path):
    """The rule is the layout the caller states for its pool: no
    threshold, no flag.  Row-major pages, the kernels off, and ``auto``
    off a TPU all take the gathered reference; a chunk of more rows
    than a page does too, whatever the layout."""
    window, page, R = 16, 8, 3
    rng = np.random.RandomState(3)
    lens = np.asarray([0, 7, 15, 16, 40, 47, 55], np.int32)
    S = len(lens)
    q = jnp.asarray(rng.randn(S, 1, HKV * 2, D), jnp.float32)
    k, v, tables = pools(rng, S, R, page, heads_major)
    want = oracle(q, k, v, tables, lens, window, page, heads_major)
    counter = metrics.REGISTRY.get("pallas_dispatch_total")

    def counted():
        return {p: counter.value(kernel="ring_paged_attention", path=p)
                for p in ("compiled", "interpret", "reference")}

    before = counted()
    pk.enable(mode, interpret=(mode == "on"))
    try:
        got = A.paged_ring_attention(q, k, v, tables, lens[:, None], window,
                                     heads_major=heads_major)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        after = counted()
        assert {p: after[p] - before[p] for p in after} == {
            **dict.fromkeys(before, 0), path: 1}
        wide = jnp.asarray(rng.randn(S, page + 1, HKV * 2, D), jnp.float32)
        pos = lens[:, None] + np.arange(page + 1, dtype=np.int32)[None]
        A.paged_ring_attention(wide, k, v, tables, pos, window,
                               heads_major=heads_major)
        assert counted()["reference"] == after["reference"] + 1
    finally:
        pk.enable("auto", interpret=False)


def test_without_a_window_the_chunk_kernel_is_the_one_it_was(monkeypatch):
    """Every other generate cell runs the chunk kernel with no window:
    traced so, it calls none of the ring's arithmetic (kernel body and
    index maps alike) and keeps its names; with one, it calls all three
    helpers and carries the ring's name alone."""
    S, T, Hq, page, P = 2, 2, 8, 8, 3
    args = (jnp.zeros((S, T, Hq, D)), jnp.zeros((7, page, HKV, D)),
            jnp.zeros((7, page, HKV, D)), jnp.zeros((S, P), jnp.int32),
            jnp.zeros((S,), jnp.int32))
    called = set()
    for name in ("_ring_held", "ring_column_seen", "_ring_seen"):
        def spy(*a, _name=name, _fn=getattr(A, name), **kw):
            called.add(_name)
            return _fn(*a, **kw)
        monkeypatch.setattr(A, name, spy)
    jax.clear_caches()
    try:
        plain = str(jax.make_jaxpr(
            lambda *a: A.ragged_paged_attention_gqa(*a, interpret=True))(
                *args))
        chunk = str(jax.make_jaxpr(
            lambda *a: A.ragged_paged_attention_chunk(*a, interpret=True))(
                args[0], *(jnp.zeros((7, page, Hq, D)),) * 2, *args[3:]))
        assert not called
        assert "name=ragged_paged_attention_gqa" in plain
        assert "name=ragged_paged_attention_chunk" in chunk
        assert "ring_paged_attention" not in plain + chunk
        ring = str(jax.make_jaxpr(
            lambda *a: A.ring_paged_attention(*a, 16, interpret=True))(*args))
        assert called == {"_ring_held", "ring_column_seen", "_ring_seen"}
        assert "name=ring_paged_attention" in ring
        assert "ragged_paged_attention" not in ring
    finally:
        jax.clear_caches()


# ---------------------------------------------------------------------------
# the page run's walk: one grid step a slot, its live pages alone
# ---------------------------------------------------------------------------

WALK_PAGE, WALK_COLUMNS = 8, 11      # a table of three turns less a page


def walk_lens(T):
    """Rows cached before a chunk of ``T``, by where the chunk's LAST
    row lies: on a page's first row and on its last (in a second turn's
    first page, the end of a whole turn, a third turn's only page), in
    one page, on the table's last row, and nowhere (an empty seat: no
    row live) as the call's first slot, two together in its middle and
    its last."""
    page, P = WALK_PAGE, WALK_COLUMNS
    ends = {"first_row": 4 * page, "last_row": 4 * page - 1,
            "third_turn": 8 * page, "one_page": T - 1 + 2,
            "whole_table": P * page - 1, "empty": -1}
    order = ["empty", "first_row", "last_row", "empty", "empty", "one_page",
             "third_turn", "whole_table", "empty"]
    return order, np.asarray([ends[o] - (T - 1) for o in order], np.int32)


def walk_pools(rng, lens, T, heads_major, poison=False):
    """Both pools, and tables whose live columns name distinct pages in
    no order and whose dead ones name page 0; ``poison``: page 0 and
    every other page no slot owns hold NaN."""
    page, P = WALK_PAGE, WALK_COLUMNS
    S = len(lens)
    live = np.clip(-(-(lens + T) // page), 0, P)
    N = int(live.sum()) + 3
    shape = (N, HKV, page, D) if heads_major else (N, page, HKV, D)
    k, v = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    ids = rng.permutation(np.arange(1, N))
    tables = np.zeros((S, P), np.int32)
    at = 0
    for s in range(S):
        tables[s, :live[s]] = ids[at:at + live[s]]
        at += live[s]
    if poison:
        k[0], v[0] = np.nan, np.nan
        k[ids[at:]], v[ids[at:]] = np.nan, np.nan
    return jnp.asarray(k), jnp.asarray(v), tables


def walk(q, k, v, tables, lens, heads_major):
    """The kernel and its reference, by the public names a caller's
    shapes lead to: plain heads on row-major pages are the chunk
    kernel's, everything else the grouped one's."""
    if q.shape[2] == HKV and not heads_major:
        return (A.ragged_paged_attention_chunk(q, k, v, tables, lens,
                                               interpret=True),
                A.ragged_paged_attention_chunk_reference(q, k, v, tables,
                                                         lens))
    return (A.ragged_paged_attention_gqa(q, k, v, tables, lens,
                                         interpret=True,
                                         heads_major=heads_major),
            A.ragged_paged_attention_gqa_reference(q, k, v, tables, lens,
                                                   heads_major=heads_major))


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("G", [1, 2, 4])
@pytest.mark.parametrize("heads_major", [False, True],
                         ids=["row-major", "heads-major"])
def test_the_walk_is_the_reference_wherever_a_run_ends(heads_major, G, T):
    """The walk against the gathered reference with slots whose runs end
    at every edge of a page and of a turn in ONE call (each slot's first
    copies are started by the slot before it, an empty seat's too).  A
    slot with no live row writes zeros, where the reference's softmax of
    nothing is the mean of every row."""
    rng = np.random.RandomState(58 + 7 * G + T)
    order, lens = walk_lens(T)
    q = jnp.asarray(rng.randn(len(lens), T, HKV * G, D), jnp.float32)
    k, v, tables = walk_pools(rng, lens, T, heads_major)
    got, want = walk(q, k, v, tables, lens, heads_major)
    empty = np.asarray([o == "empty" for o in order])
    np.testing.assert_allclose(got[~empty], want[~empty], rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(got[empty]).any() and empty.sum() == 4


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("heads_major", [False, True],
                         ids=["row-major", "heads-major"])
def test_the_walk_reads_no_page_past_a_slots_run(heads_major, T):
    """Every page no slot owns is NaN, the null page that the tables'
    dead columns name among them: read at all, as a copy that is then
    masked and multiplied by a weight of zero, it would show."""
    rng = np.random.RandomState(T)
    order, lens = walk_lens(T)
    q = jnp.asarray(rng.randn(len(lens), T, HKV * 2, D), jnp.float32)
    k, v, tables = walk_pools(rng, lens, T, heads_major, poison=True)
    assert np.isnan(np.asarray(k)).any() and (tables == 0).sum() > 40
    got, _ = walk(q, k, v, tables, lens, heads_major)
    assert np.isfinite(np.asarray(got)).all()
    clean = jnp.nan_to_num(k), jnp.nan_to_num(v)
    np.testing.assert_array_equal(
        got, walk(q, *clean, tables, lens, heads_major)[0])


@pytest.mark.parametrize("pages", [1, 3, 4, 5, WALK_COLUMNS])
def test_the_walk_takes_a_turn_of_any_width(pages, monkeypatch):
    """``WALK_PAGES`` is a constant of the module: at any width of a
    turn (one page; more than divide the table's columns; the whole
    table) the walk reads the same pages."""
    monkeypatch.setattr(A, "WALK_PAGES", pages)
    jax.clear_caches()
    try:
        rng = np.random.RandomState(pages)
        order, lens = walk_lens(1)
        q = jnp.asarray(rng.randn(len(lens), 1, HKV * 2, D), jnp.float32)
        k, v, tables = walk_pools(rng, lens, 1, True, poison=True)
        got, _ = walk(q, k, v, tables, lens, True)
        want = A.ragged_paged_attention_gqa_reference(
            q, jnp.nan_to_num(k), jnp.nan_to_num(v), tables, lens,
            heads_major=True)
        live = lens >= 0
        np.testing.assert_allclose(got[live], want[live], rtol=2e-5,
                                   atol=2e-5)
    finally:
        jax.clear_caches()


def test_the_walk_reads_bf16_pages_at_the_width_they_are_stored_in():
    """As served: bfloat16 queries and pages of 128 lanes, a decode
    step's row of four query heads a K/V head, float32 inside."""
    rng = np.random.RandomState(11)
    _, lens = walk_lens(1)
    S, page, P, dh = len(lens), WALK_PAGE, WALK_COLUMNS, 128
    q = jnp.asarray(rng.randn(S, 1, HKV * 4, dh), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(S * P + 1, HKV, page, dh), jnp.bfloat16)
            for _ in range(2))
    tables = (rng.permutation(S * P) + 1).reshape(S, P).astype(np.int32)
    lens = np.maximum(lens, 0)
    got = A.ragged_paged_attention_gqa(q, k, v, tables, lens, interpret=True,
                                       heads_major=True)
    want = A.ragged_paged_attention_gqa_reference(q, k, v, tables, lens,
                                                  heads_major=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=2e-2, atol=2e-2)


def test_the_walk_is_one_grid_step_a_slot_and_the_ring_a_column():
    """The static ``window`` alone says which body a call traces: a page
    run's is the walk (a slot a grid step, the pools left where they
    lie), a ring's the (slot, column) grid."""
    S, T, Hq, page, P = 3, 2, 8, 8, 5
    args = (jnp.zeros((S, T, Hq, D)), jnp.zeros((7, page, HKV, D)),
            jnp.zeros((7, page, HKV, D)), jnp.zeros((S, P), jnp.int32),
            jnp.zeros((S,), jnp.int32))
    run = str(jax.make_jaxpr(
        lambda *a: A.ragged_paged_attention_gqa(*a, interpret=True))(*args))
    ring = str(jax.make_jaxpr(
        lambda *a: A.ring_paged_attention(*a, 16, interpret=True))(*args))
    assert f"grid=({S},)" in run and f"grid=({S}, {P})" not in run
    assert f"grid=({S}, {P})" in ring
    assert "dma_start" in run and "dma_start" not in ring


@pytest.mark.parametrize("shape, takes", [
    # (dtype, page rows, stored heads, lanes, heads_major)
    (("bfloat16", 128, 10, 128, True), True),       # Phi-4-mini-flash
    (("bfloat16", 128, 4, 128, False), True),       # Granite, two a row
    (("bfloat16", 128, 8, 128, False), True),       # K-EXAONE
    (("bfloat16", 32, 16, 128, False), True),       # OLMoE's suffix chunk
    (("float32", 32, 16, 128, False), True),        # Cerebras' verify chunk
    (("bfloat16", 128, 8, 64, False), False),       # a row of 64 lanes
    (("bfloat16", 128, 10, 128, False), False),     # ten heads in the rows
    (("bfloat16", 128, 30, 128, False), False),
    (("float32", 8, 4, 8, False), False),           # a toy model's pages
    (("float32", 128, 8, 256, False), False),       # 16 MiB of buffers
    (("bfloat16", 128, 1, 128, False), False),      # half a sublane
    (("float32", 128, 1, 128, False), True),
], ids=lambda x: "-".join(map(str, x)) if isinstance(x, tuple) else None)
def test_what_the_compiled_walk_takes(shape, takes):
    """``walk_fits``' table (PR 58's probe of the described chip: every
    ``False`` row is a shape Mosaic refused the page copy of, or one
    whose buffers pass a kernel's VMEM)."""
    assert A.walk_fits(*shape) is takes


@pytest.mark.parametrize("interpreted, path", [(True, "interpret"),
                                               (False, "reference")])
def test_a_toy_page_run_walks_interpreted_and_is_gathered_compiled(
        interpreted, path):
    """A toy model's pages (16 lanes) fit no tile: interpreted the
    dispatchers still take the walk, as every test of a toy model wants;
    where the kernel would be compiled they take the reference."""
    rng = np.random.RandomState(5)
    _, lens = walk_lens(1)
    lens = np.maximum(lens, 0)
    counter = metrics.REGISTRY.get("pallas_dispatch_total")

    def counted():
        return {(kern, p): counter.value(kernel=kern, path=p)
                for kern in ("ragged_paged_attention_gqa",
                             "ragged_paged_attention_chunk")
                for p in ("compiled", "interpret", "reference")}

    pk.enable(True, interpret=interpreted)
    try:
        before = counted()
        for G, kern in ((2, "ragged_paged_attention_gqa"),
                        (1, "ragged_paged_attention_chunk")):
            q = jnp.asarray(rng.randn(len(lens), 2, HKV * G, D), jnp.float32)
            k, v, tables = walk_pools(rng, lens, 2, False)
            got = A.paged_chunk_attention(q, k, v, tables, lens)
            np.testing.assert_allclose(
                got, A.ragged_paged_attention_gqa_reference(
                    q, k, v, tables, lens), rtol=2e-5, atol=2e-5)
            after = counted()
            assert after[kern, path] - before[kern, path] == 1
    finally:
        pk.enable("auto", interpret=False)
