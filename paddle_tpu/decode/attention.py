"""Ragged paged-attention decode kernels + dense prefill path.

Decode shape (the "Ragged Paged Attention" design, PAPERS.md): each
active sequence contributes ONE query token per step, but its context
lives scattered across fixed-size KV pages named by a per-sequence page
table.  The table and the lengths are *scalar-prefetched* into SMEM and
the kernel picks each page straight from the table — the gather never
materializes a per-sequence contiguous copy — and pages wholly past the
sequence length are skipped (their FLOPs AND their DMA do not happen,
same trick as the causal-block skip in ``pallas/flash_attention.py``).
Softmax is the same online (running max / normalizer) accumulation as
the flash forward, in f32 VMEM scratch.  What a page's arithmetic is
follows from the pool's dtype and layout and the call's rows alone
(``page_form``): one row multiply-reduces on the VPU, several rows on
row-major bfloat16 pages feed the MXU the page as it is stored, the rest
widen it to float32 first.

Every call over a page run (a decode step's row a slot, on grouped
heads or not; a verify chunk's or a prefix suffix's T rows) takes ONE
grid step a slot and walks the slot's live pages alone, copying them
itself out of the pools left in HBM (``_rpa_walk_kernel``: PR 58 the
chunk and grouped calls, PR 60 the step's row on ungrouped heads): a
table column past a slot's length costs a grid step of a ``(slots,
pages_per_seq)`` grid even where it costs no read, 0.2-0.3 us, and at
17 live columns of 64 those steps were a third of the call.  Two calls
keep a grid step a column: the step's row on pages the compiled walk
does not take (``walk_fits``: 1 MB pages, heads under 128 lanes;
``_rpa_kernel``, the first kernel of this file), and a window layer's
ring, five columns nearly always all seen (``_rpa_chunk_kernel``).

Prefill stays dense: a prompt is contiguous, so the existing flash
attention forward (``pallas/flash_attention.py``) — or its jnp fallback
at small shapes — handles it, and the resulting K/V rows are written
into pages once.

Everything runs under ``interpret=True`` on CPU for numerics tests; the
jnp reference (``ragged_paged_attention_reference``) is both the test
oracle and what dispatch picks off-TPU or at shapes ``fits()`` rejects.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_F32 = jnp.float32
_NEG_INF = -1e30  # matches flash_attention: finite, avoids inf-inf NaN


def fits(page_size: int, num_heads: int, head_dim: int,
         kv_heads: int = None) -> bool:
    """Shapes the kernels' block layouts support.  ``kv_heads`` (None:
    ``num_heads``) is the K/V heads a page holds; where it is fewer
    than the query heads (grouped heads) it has to divide them, and the
    blocks are the K/V heads' wide.

    What it does not look at is how many heads a page holds.  At 30
    heads of 128 over 128-row bfloat16 pages (PR 39's probe on a v5e)
    ``ragged_paged_attention`` and the flash prefill compile and run
    right ALONE; inside a step the compiler lays a donated pool of 30
    heads out with the heads outside the page's rows (30 is not a
    multiple of the 16 rows a bfloat16 tile packs) and copies the whole
    pool to the kernel's row-major layout before every layer's call.
    A model whose head count is not a multiple of the tile's rows
    stores its pages at ``storage_heads`` and pads q, k and v to it.

    Nor at how wide a head is.  Pages of 8 K/V heads of 64 (PR 41's
    probe, ``tests/test_chip_compile.py``): a row of 64 lanes is padded
    to the tile's 128 (a pool twice its bytes) and the pools are copied
    to that layout around the kernel's calls (3.6 GB of temporaries
    beside 1 GB of K/V).  A model with heads of 64 stores two of them
    side by side in a row of 128 lanes and runs the grouped kernel on
    that (``models/granite_hybrid.py:heads_a_row``): read and written
    in place, nothing padded.

    Nor does a page have to keep its heads inside its rows.  Ten
    stored heads of 128 (PR 48's probe: five K/V pairs of 64-wide heads
    twice over): 4, 8 or a multiple of 16 heads are whole tiles of a
    bfloat16 page's (rows, heads), ten are not, the compiler lays the
    donated pool out with the heads outermost as at 30, and a decode
    step of eight reading layers planned 5.6 GB of copies of a 2.1 GB
    pool; sixteen stored heads would store and read 1.6 times the
    bytes.  A page stored ``(heads, rows, 128)`` is whole tiles
    whatever the head count, and it is the shape the grouped kernel's
    two batched dots take as it lies: ``heads_major``
    (``models/phi4_flash.py``).

    K and V need not be one width.  A model whose keys are wider than
    its values (``models/mimo_v2.py``: keys of 192, values of 128) hands
    the chunk kernels K pages and V pages of their own last dimensions:
    each pool's buffers are its own pages', and the accumulator and the
    output are the values' wide (PR 62).  A key of 192 lanes is no whole
    tile, though: stored as published the compiled walk cannot copy its
    page (``walk_fits``), the step falls to the gathered reference and
    the compiler re-lays the whole K pool out for the gather (19.9 GB
    asked of the chip's 15.75: ``tests/test_chip_compile_mimo.py``); so
    that model stores a key at 256 lanes, zeros behind the 192, pads q
    to match and passes the scores' scale itself.

    Nor do these kernels take a latent layer's pages: one row a token
    whose 576 numbers are the key and whose first 512 are the value, K
    and V from one buffer at ``D > 256``.  That is
    ``pallas/latent_attention.py`` (PR 45), and its layout probe found
    the same as the two above: a pool of 576 lanes is laid out at 640
    and the kernel's page copy refused, so the rows are stored at 640
    (``models/kanana_mla.py:row_width``).

    The walk over a page run (PR 58: the chunk and grouped calls; PR 60:
    the decode step's row on ungrouped heads) keeps in VMEM, beside the
    chunk's q/o blocks and float32 accumulator, two double buffers of
    ``WALK_PAGES`` pages, K's and V's: 16 pages at 4 a turn.  Ten stored
    heads of 128 over 128-row bfloat16 pages (Phi-4-mini-flash: 327,680
    B a page) are 5.24 MB; four stored heads (Granite: 131,072 B) 2.10
    MB; eight (K-EXAONE: 262,144 B) 4.19 MB; sixteen heads over 32-row
    pages bfloat16 (OLMoE: 131,072 B) 2.10 MB and float32 (Cerebras:
    262,144 B) 4.19 MB, of the 16 MiB a kernel may hold; the float32
    copies of the one page being computed on come to 1.3 MB more at the
    Phi shape.  Compiled, it also asks pages that are whole tiles where
    they lie in HBM, which the layouts above are and the ones they
    replaced are not: ``walk_fits``.  The Olmo-Hybrid's 32 stored heads
    over 128-row pages (1 MB a page, 16.8 MB of buffers) it refuses:
    that step's row is the one compiled caller the ``(S, P)`` grid of
    ``ragged_paged_attention`` has left."""
    ok = (page_size % 8 == 0 and head_dim % 8 == 0
          and head_dim <= 256 and num_heads >= 1)
    if kv_heads in (None, num_heads):
        return ok
    return ok and kv_heads >= 1 and num_heads % kv_heads == 0


def storage_heads(num_heads: int, dtype) -> int:
    """The head count a page of ``dtype`` is stored at: ``num_heads``
    rounded up to the rows one (rows, 128) tile of the dtype packs (8
    of 4 bytes, 16 of 2), so that the pool's row-major layout has no
    padding and the compiler keeps it (``fits``)."""
    rows = 32 // jnp.dtype(dtype).itemsize
    return -(-int(num_heads) // rows) * rows


WALK_PAGES = 4              # pages a turn of the walk copies and computes
WALK_BUFFER_BYTES = 10 << 20    # of the 16 MiB a kernel may hold in VMEM


def walk_fits(dtype, page_size: int, kv_heads: int, head_dim: int,
              heads_major: bool = False) -> bool:
    """What the COMPILED walk over a page run (``_rpa_walk_kernel``)
    asks beyond ``fits``: it copies a page out of a pool left in HBM,
    and Mosaic takes that slice only of pages that are whole tiles where
    they lie (PR 58's probe on a described v5e; the ``(S, P)`` grid took
    every shape below through a BlockSpec, with the compiler's copies of
    the pool round it that ``fits`` tells of).  ``head_dim``: the lanes
    a K page's row is STORED at (the wider pool's, where V is narrower:
    its pages are then the smaller and fit whenever K's do).  Rows of
    whole 128-lane tiles (64, 192: refused; a model with keys of 192
    stores them at 256); a page's second-minor extent (its heads,
    or its rows ``heads_major``) a multiple of 8 or a power of two that
    fills a 4-byte sublane (10 or 30 bfloat16 heads inside a page's
    rows: refused; heads-major: taken); and the two double buffers,
    ``4 * WALK_PAGES`` pages, within ``WALK_BUFFER_BYTES``.  Interpreted,
    every shape ``fits`` takes runs."""
    itemsize = jnp.dtype(dtype).itemsize
    rows = page_size if heads_major else kv_heads
    whole = rows % 8 == 0 or (rows & (rows - 1) == 0 and rows * itemsize >= 4)
    page_bytes = page_size * kv_heads * head_dim * itemsize
    return (head_dim % 128 == 0 and whole
            and 4 * WALK_PAGES * page_bytes <= WALK_BUFFER_BYTES)


# ---------------------------------------------------------------------------
# reference (jnp): the oracle + off-TPU fallback
# ---------------------------------------------------------------------------


def ragged_paged_attention_reference(q, k_pages, v_pages, page_tables,
                                     lens, scale=None):
    """q (S, H, D); k/v_pages (N, page, H, D); page_tables (S, P) int;
    lens (S,) valid KV rows per slot -> out (S, H, D).

    Pure jnp, fixed shape: the gather is a fancy-index over the pool,
    the mask zeroes positions at or past each slot's length.
    """
    S, H, D = q.shape
    page = k_pages.shape[1]
    P = page_tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    k = k_pages[page_tables].reshape(S, P * page, H, D).astype(_F32)
    v = v_pages[page_tables].reshape(S, P * page, H, D).astype(_F32)
    s = jnp.einsum("shd,sthd->sht", q.astype(_F32), k) * scale
    t = jnp.arange(P * page)
    mask = t[None, :] < lens.reshape(-1, 1)
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,sthd->shd", p, v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _page_update(q, k, v, m_prev, l_prev, acc_prev, t0, seq_len, scale):
    """One page's online-softmax update for ONE query row per head.

    q (H, D); k, v (page, H, D); m/l (H, 1); acc (H, D); ``t0`` is the
    page's first position.  With a single query token there is no free
    (row) dimension for an MXU matmul — Mosaic rejects a batched
    ``dot_general`` whose left operand has only a batch and a
    contracting dim — so the scores and the ``pr . v`` product are VPU
    multiply-reduces: over the lane dim D for the scores, over the
    leading page dim for the accumulator.  The padded-row MXU form
    (``_softmax_page`` on 8 sublanes, both pages turned in VMEM) read
    slower under the walk at 32-row pages of 16 heads and rounds its
    operands to bfloat16 (PERF.md §6, PR 60).  On float32 pages this
    arithmetic hides under the page's copy but for a fifth; on bfloat16
    pages it is the bound: 0.57 us a page whose copy is 0.32.

    Which pool gets this body: every pool whose call is ONE row a K/V
    head on row-major pages (``page_form``'s ``"row"``: the decode step
    on ungrouped heads, float32 or bfloat16).  Several rows a K/V head
    (grouped heads, a chunk) are a free dimension, and take the MXU
    (``_softmax_page``, on the pages as they are stored where they are
    row-major bfloat16, widened to float32 elsewhere).
    """
    q = q.astype(_F32)
    k = k.astype(_F32)
    v = v.astype(_F32)
    sc = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale  # (page, H, 1)
    t_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    sc = jnp.where(t_pos < seq_len, sc, _NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))            # (H, 1)
    pr = jnp.exp(sc - m_new[None])                              # (page, H, 1)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(pr, axis=0)
    acc_new = acc_prev * corr + jnp.sum(pr * v, axis=0)         # (H, D)
    return m_new, l_new, acc_new


def _row_page(q, k, v, m_scr, l_scr, acc_scr, col, page, seq_len, scale):
    """``_page_update`` of table column ``col``'s page on the running
    max, normaliser and accumulator in VMEM scratch."""
    m_new, l_new, acc_new = _page_update(
        q, k, v, m_scr[...], l_scr[...], acc_scr[...], col * page, seq_len,
        scale)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new


def _rpa_kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, page, npp):
    """One (slot, page) grid step: accumulate this page's contribution
    to the slot's online softmax."""
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages wholly past the length contribute nothing: skip their math
    # (the BlockSpec still names a page — the null page for table
    # padding — but the guarded body never reads it)
    seq_len = lens_ref[s]

    @pl.when(p * page < seq_len)
    def _page():
        _row_page(q_ref[0], k_ref[0], v_ref[0], m_scr, l_scr, acc_scr,
                  p, page, seq_len, scale)

    @pl.when(p == npp - 1)
    def _finish():
        o_ref[0] = _softmax_out(l_scr, acc_scr).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_paged_attention(q, k_pages, v_pages, page_tables, lens,
                           scale=None, interpret: bool = False):
    """Pallas ragged paged-attention decode step on a ``(slots,
    pages_per_seq)`` grid.

    Same contract as the reference: q (S, H, D), pools (N, page, H, D),
    page_tables (S, P), lens (S,) -> (S, H, D).  One slot a grid step,
    the slot dimension ``parallel``: sweeping 2, 4 or 8 slots' pages
    under one resident q/o block, and ``arbitrary`` for it, read the
    same or slower at the cells' shapes (PERF.md §6, PR 44).  A column
    past a slot's length is a grid step all the same:
    ``ragged_paged_attention_walk`` is the same contract without them,
    and ``paged_attention`` sends it every pool ``walk_fits`` takes
    (PR 60); compiled, this grid is left the pools it refuses (the
    Olmo-Hybrid's 1 MB pages, heads under 128 lanes).
    """
    S, H, D = q.shape
    page = k_pages.shape[1]
    P = page_tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    ptab = page_tables.astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page table + lens land in SMEM
        grid=(S, P),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, p, pt, ln: (s, 0, 0)),
            # the K/V block IS the page the table names: the pool is
            # indexed through the prefetched table, never gathered
            pl.BlockSpec((1, page, H, D),
                         lambda s, p, pt, ln: (pt[s, p], 0, 0, 0)),
            pl.BlockSpec((1, page, H, D),
                         lambda s, p, pt, ln: (pt[s, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda s, p, pt, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), _F32),     # running max
            pltpu.VMEM((H, 1), _F32),     # running normalizer
            pltpu.VMEM((H, D), _F32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_rpa_kernel, scale=scale, page=page, npp=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ragged_paged_attention",
        interpret=interpret,
    )(ptab, lens32, q, k_pages, v_pages)


def ragged_paged_attention_chunk_reference(q, k_pages, v_pages,
                                           page_tables, lens, scale=None):
    """Chunked decode attention: ``T`` query tokens per slot in one step
    (speculative verification / suffix prefill).

    q (S, T, H, D); k/v_pages (N, page, H, D); page_tables (S, P);
    lens (S,) = context rows *before* the chunk -> out (S, T, H, D).
    Query token ``j`` of slot ``s`` sits at position ``lens[s] + j`` and
    attends over pool positions ``t < lens[s] + j + 1`` — the chunk's
    own rows are causally visible because the caller writes the chunk's
    K/V into the pages before attending (same convention as the
    single-token step, which calls with ``lens + 1``).
    """
    S, T, H, D = q.shape
    page = k_pages.shape[1]
    P = page_tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    k = k_pages[page_tables].reshape(S, P * page, H, D).astype(_F32)
    v = v_pages[page_tables].reshape(S, P * page, H, D).astype(_F32)
    s = jnp.einsum("sjhd,sthd->sjht", q.astype(_F32), k) * scale
    t_pos = jnp.arange(P * page)
    limit = lens.reshape(-1, 1)[:, None] + jnp.arange(T)[None, :, None] + 1
    mask = t_pos[None, None, :] < limit                  # (S, T, Ptot)
    s = jnp.where(mask[:, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sjht,sthd->sjhd", p, v)
    return out.astype(q.dtype)


def _ring_held(newest, r, R: int):
    """The page that ring column ``r`` of ``R`` holds once page
    ``newest`` is the newest written: the newest ``pi <= newest`` with
    ``pi % R == r``; negative while the ring has not reached the
    column."""
    return newest - (newest + R - r) % R


def ring_column_seen(lens, T: int, r, page: int, R: int, window: int):
    """Whether any row of a chunk (``T <= page`` rows at positions
    ``lens .. lens + T - 1``) sees a key of ring column ``r``: the
    window kernel's page guard, on scalars (ints, arrays or traced).
    The chunk's rows lie on one page or on two; a column is reckoned
    for each from the oldest row there, which looks furthest back.  A
    column no row sees: the ring has not reached it, or it holds the
    oldest page and the window ends on that page's edge."""
    def seen(first, newest):
        held = _ring_held(newest, r, R)
        return (held >= 0) & (first - (held * page + page - 1) < window)

    n0 = lens // page
    if T == 1:
        return seen(lens, n0)
    n1 = (lens + T - 1) // page
    return seen(lens, n0) | seen(jnp.maximum(lens, n1 * page), n1)


def _ring_seen(shape, lens, r, page: int, R: int, T: int, G: int,
               window: int):
    """The window kernel's mask over ring column ``r``'s scores, ``shape``
    = (H, T * G, page): row ``t * G + g`` stands at ``lens + t`` and
    sees the key at ``held * page + lane`` when it is its own or one of
    the ``window - 1`` before it.  Rows from the next page's edge on
    (``T <= page``: at most one edge) reckon the column as that page
    leaves it, as ``ring_window_attention`` does a row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    n0 = lens // page
    base = _ring_held(n0, r, R) * page
    pos = lens
    if T > 1:
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        pos = lens + (row // G if G > 1 else row)
        base = jnp.where(pos >= (n0 + 1) * page,
                         _ring_held(n0 + 1, r, R) * page, base)
    back = pos - (base + lane)
    return (base >= 0) & (back >= 0) & (back < window)


PARTS = 3   # bfloat16 parts of a float32 query or probability: all 24 bits


def _operand(x, dtype, parts: int):
    """float32 ``x`` (H, rows, W) as the LEFT operand of a batched dot
    whose right operand is of ``dtype``: itself against float32; against
    bfloat16 its ``parts`` bfloat16 parts, whose sum is ``x`` to ``8 *
    parts`` bits (three: every bit a float32 holds), stacked as further
    ROWS (H, parts * rows, W).  The right operand IS bfloat16, so each
    product is exact in float32 and ``_sum_parts`` of the dot is the
    float32 product's, by one MXU pass over the right operand where a
    float32 dot of widened operands makes one too and rounds ``x`` to
    bfloat16 on the way in (PERF.md section 6, PR 63: the widened body's
    outputs stood 1e-3 off a float32 oracle on the chip, this one's
    1e-6).  Nothing is rounded to bfloat16."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return x
    out = []
    for i in range(parts):
        out.append(x.astype(jnp.bfloat16))
        if i + 1 < parts:
            x = x - out[-1].astype(_F32)
    return jnp.concatenate(out, axis=1)


def _sum_parts(x, rows: int):
    """(H, parts * rows, W) -> (H, rows, W): the row blocks of an
    ``_operand``'s products added up, smallest first."""
    if x.shape[1] == rows:
        return x
    total = x[:, x.shape[1] - rows:]
    for at in range(x.shape[1] - 2 * rows, -1, -rows):
        total = total + x[:, at:at + rows]
    return total


def _scores(q, k, rows: int):
    """q (H, parts * rows, D) against k (H, keys, D), both float32 or
    both bfloat16 -> (H, rows, keys) float32, unscaled: batch over H,
    contract D.  bfloat16 products are exact in float32 and are summed in
    float32: the float32 dot of the widened operands."""
    return _sum_parts(jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=_F32),
        rows)


def _softmax_page(q, k, v, seen, m_scr, l_scr, acc_scr, scale, heads_major):
    """Keys and values into a slot's online softmax, the arithmetic every
    chunk body shares: q (H, parts * T * G, D) (``_operand``'s rows); k
    and v as they are stored ((keys, H, D), or (H, keys, D)
    ``heads_major``), float32 or bfloat16 as q is; ``seen(shape)`` the
    mask over the (H, T * G, keys) scores; the running max, normaliser
    and accumulator float32 in VMEM scratch.  Row-major keys are turned
    in VMEM so that a head is a batch of the two dots.

    Who hands it what (``page_form``).  WIDENED, a page a call: a ring's
    columns (``_rpa_chunk_kernel``), a float32 pool over a page run
    (nothing is widened there: the ``astype`` is the identity) and
    heads-major pages (PERF.md section 6, PR 63, has the reading that
    kept Phi-4-mini-flash's here: at 88% of its stream neither the
    bfloat16 operands nor a turn at a time read faster).  STORED, a turn's
    pages a call: a row-major bfloat16 pool over a page run, the pages in
    the dtype they are stored in, turned as bfloat16 (half the vregs of
    the float32 turn) and never widened."""
    rows = m_scr.shape[1]
    if not heads_major:
        k, v = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)
    # scores (H, T * G, keys): batch over H, contract D
    sc = _scores(q, k, rows) * scale
    sc = jnp.where(seen(sc.shape), sc, _NEG_INF)
    m_prev = m_scr[...]                             # (H, T * G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
    pr = jnp.exp(sc - m_new)                        # (H, T * G, keys)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(pr, axis=2, keepdims=True)
    m_scr[...] = m_new
    # (H, T * G, keys) x (H, keys, Dv) batched over H -> (H, T * G, Dv)
    acc_scr[...] = acc_scr[...] * corr + _sum_parts(jax.lax.dot_general(
        _operand(pr, v.dtype, PARTS), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=_F32), rows)


def _softmax_start(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _softmax_out(l_scr, acc_scr):
    """The accumulator over the normaliser, float32.  A slot no page of
    which was live (an empty seat) gives zeros."""
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    return acc_scr[...] / l


def _softmax_finish(o_ref, l_scr, acc_scr):
    o_ref[0] = jnp.swapaxes(_softmax_out(l_scr, acc_scr), 0, 1).astype(
        o_ref.dtype)


def _rpa_walk_kernel(ptab_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                     kbuf, vbuf, sems, start, q_scr, m_scr, l_scr, acc_scr,
                     *, scale, page, npp, T, G, heads_major, fetch, slots,
                     form):
    """The chunk kernel over a page run: ONE grid step a slot, which
    walks the slot's live pages alone (``pallas/latent_attention.py``'s
    pattern).  The q block holds the slot's whole chunk, ``T * G`` rows:
    the chunk's T rows times the G query heads that read each of the
    page's K/V heads (grouped heads), row ``t * G + g`` at the chunk's
    row ``t``, so the group rides the one K/V page, read once.  The
    chunk's last row stands at ``lens + T - 1``: the first
    ``ceil((lens + T) / page)`` table columns are live, a dynamic trip
    count, and a column past them costs nothing, no grid step and no
    copy (a 96-column table of which 26 are live costs 26 page reads).

    The pools stay in HBM.  ``kbuf`` / ``vbuf`` (2, fetch, a page): a
    turn's ``fetch`` pages are copied a page a DMA into one half while
    the other half is computed on (``sems`` (K | V, half, page): one
    semaphore a copy in flight; only a live page is copied and waited
    for); a slot's last turn starts the NEXT slot's first copies, so only
    slot 0's are waited for with nothing to do; ``start`` (1,) in SMEM
    keeps the half a slot's first turn lies in from grid step to grid
    step, which is why the grid is ``arbitrary``.

    ``form`` (static, ``page_form``'s) says what the arithmetic is:

    ``"widened"`` (heads-major pages, float32 pools): a live page a
    ``_softmax_page`` on the page widened to float32.  ``q_scr`` (H, T *
    G, D): the chunk in float32 with its heads outermost, as the two
    batched dots take it, turned once a slot.

    ``"stored"`` (row-major bfloat16 pages): a TURN a ``_softmax_page``
    on the turn's ``fetch`` pages as ONE run of keys in the dtype they
    are stored in; ``q_scr`` (H, parts * T * G, D) bfloat16, a float32
    query split in exact parts.  A page's update is a chain (the scores'
    dot, a row maximum, the exponentials, the second dot) that starts
    from the last page's maximum and ends in the accumulator; a page a
    chain, each under its own ``pl.when``, nothing of one overlapped the
    next and 0.75-0.95 us a page went by whatever the page's bytes or
    arithmetic were (PERF.md section 6, PR 63).  A turn a chain is a
    quarter of them on operands four times as long.  A page of the last
    turn that is not live is computed too, under the mask: it holds an
    earlier page or the zeros both buffers are filled with before the
    first copy (a masked key's probability is an exact zero, and a zero
    times a finite number is zero; what a buffer was before anything was
    written to it need not be finite).

    ``"row"`` (a chunk of ONE row on row-major pages, the decode step's
    on ungrouped heads): one row is no free dimension for the two
    batched dots, and a page's arithmetic is ``_page_update``'s
    multiply-reduces on the page as it lies, nothing turned; ``q_scr``
    and the accumulator are (H, D), the running max and normaliser
    (H, 1)."""
    s = pl.program_id(0)
    row, stored = form == "row", form == "stored"

    def live_pages(slot):
        return jnp.clip(pl.cdiv(lens_ref[slot] + T, page), 0, npp)

    def page_copies(slot, col, half, j):
        pid = ptab_ref[slot, col]
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[half, j],
                                      sems.at[0, half, j]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[half, j],
                                      sems.at[1, half, j]))

    def for_live(live, turn, do):
        """``do(j, col)`` for each of a turn's table columns under
        ``live``."""
        for j in range(fetch):
            col = turn * fetch + j
            pl.when(col < live)(functools.partial(do, j, col))

    def start_copies(slot, live, turn, half):
        def begin(j, col):
            for c in page_copies(slot, col, half, j):
                c.start()
        for_live(live, turn, begin)

    @pl.when(s == 0)
    def _first():
        start[0] = 0
        if stored:
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)
        start_copies(0, live_pages(0), 0, 0)

    seq_len, first, live = lens_ref[s], start[0], live_pages(s)
    turns = pl.cdiv(live, fetch)
    _softmax_start(m_scr, l_scr, acc_scr)
    if row:
        q_scr[...] = q_ref[0, 0].astype(_F32)
    else:
        q_scr[...] = _operand(jnp.swapaxes(q_ref[0].astype(_F32), 0, 1),
                              q_scr.dtype, q_scr.shape[1] // (T * G))

    @pl.when((turns == 0) & (s + 1 < slots))
    def _empty_seat():
        start_copies(s + 1, live_pages(s + 1), 0, first)

    def turn(t, carry):
        half = (first + t) % 2

        @pl.when(t + 1 < turns)
        def _next_turn():
            start_copies(s, live, t + 1, 1 - half)

        @pl.when((t + 1 == turns) & (s + 1 < slots))
        def _next_slot():
            start_copies(s + 1, live_pages(s + 1), 0, 1 - half)

        def seen_from(col):
            """The mask of the keys from table column ``col`` on: chunk
            row ``r // G`` sees a key before ``seq_len + r // G + 1``."""
            def seen(shape):
                t_pos = col * page + jax.lax.broadcasted_iota(
                    jnp.int32, shape, 2)
                row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                if G > 1:
                    row = row // G
                return t_pos < seq_len + row + 1
            return seen

        def arrived(j, col):
            for c in page_copies(s, col, half, j):
                c.wait()

        def compute(j, col):
            arrived(j, col)
            if row:
                # the row sees its own key: ``seq_len + 1`` rows
                _row_page(q_scr[...], kbuf[half, j], vbuf[half, j], m_scr,
                          l_scr, acc_scr, col, page, seq_len + 1, scale)
                return
            _softmax_page(q_scr[...], kbuf[half, j].astype(_F32),
                          vbuf[half, j].astype(_F32), seen_from(col),
                          m_scr, l_scr, acc_scr, scale, heads_major)

        if stored:
            for_live(live, t, arrived)

            def keys(buf):      # the turn's pages, one run of rows
                return buf[half].reshape((fetch * page,) + buf.shape[3:])

            _softmax_page(q_scr[...], keys(kbuf), keys(vbuf),
                          seen_from(t * fetch), m_scr, l_scr, acc_scr,
                          scale, heads_major)
        else:
            for_live(live, t, compute)
        return carry

    jax.lax.fori_loop(0, turns, turn, 0)
    # the half the next slot's first turn was copied into
    start[0] = (first + turns) % 2
    if row:
        o_ref[0, 0] = _softmax_out(l_scr, acc_scr).astype(o_ref.dtype)
    else:
        _softmax_finish(o_ref, l_scr, acc_scr)


def _rpa_chunk_kernel(ptab_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, scale, page, npp, T, G,
                      heads_major, window):
    """The chunk kernel over a window layer's RING (the page run's is
    ``_rpa_walk_kernel``): one (slot, ring column) grid step, the column's
    page the K/V block, the q block and its ``T * G`` rows as the walk's.
    A ring is five columns or two, nearly always all seen: a grid step a
    column wastes none.

    The table's ``npp`` columns are the ring (``ring_window_attention``'s
    contract and its arithmetic: which page a column holds and where its
    keys stand are reckoned from the rows' positions, on scalars and one
    iota a page) and a row sees its own key and the ``window - 1``
    before it.  The columns are walked as they lie: a softmax does not
    mind the order."""
    s = pl.program_id(0)
    p = pl.program_id(1)

    pl.when(p == 0)(functools.partial(_softmax_start, m_scr, l_scr, acc_scr))
    seq_len = lens_ref[s]

    # a ring column no row of the chunk sees a key of skips its math
    @pl.when(ring_column_seen(seq_len, T, p, page, npp, window))
    def _page():
        q = q_ref[0].astype(_F32)                       # (T * G, H, D)
        k = k_ref[0].astype(_F32)
        v = v_ref[0].astype(_F32)
        _softmax_page(
            jnp.swapaxes(q, 0, 1), k, v,
            lambda shape: _ring_seen(shape, seq_len, p, page, npp, T, G,
                                     window),
            m_scr, l_scr, acc_scr, scale, heads_major)

    pl.when(p == npp - 1)(
        functools.partial(_softmax_finish, o_ref, l_scr, acc_scr))


def page_form(k_dtype, v_dtype, heads_major: bool, rows: int) -> str:
    """What the arithmetic of the walk over a page run is
    (``_rpa_walk_kernel``), from what the call sees at trace time: the
    pools' dtype, their layout and the chunk's rows a K/V head.

    ``"row"``: one row on row-major pages (PR 60's multiply-reduces).
    ``"stored"``: row-major bfloat16 pages under several rows: the MXU is
    fed the pages in the dtype they are stored in, a turn of them a
    softmax update (PR 63).  ``"widened"``: the rest, a page an update on
    float32 operands -- a float32 pool, where no widening exists, and
    heads-major pages.  ``pallas_dispatch_total``'s ``path`` carries the
    form where it is the stored one (``_use_walk``)."""
    if rows == 1 and not heads_major:
        return "row"
    if not heads_major and all(jnp.dtype(d) == jnp.bfloat16
                               for d in (k_dtype, v_dtype)):
        return "stored"
    return "widened"


def _chunk_call(q, k_pages, v_pages, page_tables, lens, scale, interpret,
                G, heads_major=False, window=None, step=False):
    """The chunk kernel's call: q (S, T * G, H, D) on pages of H heads,
    the whole chunk resident in the q/o blocks.  A page is (page, H, D),
    or (H, page, D) ``heads_major``; a V page may be narrower than a K
    page, ``(.., Dv)``: the accumulator and the output are the values'
    wide, and each pool's buffers its own pages'.

    Over a page run (``window`` None: the decode step's row through
    ``paged_attention``, the verify chunk and the prefix suffix through
    ``paged_chunk_attention``, grouped heads or not): one grid step a
    slot, the pools left in HBM and the slot's live pages copied by the
    kernel, ``WALK_PAGES`` a turn (``_rpa_walk_kernel``).  ``step``: the
    call is the decode step's on ungrouped heads and carries that
    kernel's name.  ``window``:
    the table is a ring's columns, ``lens`` the position of the chunk's
    first row, one grid step a (slot, column) with the column's page the
    K/V block (``_rpa_chunk_kernel``).  Which of the two is the static
    ``window`` alone."""
    S, TG, H, D = q.shape
    Dv = v_pages.shape[-1]          # the values' own width: the output's
    page = k_pages.shape[2 if heads_major else 1]
    P = page_tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    statics = dict(scale=scale, page=page, npp=P, T=TG // G, G=G,
                   heads_major=heads_major)
    # a (head, chunk row) a row of the softmax's state; the walk's one
    # row on a page as it lies (``_rpa_walk_kernel``) keeps its heads in
    # the sublanes
    form = None if window is not None else page_form(
        k_pages.dtype, v_pages.dtype, heads_major, TG)
    rows = (H,) if form == "row" else (H, TG)
    softmax = [
        pltpu.VMEM(rows + (1,), _F32),    # running max
        pltpu.VMEM(rows + (1,), _F32),    # running normalizer
        pltpu.VMEM(rows + (Dv,), _F32),   # output accumulator
    ]
    out_shape = jax.ShapeDtypeStruct((S, TG, H, Dv), q.dtype)
    args = (page_tables.astype(jnp.int32), lens.astype(jnp.int32),
            q, k_pages, v_pages)
    if window is not None:
        def page_of(s, p, pt, ln):
            # a column no row sees names the slot's newest page, which
            # one does: no id of a column the ring has not reached is
            # read, and an index repeated from the step before costs no
            # copy
            seen = ring_column_seen(ln[s], TG // G, p, page, P, window)
            return (pt[s, jnp.where(seen, p, (ln[s] // page) % P)],
                    0, 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, P),
            in_specs=[
                pl.BlockSpec((1, TG, H, D),
                             lambda s, p, pt, ln: (s, 0, 0, 0)),
                pl.BlockSpec((1,) + k_pages.shape[1:], page_of),
                pl.BlockSpec((1,) + v_pages.shape[1:], page_of),
            ],
            out_specs=pl.BlockSpec((1, TG, H, Dv),
                                   lambda s, p, pt, ln: (s, 0, 0, 0)),
            scratch_shapes=softmax,
        )
        return pl.pallas_call(
            functools.partial(_rpa_chunk_kernel, window=window, **statics),
            grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="ring_paged_attention", interpret=interpret)(*args)
    fetch = WALK_PAGES
    # the chunk, heads outermost: float32, or the stored pages' bfloat16
    # (a float32 query's exact parts one under another)
    q_scr = pltpu.VMEM(rows + (D,), _F32)
    if form == "stored":
        q_parts = 1 if q.dtype == jnp.bfloat16 else PARTS
        q_scr = pltpu.VMEM((H, q_parts * TG, D), jnp.bfloat16)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page table + lens land in SMEM
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, TG, H, D), lambda s, pt, ln: (s, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),          # the pools: in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, TG, H, Dv),
                               lambda s, pt, ln: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, fetch) + k_pages.shape[1:], k_pages.dtype),
            pltpu.VMEM((2, fetch) + v_pages.shape[1:], v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, fetch)),
            pltpu.SMEM((1,), jnp.int32),      # the half a slot starts in
            q_scr,
        ] + softmax,
    )
    kernel = functools.partial(_rpa_walk_kernel, fetch=fetch, slots=S,
                               form=form, **statics)
    call = dict(
        grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)
    # one body under three names: a trace tells the calls apart
    if step:
        return pl.pallas_call(
            kernel, name="ragged_paged_attention", **call)(*args)
    if G > 1:
        return pl.pallas_call(
            kernel, name="ragged_paged_attention_gqa", **call)(*args)
    return pl.pallas_call(
        kernel, name="ragged_paged_attention_chunk", **call)(*args)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_paged_attention_chunk(q, k_pages, v_pages, page_tables, lens,
                                 scale=None, interpret: bool = False):
    """Pallas chunked ragged paged-attention (same contract as
    ``ragged_paged_attention_chunk_reference``)."""
    return _chunk_call(q, k_pages, v_pages, page_tables, lens, scale,
                       interpret, 1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def ragged_paged_attention_walk(q, k_pages, v_pages, page_tables, lens,
                                scale=None, interpret: bool = False):
    """``ragged_paged_attention``'s contract by the walk: the decode
    step's row a slot is the chunk of one row after ``lens - 1`` cached
    rows (an empty seat, ``lens`` 0, walks no page and writes zeros)."""
    return _chunk_call(q[:, None], k_pages, v_pages, page_tables, lens - 1,
                       scale, interpret, 1, step=True)[:, 0]


# ---------------------------------------------------------------------------
# grouped-query pages: Hq query heads on Hkv K/V heads
# ---------------------------------------------------------------------------


def ragged_paged_attention_gqa_reference(q, k_pages, v_pages, page_tables,
                                         lens, scale=None,
                                         heads_major: bool = False):
    """The chunk reference on grouped heads: q (S, T, Hq, D); k/v_pages
    (N, page, Hkv, D), or (N, Hkv, page, D) ``heads_major``; query head
    ``i`` reading K/V head ``i // (Hq // Hkv)``; ``lens`` the rows
    before the chunk -> (S, T, Hq, D).  The V pages may be narrower
    than the K pages, ``(.., Dv)``: the output is then (S, T, Hq, Dv)
    (``models/mimo_v2.py``: keys of 192 stored at 256 lanes on values
    of 128)."""
    if heads_major:
        k_pages, v_pages = (jnp.swapaxes(k_pages, 1, 2),
                            jnp.swapaxes(v_pages, 1, 2))
    S, T, Hq, D = q.shape
    page, Hkv = k_pages.shape[1:3]
    G = Hq // Hkv
    P = page_tables.shape[1]
    if scale is None:
        scale = D ** -0.5
    Dv = v_pages.shape[-1]
    k = k_pages[page_tables].reshape(S, P * page, Hkv, D).astype(_F32)
    v = v_pages[page_tables].reshape(S, P * page, Hkv, Dv).astype(_F32)
    qg = q.astype(_F32).reshape(S, T, Hkv, G, D)
    s = jnp.einsum("sjhgd,sthd->sjhgt", qg, k) * scale
    limit = lens.reshape(-1, 1) + jnp.arange(T)[None, :] + 1     # (S, T)
    mask = jnp.arange(P * page)[None, None, :] < limit[:, :, None]
    s = jnp.where(mask[:, :, None, None, :], s, _NEG_INF)
    out = jnp.einsum("sjhgt,sthd->sjhgd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(S, T, Hq, Dv).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "heads_major"))
def ragged_paged_attention_gqa(q, k_pages, v_pages, page_tables, lens,
                               scale=None, interpret: bool = False,
                               heads_major: bool = False):
    """Pallas ragged paged attention on grouped heads (same contract as
    ``ragged_paged_attention_gqa_reference``): the chunk kernel with
    the G query heads of each K/V head laid out as further rows of the
    chunk, so a page is read once for all Hq heads and the bytes read
    are the Hkv heads', whatever Hq is.  A decode step is the chunk of
    one row."""
    return _grouped_call(q, k_pages, v_pages, page_tables, lens, scale,
                         interpret, heads_major)


def _grouped_call(q, k_pages, v_pages, page_tables, lens, scale, interpret,
                  heads_major, window=None):
    """The chunk kernel on grouped heads: over a page run's table
    columns, or over a ring's under ``window``."""
    S, T, Hq, D = q.shape
    Hkv = k_pages.shape[1 if heads_major else 2]
    G = Hq // Hkv
    # (S, T, Hkv, G, D) -> (S, T * G, Hkv, D): outside the kernel, on
    # S * T * Hq * D numbers
    rows = jnp.moveaxis(q.reshape(S, T, Hkv, G, D), 3, 2).reshape(
        S, T * G, Hkv, D)
    out = _chunk_call(rows, k_pages, v_pages, page_tables, lens, scale,
                      interpret, G, heads_major, window)
    Dv = out.shape[-1]
    return jnp.moveaxis(out.reshape(S, T, G, Hkv, Dv), 2, 3).reshape(
        S, T, Hq, Dv)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret",
                                             "heads_major"))
def ring_paged_attention(q, k_pages, v_pages, ring_tables, lens, window,
                         scale=None, interpret: bool = False,
                         heads_major: bool = False):
    """Pallas attention of a window layer over its rings where they lie
    in the pool: q (S, T, Hq, D), a chunk of ``T <= page`` rows a slot
    whose first stands at position ``lens`` (S,); k/v_pages (N, page,
    Hkv, D), or (N, Hkv, page, D) ``heads_major``, already written up to
    the chunk's last row; ``ring_tables`` (S, R) the ring's table
    columns, column ``r`` holding the newest page ``pi`` with ``pi % R
    == r`` -> (S, T, Hq, D).  ``ring_window_attention`` on the gathered
    rings is its reference: the same rows under the same mask, the
    grouped chunk kernel reading a page once at the width it is stored
    in, nothing gathered, turned or widened outside VMEM."""
    return _grouped_call(q, k_pages, v_pages, ring_tables, lens, scale,
                         interpret, heads_major, window)


def _grouped(q, k_pages) -> bool:
    return q.shape[-2] != k_pages.shape[2]


def _paged_gqa(q, k_pages, v_pages, page_tables, lens, scale,
               heads_major=False):
    """Dispatcher of the grouped path: a chunk (S, T, Hq, D) after
    ``lens`` cached rows."""
    from paddle_tpu import pallas as pk

    if _use_walk("ragged_paged_attention_gqa", q, k_pages, v_pages,
                 heads_major):
        return ragged_paged_attention_gqa(
            q, k_pages, v_pages, page_tables, lens, scale=scale,
            interpret=pk.interpret_mode(), heads_major=heads_major)
    return ragged_paged_attention_gqa_reference(
        q, k_pages, v_pages, page_tables, lens, scale=scale,
        heads_major=heads_major)


def paged_ring_attention(q, k_pages, v_pages, ring_tables, pos, window: int,
                         heads_major: bool = False):
    """Dispatcher of a window layer's cached attention: q (S, T, Hq, D)
    at positions ``pos`` (S, T), a chunk's one after another; the pages
    as ``paged_attention`` takes them and ``ring_tables`` (S, R) the
    ring's table columns.  Pages stored heads-major are read where they
    lie by the kernel (``ring_paged_attention``); row-major pages are
    gathered for ``ring_window_attention``, whose gather XLA fuses into
    the scores' product.  Read on the chip (PERF.md section 6, PR 50):
    K-EXAONE's six row-major rings of 256 rows took 0.80 ms a step
    gathered and 0.90 through the kernel, Phi-4-mini-flash's eight
    heads-major rings of 640 rows 8.1 gathered (a gather, a transpose
    of the copy and a widening of both before the products) and 3.4
    through the kernel.  The layout is what the caller states for its
    pool; there is no threshold."""
    from paddle_tpu import pallas as pk

    T, Hq, D = q.shape[1:]
    page, Hkv = k_pages.shape[1:3]
    if heads_major:
        page, Hkv = Hkv, page
    if pk.dispatch("ring_paged_attention", pk.policy(
            heads_major and T <= page and fits(page, Hq, D, Hkv), True)):
        return ring_paged_attention(
            q, k_pages, v_pages, ring_tables, pos[:, 0], window,
            interpret=pk.interpret_mode(), heads_major=True)
    k_ring, v_ring = k_pages[ring_tables], v_pages[ring_tables]
    if heads_major:
        # a ring's pages as rows of heads
        k_ring, v_ring = jnp.swapaxes(k_ring, 2, 3), jnp.swapaxes(v_ring, 2, 3)
    return ring_window_attention(q, k_ring, v_ring, pos, window, page)


def _softmax(s, sink=None):
    """Softmax over the last axis.  ``sink`` (broadcast against ``s``
    less its last axis): a learned scalar a query head that joins the
    denominator and carries no value, ``p_j = exp(s_j - m) / (exp(b -
    m) + sum_k exp(s_k - m))``: a softmax over one more key whose value
    row is zero (the gpt-oss form; ``models/mimo_v2.py``'s window
    layers).  A row that sees no key gives zeros under a sink."""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    sink = sink.astype(_F32)[..., None]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def ring_window_attention(q, k_ring, v_ring, pos, window: int, page: int,
                          sink=None, scale=None):
    """Attention of a window layer over its per-sequence ring, plain
    XLA, on a gathered copy of the ring (a few pages a slot, 256 rows
    at a window of 128 and 640 at 512).  Of row-major pages the gather
    IS the read: XLA fuses it into the scores' product.  Of heads-major
    pages it is a copy that is then turned and widened, and
    ``paged_ring_attention`` takes the kernel, whose oracle this is.

    q (S, T, Hq, D) at absolute positions ``pos`` (S, T); k/v_ring
    (S, R, page, Hkv, D): ring slot ``r`` holds the newest page ``pi``
    with ``pi % R == r``, already written up to the chunk's last row.
    A key is seen when it is the query's own row or one of the
    ``window - 1`` before it.  Which page a ring slot holds is reckoned
    per query row from its own position, so within a chunk of up to
    ``page`` rows a slot that a later row has begun to overwrite still
    reads as the old page for an earlier row: the overwritten rows are
    older than that row's window.

    ``v_ring`` may be narrower than ``k_ring``, (.., Dv): the output is
    (S, T, Hq, Dv).  ``scale``: the scores' (None: ``D ** -0.5``).
    ``sink`` (Hq,): a scalar a query head in the softmax's denominator
    (``_softmax``)."""
    S, T, Hq, D = q.shape
    R, Hkv, Dv = k_ring.shape[1], k_ring.shape[3], v_ring.shape[-1]
    G = Hq // Hkv
    k = k_ring.reshape(S, R * page, Hkv, D).astype(_F32)
    v = v_ring.reshape(S, R * page, Hkv, Dv).astype(_F32)
    qg = q.astype(_F32).reshape(S, T, Hkv, G, D)
    s = jnp.einsum("sjhgd,sthd->sjhgt", qg, k) * (
        D ** -0.5 if scale is None else scale)
    slot = jnp.arange(R * page, dtype=jnp.int32) // page
    off = jnp.arange(R * page, dtype=jnp.int32) % page
    newest = (pos // page)[:, :, None]                         # (S, T, 1)
    held = newest - (newest - slot) % R
    k_pos = held * page + off                                  # (S, T, R*pg)
    back = pos[:, :, None] - k_pos
    seen = (k_pos >= 0) & (back >= 0) & (back < window)
    s = jnp.where(seen[:, :, None, None, :], s, _NEG_INF)
    pr = _softmax(s, None if sink is None else sink.reshape(Hkv, G))
    out = jnp.einsum("sjhgt,sthd->sjhgd", pr, v)
    return out.reshape(S, T, Hq, Dv).astype(q.dtype)


def banded_prefill_attention(q, k, v, window: int, sink=None, scale=None,
                             before=None):
    """Causal attention of one contiguous prompt in which a row sees
    itself and the ``window - 1`` rows before it: q (T, Hq, D), k/v
    (T, Hkv, D) -> (T, Hq, D).  Blocks of ``window`` queries against
    their own and the previous block of keys: the scores are (T, Hq, 2
    * window), never T x T.  A prompt that is not whole blocks (only
    the eager oracle's) takes the masked dense form.

    ``v`` may be narrower than ``k``, (T, Hkv, Dv): the output is (T,
    Hq, Dv).  ``scale``: the scores' (None: ``D ** -0.5``).  ``sink``
    (Hq,): a scalar a query head in the softmax's denominator
    (``_softmax``).  ``before``: (k, v) of the ``window`` rows that
    stand before row 0, for a CHUNK of a prompt that continues over what
    a ring keeps (whole blocks only): the first block sees them where a
    prompt's sees nothing."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G, W = Hq // Hkv, window
    if scale is None:
        scale = D ** -0.5
    if sink is not None:
        sink = sink.reshape(Hkv, G, 1)
    if T % W:
        if before is not None:
            raise ValueError("rows before a chunk: whole blocks only")
        t = jnp.arange(T)
        back = t[:, None] - t[None, :]
        s = jnp.einsum("qhgd,khd->hgqk",
                       q.astype(_F32).reshape(T, Hkv, G, D),
                       k.astype(_F32)) * scale
        s = jnp.where((back >= 0) & (back < W), s, _NEG_INF)
        out = jnp.einsum("hgqk,khd->qhgd", _softmax(s, sink),
                         v.astype(_F32))
        return out.reshape(T, Hq, v.shape[-1]).astype(q.dtype)
    nb = T // W
    qb = q.reshape(nb, W, Hkv, G, D)

    def with_previous(x, x_before):
        xb = x.reshape(nb, W, Hkv, x.shape[-1])
        lead = (jnp.zeros_like(xb[:1]) if x_before is None
                else x_before.astype(xb.dtype)[None])
        prev = jnp.concatenate([lead, xb[:-1]], axis=0)
        return jnp.concatenate([prev, xb], axis=1)          # (nb, 2W, ..)

    k_before, v_before = (None, None) if before is None else before
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, with_previous(k, k_before),
                   preferred_element_type=_F32) * scale
    i = jnp.arange(W)[:, None]
    j = jnp.arange(2 * W)[None, :]
    seen = (j > i) & (j <= i + W)                            # (W, 2W)
    if before is None:      # a prompt's first block: nothing before it
        seen = seen & ((jnp.arange(nb) > 0)[:, None, None] | (j >= W))
    else:
        seen = seen[None]
    s = jnp.where(seen[:, None, None], s, _NEG_INF)
    pr = _softmax(s, None if sink is None else sink[None]).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", pr, with_previous(v, v_before),
                     preferred_element_type=_F32)
    return out.reshape(T, Hq, v.shape[-1]).astype(q.dtype)


def _use_kernel(kernel: str, page_size: int, H: int, D: int) -> bool:
    """The decode kernels' dispatch rule (``pallas.policy`` with no
    size threshold): the Pallas kernel at every shape ``fits()``
    accepts unless the mode is off — compiled on a TPU, interpreted
    where interpret mode is set off-TPU — else the jnp reference."""
    from paddle_tpu import pallas as pk

    return pk.dispatch(kernel, pk.policy(fits(page_size, H, D), True))


def _walks(dtype, page_size: int, Hkv: int, D: int,
           heads_major: bool = False) -> bool:
    """Whether the walk takes the pool's pages: interpreted, every
    shape; compiled, what ``walk_fits`` says."""
    from paddle_tpu import pallas as pk

    return pk.interpret_mode() or walk_fits(dtype, page_size, Hkv, D,
                                            heads_major)


def _use_walk(kernel: str, q, k_pages, v_pages,
              heads_major: bool = False) -> bool:
    """``_use_kernel`` for the calls over a page run that have no other
    kernel than the walk, q (S, T, Hq, D): where it would be compiled
    the pages also have to be whole tiles where they lie
    (``walk_fits``).  A call whose pages the walk consumes as they are
    stored is counted under ``path="compiled_stored"`` (or
    ``interpret_stored``), one whose pages it widens under the plain
    path (``page_form``)."""
    from paddle_tpu import pallas as pk

    T, Hq, D = q.shape[1:]
    page, Hkv = k_pages.shape[1:3]
    if heads_major:
        page, Hkv = Hkv, page
    form = page_form(k_pages.dtype, v_pages.dtype, heads_major,
                     T * (Hq // Hkv))
    return pk.dispatch(kernel, pk.policy(
        fits(page, Hq, D, Hkv)
        and _walks(k_pages.dtype, page, Hkv, D, heads_major), True),
        form if form == "stored" else "")


def paged_chunk_attention(q, k_pages, v_pages, page_tables, lens,
                          scale=None):
    """Dispatcher for the chunked step (mirrors ``paged_attention``)."""
    from paddle_tpu import pallas as pk

    if _grouped(q, k_pages):
        return _paged_gqa(q, k_pages, v_pages, page_tables, lens, scale)
    if _use_walk("ragged_paged_attention_chunk", q, k_pages, v_pages):
        return ragged_paged_attention_chunk(
            q, k_pages, v_pages, page_tables, lens, scale=scale,
            interpret=pk.interpret_mode())
    return ragged_paged_attention_chunk_reference(
        q, k_pages, v_pages, page_tables, lens, scale=scale)


def paged_attention(q, k_pages, v_pages, page_tables, lens, scale=None,
                    heads_major: bool = False):
    """Dispatcher: the Pallas kernel or the jnp reference — both
    jit-embeddable, identical contract (see ``_use_kernel``).
    ``heads_major``: the pages are stored (N, Hkv, page, D), a head's
    rows together (grouped heads only: ``fits`` says which head counts
    the row-major page cannot hold in place)."""
    from paddle_tpu import pallas as pk

    if heads_major or _grouped(q, k_pages):
        # the chunk of one row after ``lens - 1`` cached rows
        return _paged_gqa(q[:, None], k_pages, v_pages, page_tables,
                          lens - 1, scale, heads_major)[:, 0]
    S, H, D = q.shape
    page = k_pages.shape[1]
    if _use_kernel("ragged_paged_attention", page, H, D):
        # one online softmax over a slot's live pages, two grids: which
        # one follows from the pool's shape and dtype alone
        return (ragged_paged_attention_walk
                if _walks(k_pages.dtype, page, H, D)
                else ragged_paged_attention)(
            q, k_pages, v_pages, page_tables, lens, scale=scale,
            interpret=pk.interpret_mode())
    return ragged_paged_attention_reference(
        q, k_pages, v_pages, page_tables, lens, scale=scale)


# ---------------------------------------------------------------------------
# dense prefill
# ---------------------------------------------------------------------------


def dense_prefill_attention(q, k, v, causal: bool = True):
    """Prompt-time attention for ONE contiguous sequence: q/k/v
    (T, H, D) -> (T, H, D).  Reuses the flash-attention forward when its
    block layout fits the shape (the separately-compiled dense-prefill
    program of the prefill/decode split); otherwise the plain jnp
    softmax path — prompts are short where flash does not fit.  K and V
    of fewer heads than q (grouped heads) are repeated to q's: a prompt's
    K/V rows are a few MB, and the flash kernel stays the one it is."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.pallas import flash_attention as fa

    T, H, D = q.shape
    qb = jnp.moveaxis(q, 1, 0)            # (H, T, D) = (BH, S, D)
    kb = jnp.moveaxis(k, 1, 0)
    vb = jnp.moveaxis(v, 1, 0)
    if kb.shape[0] != H:
        kb = jnp.repeat(kb, H // kb.shape[0], axis=0)
        vb = jnp.repeat(vb, H // vb.shape[0], axis=0)
    if pk.dispatch("prefill_flash_attention",
                   pk.policy(fa.fits(1, H, T, D), True)):
        out = fa.flash_attention(qb, kb, vb, causal=causal,
                                 interpret=pk.interpret_mode())
    else:
        s = jnp.einsum("htd,hsd->hts", qb.astype(_F32),
                       kb.astype(_F32)) * (D ** -0.5)
        if causal:
            t = jnp.arange(T)
            s = jnp.where(t[:, None] >= t[None, :], s, _NEG_INF)
        out = jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1),
                         vb.astype(_F32)).astype(q.dtype)
    return jnp.moveaxis(out, 0, 1)


def _attend_with_lse(q, k, v, causal: bool):
    """(BH, S, D) over (BH, Sk, D) -> (out (BH, S, D) float32, the rows'
    log-sum-exp (BH, S)): the flash forward where its blocks fit the
    shape, else the plain softmax."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.pallas import flash_attention as fa

    (_, S, D), Sk = q.shape, k.shape[1]
    if pk.dispatch("prefill_flash_attention", pk.policy(
            fa.fits_forward(S, Sk, D, q.dtype.itemsize), True)):
        out, lse = fa.flash_attention_with_lse(
            q, k, v, causal, None, pk.interpret_mode())
        return out.astype(_F32), lse
    s = jnp.einsum("htd,hsd->hts", q.astype(_F32),
                   k.astype(_F32)) * (D ** -0.5)
    if causal:
        s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(Sk)[None, :], s,
                      _NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("hts,hsd->htd", jnp.exp(s - lse[..., None]),
                      v.astype(_F32)), lse


def prompt_chunk_attention(q, k, v, k_run, v_run):
    """Attention of a CHUNK of one prompt that continues its own
    prefill: ``q`` (C, H, D) and the chunk's own ``k``, ``v`` (C, Hkv,
    D), after the sequence's cached rows ``k_run``, ``v_run`` (Hkv,
    done, D: heads first, as the kernel takes them), every one real and
    before every chunk row -> (C, H, D) in q's dtype.  Two reads merged
    by their log-sum-exp: the cached rows
    without a mask, the group's query heads folded into the rows of
    their K/V head (no row order matters there, so K/V are not
    repeated), and the chunk itself causal (``dense_prefill_attention``'s
    repeat of a few MB).  Padding rows at the chunk's end are hidden
    from the real rows by causality."""
    (C, H, D), Hkv = q.shape, k.shape[1]
    G = H // Hkv
    qh = jnp.moveaxis(q, 1, 0)                               # (H, C, D)
    own, own_lse = _attend_with_lse(
        qh, jnp.repeat(jnp.moveaxis(k, 1, 0), G, axis=0),
        jnp.repeat(jnp.moveaxis(v, 1, 0), G, axis=0), True)
    run, run_lse = _attend_with_lse(
        qh.reshape(Hkv, G * C, D), k_run, v_run, False)
    run, run_lse = run.reshape(H, C, D), run_lse.reshape(H, C)
    top = jnp.maximum(own_lse, run_lse)
    w_own, w_run = jnp.exp(own_lse - top), jnp.exp(run_lse - top)
    out = ((own * w_own[..., None] + run * w_run[..., None])
           / (w_own + w_run)[..., None])
    return jnp.moveaxis(out, 0, 1).astype(q.dtype)
