"""The four kernels of sparse latent attention (``models/glm_dsa.py``:
a learned indexer chooses, for every query row, the cached rows its
latent attention reads).  The fifth the layer runs, a decode step's
read, is ``latent_attention.py``'s walk of a slot's live pages, under
the bias ``selection_bias`` makes of ``paged_index_scores``' scores.

**``paged_index_scores``** (a decode step).  The indexer keeps ONE key
row a token a layer, ``D`` lanes, on the page run beside the latent row.
A step's index query is ``J`` heads of ``D`` and a weight a head:

    q      (S, J, D)       the slots' index queries (rotated)
    w      (S, J) float32  the heads' weights
    pages  (N, page, D)    every layer's index pages, seen flat
    tables (S, P) int32    a slot's pages (already moved to the layer)
    lens   (S,)            rows the slot's query sees (its own included)
    ->     (S, P * page) float32:
           I[s, t] = sum_j w[s, j] ReLU(q[s, j] . row t)   t < lens[s]
           -inf                                           elsewhere

One grid step a SLOT, as ``latent_attention.py``: the pool stays in HBM,
the step walks the slot's live pages alone, ``fetch`` of them a turn
(index pages are a fifth of a latent page's bytes, so a turn takes up to
32), each page one DMA into one half of a double buffer; a slot's last
turn starts the next slot's first copies.  A turn is one ``(J, D) x (D,
rows)`` product, ReLU, the heads' weights and a sum over the heads'
sublanes: 256 B read a row scored for 2 J D FLOPs (32 FLOP a byte at 32
heads of 128: a stream).

**``index_scores``** (a prefill).  The same numbers for ``T`` query
rows against ``n`` key rows laid out dense, in blocks, nothing masked:

    q (J, T, D), w (T, J) float32, k (n, D)  ->  (T, n) float32

A block past ``limit[0] + `` the query block's last row (keys no query
row of the block may see) is skipped and its output left unwritten: the
caller masks by position.

**``selection_bias``** (the selection: a prefill's, and a decode
step's).  From those scores the mask the attention below runs under,
``S_t`` of every query row as an additive bias:

    scores (T, n) float32   ``index_scores``' output; the blocks it left
                            unwritten hold anything (NaN included)
    limit  (1,) int32       the position of the first query row
    k      static           rows a query row keeps (``index_topk``)
    ->     (T, n) ``dtype``: 0 where key ``s`` is in ``S_t``, a large
           negative number elsewhere

``S_t`` is, of the keys row ``t`` sees (``s <= limit + t``), the
``min(k, seen)`` of largest score in the floats' total order (-0.0 under
0.0, the infinities at the ends), a tie at the edge to the lower ``s``:
member for member ``models/glm_dsa.py:selection_mask``.  One grid step
holds ``selection_rows()`` query rows over the WHOLE key width in VMEM
(64 rows x 25,600 keys x 4 B = 6.5 MB): it masks by position, turns the
scores into int32 keys of the same order in a scratch, finds each row's
``k``-th largest there by bisection over the 32 bits (a pass is a
compare and a lane-wise count over the scratch, the lanes summed once a
pass; the counts at both ends of the interval ride along, so the rows
above the edge and the rows tied on it are known when it closes), cuts
the tied, where a row has more of them than room, at a column found by
a second bisection over the column index (a block none of whose rows
needs it skips that), and writes the bias.  The scores are read from
HBM once and the bias written once: 4 + 2 B a pair where the XLA form
passed 34 times over the matrix.  Key columns past the row block's last
seen position (a bucket's causal half) are neither compared nor
counted; they are written as not selected.

A decode step hands it ``paged_index_scores``' block as it is, a row a
SLOT (32 slots x 25,600 scores are one grid step), ``limit`` the longest
slot's last row and ``dtype`` float32: every row then sees every column
any slot has, and a slot's own length is in its scores, -inf past it and
so under every real score.  A slot with ``k`` rows or more gets its
``k`` best, ties as above; one with fewer gets them all and, as
"members", the first of its -inf columns up to ``k``: the walk that
adds the bias (``latent_attention.py``) masks by the slot's length.

**``selected_flash_attention``** (a prefill over selected rows).  Flash
attention forward whose mask is an additive bias a (query row, key row)
pair, shared by all heads (0 where the pair is selected, a large
negative number elsewhere; causality is part of it):

    q (H, T, D), k (H, n, D), v (H, n, D), bias (T, n)  ->  (H, T, D)

``heads_a_step`` heads ride one grid step, so a bias block is read once
for all of them; key blocks past ``limit[0] +`` the query block's last
row are neither fetched nor computed.  The causal flash kernel
(``flash_attention.py``) is not touched: this is a second entry point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NEG_INF = -1e30            # finite, inside the attention kernel
_INT_MIN = -2 ** 31
LANES = 128
MAX_FETCH = 32              # index pages a turn of the paged walk reads
SCORE_BLOCK_Q = 256
SCORE_BLOCK_K = 512
SELECT_ROWS = 64            # query rows a step of the selection holds
SELECT_CHUNK = 1024         # key columns a turn of one of its passes takes
FLASH_BLOCK = 512
HEADS_A_STEP = 4
VMEM_LIMIT = 64 * 1024 * 1024


# -- the indexer's scores over a slot's pages (a decode step) -----------------


def fetch_pages(pages_per_seq: int) -> int:
    """Pages a turn takes: the most up to ``MAX_FETCH`` that divide the
    table's width, so a turn never reads past a slot's table row."""
    return max(f for f in range(1, MAX_FETCH + 1)
               if pages_per_seq % f == 0)


def paged_fits(dtype, page_size: int, heads: int, dim: int) -> bool:
    """Rows of whole 128-lane tiles, pages of whole sublane tiles of the
    dtype, a query of whole sublane tiles."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (dim % LANES == 0 and page_size % sublanes == 0
            and heads % 8 == 0)


def paged_index_scores_reference(q, w, pages, tables, lens):
    """The contract above in jnp: the oracle, and the path off a TPU."""
    S, P = tables.shape
    page = pages.shape[1]
    rows = pages[tables].reshape(S, P * page, -1)
    s = jnp.einsum("sjd,snd->sjn", q.astype(rows.dtype), rows,
                   preferred_element_type=_F32)
    scores = jnp.einsum("sjn,sj->sn", jax.nn.relu(s), w.astype(_F32))
    seen = jnp.arange(P * page)[None, :] < lens.reshape(-1, 1)
    return jnp.where(seen, scores, -jnp.inf)


def _paged_kernel(tab_ref, lens_ref, q_ref, w_ref, pool_ref, o_ref, buf,
                  sems, start, *, page, fetch, slots):
    s = pl.program_id(0)
    turn_rows = fetch * page

    def copies(slot, turn, half):
        return [pltpu.make_async_copy(
            pool_ref.at[tab_ref[slot, turn * fetch + j]],
            buf.at[half, pl.ds(j * page, page)], sems.at[half, j])
            for j in range(fetch)]

    @pl.when(s == 0)
    def _first():
        start[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    n, first = lens_ref[s], start[0]
    turns = jnp.maximum((n + turn_rows - 1) // turn_rows, 1)
    o_ref[...] = jnp.full_like(o_ref, -jnp.inf)
    q, w = q_ref[0], w_ref[0]                           # (J, D), (J, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, turn_rows), 1)

    def turn(t, carry):
        half = (first + t) % 2

        @pl.when(t + 1 < turns)
        def _next_turn():
            for c in copies(s, t + 1, 1 - half):
                c.start()

        @pl.when((t + 1 == turns) & (s + 1 < slots))
        def _next_slot():
            for c in copies(s + 1, 0, 1 - half):
                c.start()

        for c in copies(s, t, half):
            c.wait()
        sc = jax.lax.dot_general(q, buf[half], (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)
        sc = jnp.sum(jnp.maximum(sc, 0.0) * w, axis=0, keepdims=True)
        o_ref[0, pl.ds(t, 1), :] = jnp.where(t * turn_rows + col < n, sc,
                                             -jnp.inf)
        return carry

    jax.lax.fori_loop(0, turns, turn, 0)
    start[0] = (first + turns) % 2


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_index_scores(q, w, pages, tables, lens, *,
                       interpret: bool = False):
    """The Pallas call (the contract at the top of the file)."""
    S, J, D = q.shape
    page, P = pages.shape[1], tables.shape[1]
    fetch = fetch_pages(P)
    turns, turn_rows = P // fetch, fetch * page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # tables and lens land in SMEM
        grid=(S,),
        in_specs=[pl.BlockSpec((1, J, D), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec((1, J, 1), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],       # pool: in HBM
        out_specs=pl.BlockSpec((1, turns, turn_rows),
                               lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, turn_rows, D), pages.dtype),
            pltpu.SemaphoreType.DMA((2, fetch)),
            pltpu.SMEM((1,), jnp.int32),      # the half a slot starts in
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, fetch=fetch, slots=S),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, turns, turn_rows), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_index_scores",
        interpret=interpret,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32),
      q.astype(pages.dtype), w.astype(_F32)[..., None], pages)
    return out.reshape(S, P * page)


# -- the indexer's scores over dense rows (a prefill) -------------------------


def _block(n: int, pref: int) -> int:
    """The largest block up to ``pref`` that divides ``n`` in halvings
    down to 128 rows, or 0."""
    b = pref
    while b >= LANES and n % b:
        b //= 2
    return b if b >= LANES and n % b == 0 else 0


def dense_fits(T: int, n: int, heads: int, dim: int) -> bool:
    """Index rows of whole tiles, query and key rows in whole blocks of
    128 and up."""
    return (dim % LANES == 0 and heads > 0 and _block(T, SCORE_BLOCK_Q) > 0
            and _block(n, SCORE_BLOCK_K) > 0)


def index_scores_reference(q, w, k):
    """(J, T, D), (T, J), (n, D) -> (T, n) float32, every pair."""
    s = jnp.einsum("jtd,nd->jtn", q.astype(k.dtype), k,
                   preferred_element_type=_F32)
    return jnp.einsum("jtn,tj->tn", jax.nn.relu(s), w.astype(_F32))


def _dense_kernel(limit_ref, q_ref, w_ref, k_ref, o_ref, *, heads, blk_q,
                  blk_k):
    qi, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki * blk_k <= limit_ref[0] + qi * blk_q + blk_q - 1)
    def _scores():
        k = k_ref[...]
        acc = jnp.zeros((blk_q, blk_k), _F32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=_F32)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, j:j + 1]
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_scores(q, w, k, limit, *, interpret: bool = False):
    """The Pallas call.  ``limit`` (1,) int32: the position of the first
    query row (key ``s`` is seen by query row ``t`` when ``s <= limit +
    t``); blocks no row of theirs sees are not computed."""
    J, T, D = q.shape
    n = k.shape[0]
    blk_q, blk_k = _block(T, SCORE_BLOCK_Q), _block(n, SCORE_BLOCK_K)

    def last_seen(i, lim):
        return jnp.minimum((lim[0] + i * blk_q + blk_q - 1) // blk_k,
                           n // blk_k - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T // blk_q, n // blk_k),
        in_specs=[
            pl.BlockSpec((J, blk_q, D), lambda i, j, lim: (0, i, 0)),
            pl.BlockSpec((blk_q, J), lambda i, j, lim: (i, 0)),
            pl.BlockSpec((blk_k, D), lambda i, j, lim: (
                jnp.minimum(j, last_seen(i, lim)), 0)),
        ],
        out_specs=pl.BlockSpec((blk_q, blk_k), lambda i, j, lim: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(_dense_kernel, heads=J, blk_q=blk_q, blk_k=blk_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, n), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="index_scores",
        interpret=interpret,
    )(limit.astype(jnp.int32).reshape(1), q.astype(k.dtype), w.astype(_F32),
      k)


# -- each row's selected set as a bias (a prefill) ----------------------------


def selection_rows(T: int, n: int, itemsize: int) -> int:
    """Query rows a grid step holds: the most up to ``SELECT_ROWS``, in
    halvings down to a bfloat16 tile's 16, that divide ``T`` and whose
    scores (two buffers), keys and bias (two buffers) over ``n`` keys
    take half of ``VMEM_LIMIT``; or 0."""
    r = SELECT_ROWS
    while r >= 16 and (T % r
                       or r * n * (12 + 2 * itemsize) > VMEM_LIMIT // 2):
        r //= 2
    return r if r >= 16 else 0


def selection_fits(T: int, n: int, dtype) -> bool:
    """Key rows in whole chunks of 128 and up, query rows in whole row
    blocks that stay resident over the key width."""
    return (_block(n, SELECT_CHUNK) > 0
            and selection_rows(T, n, jnp.dtype(dtype).itemsize) > 0)


def _select_kernel(limit_ref, s_ref, o_ref, keys, *, k, rows, chunk, n):
    first = limit_ref[0] + pl.program_id(0) * rows
    # a row's position is the last key column it sees
    pos = first + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    turns = jnp.minimum((first + rows - 1) // chunk + 1, n // chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)

    def tiles(c):
        """The column offsets of chunk ``c``'s 128-lane tiles."""
        return [pl.multiple_of(c * chunk + j * LANES, LANES)
                for j in range(chunk // LANES)]

    def make(c, carry):
        for off in tiles(c):
            b = jax.lax.bitcast_convert_type(s_ref[:, pl.ds(off, LANES)],
                                             jnp.int32)
            key = jnp.where(b < 0, b ^ jnp.int32(0x7fffffff), b)
            keys[:, pl.ds(off, LANES)] = jnp.where(lane <= pos - off, key,
                                                   _INT_MIN)
        return carry

    jax.lax.fori_loop(0, turns, make, 0)

    def count(hit):
        """(rows, 1): the seen chunks' keys ``x`` at columns ``off +
        lane`` with ``hit(x, off)``, counted lane-wise, the lanes summed
        once."""
        def turn(c, acc):
            for off in tiles(c):
                acc = acc + hit(keys[:, pl.ds(off, LANES)],
                                off).astype(jnp.int32)
            return acc

        acc = jax.lax.fori_loop(0, turns, turn,
                                jnp.zeros((rows, LANES), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    # the k-th largest key: the largest v with count(keys >= v) >= k
    # (``glm_dsa.kth_largest``), count(>= lo) and count(> hi) carried
    def halve(_, bounds):
        lo, hi, at_lo, over_hi = bounds
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)        # the ceiling
        wide = jnp.broadcast_to(mid, (rows, LANES))
        cnt = count(lambda x, off: x >= wide)
        ok = cnt >= k
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1),
                jnp.where(ok, cnt, at_lo), jnp.where(ok, over_hi, cnt))

    col = jnp.zeros((rows, 1), jnp.int32)
    edge, _, at_edge, above = jax.lax.fori_loop(0, 32, halve, (
        col + _INT_MIN, col + (2 ** 31 - 1), col + turns * chunk, col))
    edge_wide = jnp.broadcast_to(edge, (rows, LANES))
    # a row that sees k keys or fewer keeps them all; another keeps the
    # keys above its edge and the first ``room`` of those tied on it
    room = k - above
    crowded = (jnp.minimum(pos + 1, n) > k) & (at_edge - above > room)

    def cut(_):
        """The column of each crowded row's ``room``-th tied key."""
        def narrow(_, bounds):
            lo, hi = bounds
            mid = (lo + hi) >> 1
            ok = count(lambda x, off: (x == edge_wide)
                       & (lane <= mid - off)) >= room
            return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

        return jax.lax.fori_loop(0, (n - 1).bit_length(), narrow,
                                 (col, col + (n - 1)))[0]

    last = jax.lax.cond(jnp.max(crowded.astype(jnp.int32)) > 0, cut,
                        lambda _: col, None)
    last = jnp.minimum(jnp.where(crowded, last, n), pos)

    def write(c, carry):
        for off in tiles(c):
            x = keys[:, pl.ds(off, LANES)]
            sel = (x > edge_wide) | ((x == edge_wide) & (lane <= last - off))
            o_ref[:, pl.ds(off, LANES)] = jnp.where(
                sel, 0.0, _NEG_INF).astype(o_ref.dtype)
        return carry

    def blank(c, carry):
        for off in tiles(c):
            o_ref[:, pl.ds(off, LANES)] = jnp.full(
                (rows, LANES), _NEG_INF, _F32).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, turns, write, 0)
    jax.lax.fori_loop(turns, n // chunk, blank, 0)


@functools.partial(jax.jit, static_argnames=("k", "dtype", "interpret"))
def selection_bias(scores, limit, *, k: int, dtype,
                   interpret: bool = False):
    """The Pallas call (the contract at the top of the file)."""
    T, n = scores.shape
    rows = selection_rows(T, n, jnp.dtype(dtype).itemsize)
    chunk = _block(n, SELECT_CHUNK)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T // rows,),
        in_specs=[pl.BlockSpec((rows, n), lambda i, lim: (i, 0))],
        out_specs=pl.BlockSpec((rows, n), lambda i, lim: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows, n), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, rows=rows, chunk=chunk, n=n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="selection_bias",
        interpret=interpret,
    )(limit.astype(jnp.int32).reshape(1), scores.astype(_F32))


# -- flash attention under a bias a (query row, key row) pair -----------------


def flash_fits(H: int, T: int, n: int, D: int) -> bool:
    return (D % LANES == 0 and D <= 256 and _block(T, FLASH_BLOCK) > 0
            and _block(n, FLASH_BLOCK) > 0 and H % _heads_a_step(H) == 0)


def _heads_a_step(H: int) -> int:
    h = HEADS_A_STEP
    while H % h:
        h //= 2
    return h


def selected_attention_reference(q, k, v, bias, scale):
    """(H, T, D), (H, n, D), (H, n, D), (T, n) -> (H, T, D): softmax
    over the keys of ``q . k * scale + bias``."""
    s = jnp.einsum("htd,hnd->htn", q, k, preferred_element_type=_F32)
    p = jax.nn.softmax(s * scale + bias.astype(_F32)[None], axis=-1)
    return jnp.einsum("htn,hnd->htd", p.astype(v.dtype), v,
                      preferred_element_type=_F32).astype(q.dtype)


def _flash_kernel(limit_ref, q_ref, k_ref, v_ref, b_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, scale, heads, blk_q, blk_k, nk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * blk_k <= limit_ref[0] + qi * blk_q + blk_q - 1)
    def _block_of_keys():
        bias = b_ref[...].astype(_F32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=_F32) * scale + bias
            m_prev = m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h, :, 0:1] = l_scr[h, :, 0:1] * corr + jnp.sum(
                p, axis=1, keepdims=True)
            m_scr[h, :, 0:1] = m_new
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=_F32)

    @pl.when(ki == nk - 1)
    def _finish():
        for h in range(heads):
            l = l_scr[h, :, 0:1]
            o_ref[h] = (acc_scr[h] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def selected_flash_attention(q, k, v, bias, limit, *, scale: float,
                             interpret: bool = False):
    """The Pallas call.  ``limit`` (1,) int32: the position of the first
    query row; the key blocks past the query block's last position hold
    nothing selected (the bias is causal) and are skipped."""
    H, T, D = q.shape
    n = k.shape[1]
    hb = _heads_a_step(H)
    blk_q, blk_k = _block(T, FLASH_BLOCK), _block(n, FLASH_BLOCK)
    nk = n // blk_k

    def seen(i, j, lim):
        return jnp.minimum(j, jnp.minimum(
            (lim[0] + i * blk_q + blk_q - 1) // blk_k, nk - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H // hb, T // blk_q, nk),
        in_specs=[
            pl.BlockSpec((hb, blk_q, D), lambda g, i, j, lim: (g, i, 0)),
            pl.BlockSpec((hb, blk_k, D),
                         lambda g, i, j, lim: (g, seen(i, j, lim), 0)),
            pl.BlockSpec((hb, blk_k, D),
                         lambda g, i, j, lim: (g, seen(i, j, lim), 0)),
            pl.BlockSpec((blk_q, blk_k),
                         lambda g, i, j, lim: (i, seen(i, j, lim))),
        ],
        out_specs=pl.BlockSpec((hb, blk_q, D),
                               lambda g, i, j, lim: (g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((hb, blk_q, LANES), _F32),
            pltpu.VMEM((hb, blk_q, LANES), _F32),
            pltpu.VMEM((hb, blk_q, D), _F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, heads=hb,
                          blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="selected_flash_attention",
        interpret=interpret,
    )(limit.astype(jnp.int32).reshape(1), q, k, v, bias)
