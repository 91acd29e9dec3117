"""Absorbed latent attention (MLA) over paged latent rows: the decode
step's kernel, and the verify chunk's.

A latent layer keeps ONE row a token: the normalised compression ``c``
(``v_width`` lanes) followed by the rotated shared key ``k^r``, padded
with zeros to whole 128-lane tiles (``width`` lanes as stored).  With
the per-head up-projections absorbed into the query and the output, a
step is attention of every query head on that one row: the row's
``width`` lanes are the key, its first ``v_width`` lanes the value, and
the page is read ONCE for both and for all heads.

    q      (S, R, width)   R = T * heads: chunk row r // heads, zero in
                           the lanes where the stored row is padding
    pages  (N, page, width) every layer's pages, seen flat
    tables (S, P) int32    a slot's pages (already moved to the layer)
    lens   (S,)            rows cached BEFORE the chunk; the chunk's own
                           T rows are written before the call
    bias   (S, P * page) float32, or None: added to a slot's scores
                           column by column, the same for all its query
                           rows (a sparse layer's selected set: 0 on a
                           member, a large negative number elsewhere)
    ->     (S, R, v_width) row r: softmax over t < lens + r // heads + 1
                           of q . row * scale (+ bias)

One grid step a SLOT.  The pool stays in HBM; the step walks the slot's
live pages alone (``ceil((lens + T) / page)`` of them, a dynamic trip
count: a 64-column table of which 17 are live costs 17 page reads, not
64 grid steps), ``fetch_pages`` of them a turn, each page one DMA into
one half of a double buffer while the other half is computed on; a
slot's last turn starts the NEXT slot's first copies, so only slot 0's
are waited for with nothing to do (which half a slot starts in is kept
in SMEM from grid step to grid step, which is why the grid is
``arbitrary``).  Both products run on the MXU in the pool's dtype with
float32 accumulation: ``q k^T`` over ``width`` lanes, ``p`` (rounded to
the pool's dtype) on the first ``v_width`` lanes of the same buffer.

The bias is an operand that is there or not, a static of the trace: a
slot's row of it resident beside its q block, a turn a sublane row (one
row of ``fetch * page`` columns, broadcast over the query rows).  The
walk under a bias is still the walk of ALL the slot's live pages: a turn
none of whose columns is a member leaves the running max where it
started, and the first member's turn rescales what such turns summed to
nothing.  Without one the traced body holds no load and no buffer more.

At 32 heads a row of 576 stored at 640 does 32 x (640 + 512) x 2 / 1,280
= 58 FLOP a byte: beside a v5e's ridge at 32 rows a tile (~60), neither
a stream nor a matmul.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NEG_INF = -1e30            # finite, as the other attention kernels
LANES = 128
FETCH_PAGES = 4             # pages a turn of the loop reads and computes
MAX_ROWS = 512              # q / accumulator rows resident (T * heads)


def fetch_pages(pages_per_seq: int) -> int:
    """Pages a turn takes: the most up to ``FETCH_PAGES`` that divide
    the table's width, so a turn never reads past a slot's table row."""
    return math.gcd(int(pages_per_seq), FETCH_PAGES)


def fits(dtype, page_size: int, rows: int, width: int, v_width: int) -> bool:
    """Rows of whole 128-lane tiles whose value is a leading run of
    whole tiles, pages of whole sublane tiles of the dtype (8 rows of 4
    bytes, 16 of 2), and a chunk whose q block stays resident."""
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return (width % LANES == 0 and v_width % LANES == 0
            and 0 < v_width <= width and page_size % sublanes == 0
            and 0 < rows <= MAX_ROWS)


def latent_paged_attention_reference(q, pages, tables, lens, *, heads,
                                     v_width, scale, bias=None):
    """The contract above in jnp: the oracle, and the path off a TPU."""
    S, R, W = q.shape
    page, P = pages.shape[1], tables.shape[1]
    rows = pages[tables].reshape(S, P * page, W).astype(_F32)
    s = jnp.einsum("srw,stw->srt", q.astype(_F32), rows) * scale
    if bias is not None:
        s = s + bias.astype(_F32)[:, None, :]
    limit = lens.reshape(-1, 1) + jnp.arange(R)[None, :] // heads + 1
    seen = jnp.arange(P * page)[None, None, :] < limit[:, :, None]
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum("srt,stv->srv", p,
                      rows[..., :v_width]).astype(q.dtype)


def _kernel(tab_ref, lens_ref, q_ref, pool_ref, *rest, heads, chunk, page,
            fetch, v_width, scale, slots, biased):
    """One slot.  ``buf`` (2, fetch * page, width): the double buffer;
    ``sems`` (2, fetch): one DMA semaphore a page in flight; ``start``
    (1,) in SMEM: the half this slot's first turn was copied into;
    ``bias_ref`` (1, turns, fetch * page), where ``biased``: the slot's
    bias, a turn a row."""
    bias_ref = rest[0] if biased else None
    o_ref, buf, sems, start, m_scr, l_scr, acc_scr = rest[biased:]
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

    seq_len, first = lens_ref[s], start[0]
    turns = (seq_len + chunk + turn_rows - 1) // turn_rows
    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                                            # (R, width)
    row = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], turn_rows), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], turn_rows), 1)
    limit = seq_len + row // heads + 1

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
        rows = buf[half]                                    # (rows, width)
        sc = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32) * scale
        if biased:
            sc = sc + bias_ref[0, pl.ds(t, 1), :]
        sc = jnp.where(t * turn_rows + col < limit, sc, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :v_width],
            (((1,), (0,)), ((), ())), preferred_element_type=_F32)
        return carry

    jax.lax.fori_loop(0, turns, turn, 0)
    start[0] = (first + turns) % 2
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "v_width", "scale",
                                             "interpret"))
def latent_paged_attention(q, pages, tables, lens, bias=None, *, heads,
                           v_width, scale, interpret: bool = False):
    """The Pallas call (the contract at the top of the file)."""
    S, R, W = q.shape
    page, P = pages.shape[1], tables.shape[1]
    fetch = fetch_pages(P)
    in_specs = [pl.BlockSpec((1, R, W), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)]          # pool: in HBM
    operands = [q.astype(pages.dtype), pages]
    if bias is not None:                  # a slot's row, a turn a sublane
        by_turn = (P // fetch, fetch * page)
        in_specs.append(pl.BlockSpec((1,) + by_turn,
                                     lambda s, *_: (s, 0, 0)))
        operands.append(bias.astype(_F32).reshape((S,) + by_turn))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # tables and lens land in SMEM
        grid=(S,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, R, v_width), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, fetch * page, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2, fetch)),
            pltpu.SMEM((1,), jnp.int32),      # the half a slot starts in
            pltpu.VMEM((R, 1), _F32),         # running max
            pltpu.VMEM((R, 1), _F32),         # running normaliser
            pltpu.VMEM((R, v_width), _F32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, chunk=R // heads, page=page,
                          fetch=fetch, v_width=v_width, scale=scale, slots=S,
                          biased=bias is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, R, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_paged_attention",
        interpret=interpret,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), *operands)
