"""The grouped GEMM of a routed layer's experts over sorted rows, as a
Pallas call that streams each hit expert's matrix ONCE.

    xs     (M, K)      rows sorted by group
    w      (C, K, N)   a matrix a group
    sizes  (C,) int32  rows a group, in order; rows behind the last
                       group are no group's
    ->     (M, N) float32: ``out[i] = xs[i] @ w[group of row i]``; what
           comes out for a row that is no group's means nothing

``jax.lax.ragged_dot`` is the same contract and this kernel's jnp/XLA
reference (``grouped_gemm_reference``).

The rows are walked in tiles of ``tm`` and a grid step is a *visit*: a
(row tile, group) pair that overlaps, in sorted order, so a tile a group
boundary cuts is visited once a group and a group over several tiles
once a tile.  Only overlapping pairs are visited (the visit count is the
grid's dynamic extent): a tile behind the last group's end is never
read, multiplied or written.  A visit multiplies the whole tile with the
group's ``(K, tn)`` column block, ``COL_CHUNK`` columns a product, the
contraction whole in each (no accumulator, nothing summed from step to
step), and stores the rows that are the group's; the tile's first visit
zeroes the rest.

**Weight traffic is the hit experts' bytes, once.**  The grid is
(column blocks, visits), visits inner, and the groups of consecutive
visits never decrease.  The matrices stay in HBM; a group's ``(K, tn)``
column block is copied by hand into one half of a double buffer, asked
for at the FIRST visit of the group before it and waited for at its
own first visit, so the copy runs under all of the earlier group's
visits (the last group of a column block asks for the first of the
next block).  An expert no row chose is no visit's group and is not
read.  Left to the grid's own pipeline (a ``BlockSpec`` on the group's
index, the first form this file had) the block is asked for one
*visit* ahead: a group of two visits then hides one visit's arithmetic
of its successor's 31 us copy and pays the other, 0.79 ms a GEMM where
this form reads 0.67 (K-EXAONE shape, PERF.md section 6, PR 47).  What
is read again is the row tile, once a column block: ``N / tn x visits
x tm x K`` bytes, which is why ``tn`` is the widest that the residency
allows (``col_tile``), and ``tm`` is ``ROW_TILE``, near the rows a
group holds: a visit multiplies ``tm`` rows whatever part of them is the
group's.

``gate_up`` is the same walk with two matrices a visit: it reads the
tile once for both and writes ``silu(g) * u`` rounded to the rows'
dtype, the ``h`` of a SwiGLU, where two calls would write two float32
arrays for XLA to read back.  ``up`` is the walk with ONE matrix whose
epilogue squares the rectified product: the ``h`` of an expert of two
matrices, ``relu(xs @ w_up)^2`` (Nemotron-H's ``relu2``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas import expert_acts

_F32 = jnp.float32
LANES = 128
# what the operand blocks may hold, double buffers counted (a v5e core
# has 128 MiB of VMEM; the rest is the products' float32 values)
VMEM_BUDGET = 56 * 1024 * 1024
VMEM_LIMIT = 100 * 1024 * 1024
# Rows a visit multiplies: the MXU's own.  A visit multiplies the whole
# tile whatever part of it is the group's, so a tile wider than the
# rows a group holds multiplies mostly its neighbours' (256 rows: +0 to
# 18% under the cells' skew, 512: x 1.5 to 1.9), and one narrower than
# the MXU leaves it idle (32: up to +30% at 4,096 rows).  Swept on the
# chip at the three cells' expert shapes from 16 to 256 sorted rows a
# group: 64 and 128 read within 2% of each other and of the best
# everywhere, so the tile is one number and not a rule of rows over
# groups (PERF.md section 6, PR 47).
ROW_TILE = 128
# Columns one product of a visit covers; a visit loops over its block in
# chunks of this many.  The arithmetic and the kernel's time are the
# whole block's (SwiGLU 1.92 ms whole, 1.92 / 1.93 / 2.01 at 512 / 256 /
# 128 columns, K-EXAONE shape, 2,304 rows), but Mosaic unrolls a product
# over its MXU tiles, and a program holds a copy of the kernel a routed
# layer: at 512 a call's executable is 1.35 MB where the whole block's
# is 2.0, which a server pays for at every start, when its programs
# come back from the compile cache (``setup_s``), and the first compile
# halves (PERF.md section 6, PR 47).
COL_CHUNK = 512


def _resident(tm: int, k: int, tn: int, itemsize: int, mats: int) -> int:
    """Bytes of one visit's blocks, each double-buffered: the row tile,
    ``mats`` weight blocks, the output tile (float32 at most)."""
    return 2 * (tm * k * itemsize + mats * k * tn * itemsize + tm * tn * 4)


def col_tile(tm: int, k: int, n: int, itemsize: int, mats: int = 1) -> int:
    """Columns a visit produces: the widest divisor of ``n`` in whole
    128-lane tiles whose blocks stay under ``VMEM_BUDGET`` (0: none)."""
    for parts in range(1, n // LANES + 1):
        tn = n // parts
        if n % parts == 0 and tn % LANES == 0 and _resident(
                tm, k, tn, itemsize, mats) <= VMEM_BUDGET:
            return tn
    return 0


def col_chunk(tn: int) -> int:
    """Columns a product covers: the widest divisor of the block's
    ``tn`` in whole 128-lane tiles up to ``COL_CHUNK``."""
    return next(c for c in range(min(tn, COL_CHUNK), 0, -LANES)
                if tn % c == 0)


def fits(dtype, w_dtype, rows: int, k: int, n: int) -> bool:
    """Rows and matrices of one dtype (bfloat16 or float32), ``k`` and
    ``n`` whole 128-lane tiles, a column block that stays resident, and
    rows that are whole row tiles.  A call of fewer rows than one tile
    (a step of a few slots) is ``ragged_dot``'s by this rule: at 32
    sorted rows of which 4 are some group's the kernel read 0.182 ms a
    GEMM beside ``ragged_dot``'s 0.178, from 256 rows up it reads 0.5
    to 0.8 of it (K-EXAONE shape; PERF.md section 6, PR 47)."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.dtype(w_dtype) or dtype not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return False
    return (k % LANES == 0 and n % LANES == 0
            and rows > 0 and rows % ROW_TILE == 0
            and col_tile(ROW_TILE, k, n, dtype.itemsize, 2) > 0)


def grouped_gemm_reference(xs, w, sizes):
    """The contract above in XLA: the oracle, and the path off a TPU."""
    return jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=_F32)


@functools.partial(jax.jit, static_argnames=("rows", "tm"))
def visits(sizes, rows: int, tm: int):
    """The walk over ``rows`` sorted rows in tiles of ``tm``: for each
    visit its row tile, its group and the next group that has rows (-1
    behind the last), padded to the most visits there can be (``rows /
    tm + C - 1``) with the last real one; each group's first row and
    the row behind its last; and the number of visits.  One walk serves
    every call over the same rows and sizes (a SwiGLU's two); written
    in few operations, and a group found by counting and not by a
    search, because a prefill program pays for its tracing and lowering
    at every start of the server (``setup_s``)."""
    C = sizes.shape[0]
    ends = jnp.minimum(jnp.cumsum(sizes.astype(jnp.int32)), rows)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // tm
    tiles = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)                 # visits up to a group's last
    count = upto[-1]

    def group_at(v):                         # (n,) visits -> their groups
        return jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                                   dtype=jnp.int32), C - 1)

    v = jnp.minimum(jnp.arange(rows // tm + C - 1, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = group_at(v)
    tile = jnp.clip(first[group] + v - (upto[group] - tiles[group]),
                    0, rows // tm - 1)
    # the next group's first visit is the one behind this group's last
    then = jnp.where(upto < count, group_at(upto), -1)[group]
    return tile, group, then, starts, ends, count


def _kernel(tile_of, group_of, next_of, starts, ends, x_ref, *refs, tm, tn,
            mats, precision, act):
    """One visit.  ``x_ref`` (tm, K), the visit's row tile; ``mats``
    stacks of matrices (C, K, N) in HBM; the output tile (tm, tn); a
    double buffer (2, K, tn) a stack, a DMA semaphore a buffer, and in
    SMEM the half that holds the visit's group.  ``act``: what is
    written of the visit's float32 products (one a stack), or None for
    the one product as it is."""
    w_hbm, o_ref = refs[:mats], refs[mats]
    bufs, sems, half = refs[mats + 1:2 * mats + 1], refs[-2], refs[-1]
    n, v = pl.program_id(0), pl.program_id(1)
    t, g = tile_of[v], group_of[v]

    def copies(group, col, slot):
        at = pl.ds(pl.multiple_of(col * tn, LANES), tn)
        return [pltpu.make_async_copy(w.at[group, :, at], buf.at[slot],
                                      sems.at[i, slot])
                for i, (w, buf) in enumerate(zip(w_hbm, bufs))]

    very_first = (n == 0) & (v == 0)
    new_group = (v == 0) | (group_of[jnp.maximum(v - 1, 0)] != g)

    @pl.when(very_first)
    def _first():
        half[0] = 1                            # flipped to 0 just below
        for c in copies(g, 0, 0):
            c.start()

    @pl.when(new_group)
    def _next():
        # the group's block was asked for a whole group ago: at the first
        # visit of the group before it (or of the last group of the
        # column block before this one)
        slot = 1 - half[0]
        half[0] = slot
        for c in copies(g, n, slot):
            c.wait()
        then = next_of[v]
        more = then >= 0

        # the next group of this column block, or behind the last the
        # first group of the next block
        @pl.when(more | (n + 1 < pl.num_programs(0)))
        def _ask():
            for c in copies(jnp.where(more, then, group_of[0]),
                            jnp.where(more, n, n + 1), 1 - slot):
                c.start()

    slot = half[0]
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= starts[g]) & (row < ends[g])
    first_visit = (v == 0) | (tile_of[jnp.maximum(v - 1, 0)] != t)
    tc = col_chunk(tn)

    def columns(j, carry):
        at = pl.ds(pl.multiple_of(j * tc, LANES), tc)
        y = [jnp.dot(x_ref[...], buf[slot, :, at],
                     preferred_element_type=_F32, precision=precision)
             for buf in bufs]
        y = y[0] if act is None else act(*y)
        kept = jnp.where(first_visit, 0, o_ref[:, at])
        o_ref[:, at] = jnp.where(mine, y.astype(o_ref.dtype), kept)
        return carry

    jax.lax.fori_loop(0, tn // tc, columns, 0)


def _plan(xs, ws, sizes, walk, out_dtype, tm, tn, interpret, act=None):
    """The kernel of one call, ``pl.pallas_call``'s other arguments and
    the call's operands."""
    M, K = xs.shape
    C, _, N = ws[0].shape
    itemsize = jnp.dtype(xs.dtype).itemsize
    tm = tm or min(ROW_TILE, M)
    tn = tn or col_tile(tm, K, N, itemsize, len(ws))
    *walk, count = walk or visits(sizes, M, tm)
    # float32 operands at full precision, as the reference multiplies
    # them: the default is one bfloat16 pass on a TPU
    precision = (jax.lax.Precision.HIGHEST if xs.dtype == _F32 else None)
    kernel = functools.partial(_kernel, tm=tm, tn=tn, mats=len(ws),
                               precision=precision, act=act)
    kwargs = dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,    # the walk, in SMEM
            grid=(N // tn, count),
            in_specs=[pl.BlockSpec((tm, K),
                                   lambda n, v, tile, *_: (tile[v], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(ws),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, tile, *_: (tile[v], n)),
            scratch_shapes=[pltpu.VMEM((2, K, tn), xs.dtype) for _ in ws]
            + [pltpu.SemaphoreType.DMA((len(ws), 2)),
               pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        # the buffers' state passes from visit to visit and from column
        # block to column block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N * len(ws), transcendentals=0,
            bytes_accessed=(len(ws) * C * K * N * itemsize
                            + (N // tn) * M * K * itemsize
                            + M * N * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret)
    return kernel, kwargs, (*walk, xs, *ws)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def grouped_gemm(xs, w, sizes, *, walk=None, tm: int = 0, tn: int = 0,
                 interpret: bool = False):
    """The Pallas call (the contract at the top of the file); ``tm``,
    ``tn`` 0: ``ROW_TILE`` and ``col_tile`` of the shape; ``walk``:
    ``visits(sizes, rows, tm)`` where the caller has it already."""
    kernel, kwargs, operands = _plan(xs, (w,), sizes, walk, _F32, tm, tn,
                                     interpret)
    return pl.pallas_call(kernel, name="grouped_gemm", **kwargs)(*operands)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def gate_up(xs, w_gate, w_up, sizes, *, walk=None, tm: int = 0, tn: int = 0,
            interpret: bool = False):
    """``silu(xs @ w_gate[g]) * (xs @ w_up[g])`` over the same walk,
    both products float32, the result rounded to the rows' dtype."""
    kernel, kwargs, operands = _plan(xs, (w_gate, w_up), sizes, walk,
                                     xs.dtype, tm, tn, interpret,
                                     expert_acts.swiglu)
    return pl.pallas_call(kernel, name="grouped_gemm_gate_up",
                          **kwargs)(*operands)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def up(xs, w_up, sizes, *, walk=None, tm: int = 0, tn: int = 0,
       interpret: bool = False):
    """``relu(xs @ w_up[g])^2`` over the same walk, the product float32,
    the result rounded to the rows' dtype."""
    kernel, kwargs, operands = _plan(xs, (w_up,), sizes, walk, xs.dtype,
                                     tm, tn, interpret, expert_acts.relu2)
    return pl.pallas_call(kernel, name="grouped_gemm_up",
                          **kwargs)(*operands)
