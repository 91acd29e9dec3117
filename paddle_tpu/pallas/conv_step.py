"""A decode step's depthwise causal conv over the slots' tails
(``decode/state_entry.py:step_conv``), for ONE recurrent layer, as one
Pallas call over the tail pool where it lies.

A sibling of ``ssd_step.py`` and ``gated_delta.py`` on their pattern:
the pool seen flat, each slot's entry index *scalar-prefetched*, the
pool's in- and out-``BlockSpec`` picking the slot's entry straight from
it, the pool aliased input to output.  So an entry moves HBM -> VMEM ->
HBM once, a slot a grid step, the pipeline fetching the next entry
under this one's arithmetic, and every entry no slot addresses is
untouched.  Both hybrids' steps call it: Granite's mamba layers (4,352
channels, a bias) and Olmo-Hybrid's linear layers (11,520, none).

**The layout: an entry is its kept rows one after another in rows of
128 lanes**, ``((taps - 1) * C / 128, 128)`` in the weights' dtype
(``state_entry.tail_shape``).  A block of one entry is then the pool's
two minor dimensions whole, which a ``BlockSpec`` may take whatever
their size; an entry a ROW of a layer's slab (Granite's first layout,
``(65, 13,056)`` bfloat16) is no whole tile and cannot be picked, and
``(taps - 1, C)`` would pad three rows to a tile of sixteen.  The bytes
are the rows' own: nothing is padded in the shape, and the chip rounds
an entry's rows up to eight (102 -> 104, 270 -> 272).  Tap ``j`` is
rows ``j C / 128 ..`` of the block, at no tile's edge for either model
(34 and 90 rows a tap): Mosaic shifts the sublanes, a few vregs a slot.

Per slot, float32 multiply-adds on rows kept in the weights' dtype, tap
0 first as ``step_conv`` sums them: ``out = act(sum_j w[j] rows[j] (+
b))`` (``act`` SiLU in front of a recurrence, the caller's default;
none for a gated short conv, ``models/lfm2_moe.py``, whose entries are
its whole state: 32 rows of lanes at 2,048 channels) over ``rows = [the entry's taps - 1 rows; the step's row]``, and
the entry written back as ``rows[1:]``.

Slots seated nowhere all address the null entry 0 (``gated_delta.py``
says why that harms nobody).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas.gated_delta import LANES

_F32 = jnp.float32
SILU = "silu"


def activate(acc, activation):
    """What a conv does to its sum: ``"silu"``, or nothing (None)."""
    if activation is None:
        return acc
    if activation != SILU:
        raise ValueError(f"a conv's activation is {SILU!r} or None, not "
                         f"{activation!r}")
    return jax.nn.silu(acc)


def fits(pool_dtype, entry_shape, row_dtype, taps: int,
         channels: int) -> bool:
    """Entries ``((taps - 1) * channels / 128, 128)`` of whole lanes, in
    the dtype of the step's rows (what is kept is what was seen)."""
    return (channels % LANES == 0 and taps >= 2
            and jnp.dtype(pool_dtype) == jnp.dtype(row_dtype)
            and tuple(entry_shape) == ((taps - 1) * channels // LANES,
                                       LANES))


def _kernel(at_ref, row_ref, w_ref, *refs, taps, R, activation):
    """One slot.  ``row_ref``, ``out_ref`` (1, R, 128), R rows of lanes
    a tap; ``w_ref`` (taps, R, 128); then ``b_ref`` (R, 128) where the
    conv has a bias; ``pool_ref``, ``new_ref`` (1, (taps - 1) * R,
    128), the slot's entry."""
    *bias, pool_ref, out_ref, new_ref = refs
    row = row_ref[0]
    acc = None
    for j in range(taps - 1):
        term = (pool_ref[0, j * R:(j + 1) * R, :].astype(_F32)
                * w_ref[j].astype(_F32))
        acc = term if acc is None else acc + term
    acc = acc + row.astype(_F32) * w_ref[taps - 1].astype(_F32)
    for b_ref in bias:
        acc = acc + b_ref[...].astype(_F32)
    out_ref[0] = activate(acc, activation)
    if taps > 2:
        new_ref[0, :(taps - 2) * R, :] = pool_ref[0, R:, :]
    new_ref[0, (taps - 2) * R:, :] = row


def conv_step(pool, at, row, w, b=None, activation=SILU,
              interpret: bool = False):
    """``pool`` (entries, (taps - 1) * C / 128, 128); ``at`` (S,) the
    entry of each slot; ``row`` (S, C) the step's rows, in the pool's
    dtype; ``w`` (taps, C); ``b`` (C,) or None; ``activation``
    ``"silu"`` or None (static) -> (out (S, C) float32 = ``act(sum_j
    w[j] rows[j] (+ b))``, the pool with the S entries moved on one
    row).  The pool is aliased input to output: donate it."""
    taps, C = w.shape
    S, R = at.shape[0], C // LANES

    def by_lanes(v):             # (..., C) -> (..., R, 128)
        return v.reshape(v.shape[:-1] + (R, LANES))

    slot = pl.BlockSpec((1, R, LANES), lambda s, *_: (s, 0, 0))
    entry = pl.BlockSpec((1,) + pool.shape[1:],
                         lambda s, at: (at[s], 0, 0))
    shared = [pl.BlockSpec((taps, R, LANES), lambda s, *_: (0, 0, 0))]
    operands = [by_lanes(w)]
    if b is not None:
        shared.append(pl.BlockSpec((R, LANES), lambda s, *_: (0, 0)))
        operands.append(by_lanes(b))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the slots' entries, in SMEM
        grid=(S,),
        in_specs=[slot, *shared, entry],
        out_specs=[slot, entry],
    )
    out, pool = pl.pallas_call(
        functools.partial(_kernel, taps=taps, R=R, activation=activation),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, R, LANES), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool, the last operand (after the prefetched entries, the
        # rows, the taps and the bias if there is one), is output 1
        input_output_aliases={2 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="conv_step",
        interpret=interpret,
    )(at.astype(jnp.int32), by_lanes(row), *operands, pool)
    return out.reshape(S, C), pool
