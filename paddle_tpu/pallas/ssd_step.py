"""Mamba-2's (SSD) one-token state update over a decode step's slots
(``models/granite_hybrid.py:step_ssd``), for ONE state-space layer, as
one Pallas call over the state pool where it lies.

A sibling of ``gated_delta.py`` on the same pattern, not a second rule
of that kernel: the pool seen flat, each slot's entry index
*scalar-prefetched*, the pool's in- and out-``BlockSpec`` picking the
slot's entry straight from it, the pool aliased input to output.  So an
entry moves HBM -> VMEM -> HBM once, ``hb`` rows of heads a grid step,
and every entry no slot addresses is untouched.  What differs is the
rule, and with it the layout.  There is no delta term (nothing is read
from the state before it is written), and ``B`` and ``C`` are one row a
slot a GROUP of heads (one group, shared by all the heads: Granite;
eight, heads ``8g .. 8g + 7`` on ``B_g`` and ``C_g``: Nemotron-H),
where the delta rule has a key and a query a head.

**The layout: the state size down the rows, the heads' channels along
the lanes.**  An entry is ``(H / pack, N, pack * P)`` float32: ``pack``
heads of ``P`` channels side by side in a row of whole lanes (2 at the
published 64), the state index ``n`` down the sublanes.  Then ``dt x``
and the decay arrive, and ``y`` leaves, along the lanes as they lie in
the step's rows (no turn in VMEM), the write is ``B``'s column times
``dt x``'s row, and ``y = S_new C`` reduces over the ROWS of the block:
vector adds and one sublane reduce a row of heads, where the state size
along the lanes would cost a lane reduction a tile (the delta rule's
kernel pays those: 8 a head there).  ``B`` and ``C`` are turned to
columns once a group a grid step: a block of rows of heads is whole
groups (16 rows of the published 32 against 4 rows a group of eight),
or lies inside one.

Per row of heads, in float32 and in ``step_ssd``'s order: the block
written back as ``a S + B (dt x)^T`` and ``y = S_new C`` from that one
pass over it in VMEM.  ``dt x``, the decay ``a`` spread over its head's
lanes and the skip ``D x`` are the caller's (a few thousand numbers a
slot).

Slots seated nowhere all address the null entry 0 (``gated_delta.py``
says why that harms nobody).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas.gated_delta import LANES, SUBLANES, head_block

_F32 = jnp.float32


def fits(state_dtype, rows: int, d_state: int, lanes: int,
         groups: int = 1) -> bool:
    """Float32 entries ``(rows, d_state, lanes)`` whose rows of heads
    are whole lanes and whose state size is whole tiles of 8, in blocks
    of ``head_block`` rows of heads (``BLOCK_BYTES``) that are whole
    groups of ``rows / groups`` rows of heads or lie inside one."""
    hb = head_block(rows, d_state, lanes)
    return (jnp.dtype(state_dtype) == _F32 and lanes % LANES == 0
            and d_state % SUBLANES == 0 and hb is not None
            and rows % groups == 0
            and (hb % (rows // groups) == 0 or (rows // groups) % hb == 0))


def _kernel(at_ref, a_ref, x_ref, b_ref, c_ref, pool_ref, y_ref, out_ref,
            *, hb, per_group):
    """One (slot, block of rows of heads) grid step.  ``a_ref``,
    ``x_ref``, ``y_ref`` (1, 1, hb, lanes); ``b_ref``, ``c_ref`` (1, 8 x
    the block's groups, N), each group's row of the slot eight times;
    ``pool_ref``, ``out_ref`` (1, hb, N, lanes), the slot's entry.
    ``per_group``: the rows of heads that read one group's B and C."""
    shape = pool_ref.shape[2:]                              # (N, lanes)
    one = b_ref.shape[1] == SUBLANES      # the block reads one group

    def column(ref, group):                                 # B[n] a row
        rows = ref[0] if one else ref[0, pl.ds(group * SUBLANES, SUBLANES)]
        return jnp.broadcast_to(rows.T[:, :1], shape)

    for h in range(hb):
        if h % per_group == 0:
            group = h // per_group
            B, C = column(b_ref, group), column(c_ref, group)
        new = (a_ref[0, 0, h:h + 1, :] * pool_ref[0, h]
               + B * x_ref[0, 0, h:h + 1, :])
        out_ref[0, h] = new
        y_ref[0, 0, h:h + 1, :] = jnp.sum(new * C, axis=0, keepdims=True)


def ssd_step(pool, at, a, x, B, C, interpret: bool = False):
    """``pool`` (entries, R, N, lanes) float32, R rows of heads; ``at``
    (S,) the entry of each slot; ``a`` (S, R, lanes) the decay, each
    head's over its own lanes; ``x`` (S, R, lanes), already times
    ``dt``; ``B``, ``C`` (S, N), or (S, G, N) a group of ``R / G`` rows
    of heads -> (y (S, R, lanes) = S_new C, the pool
    with the S entries advanced one row).  The pool is aliased input to
    output: donate it."""
    _, R, N, lanes = pool.shape
    S = at.shape[0]
    hb = head_block(R, N, lanes)
    blocks = R // hb
    G = 1 if B.ndim == 2 else B.shape[1]
    per_group = R // G                  # rows of heads on one B and C
    gb = max(hb // per_group, 1)        # groups a block of rows reads

    def by_block(v):            # (S, R, lanes) -> (S, R / hb, hb, lanes)
        return v.astype(_F32).reshape(S, blocks, hb, lanes)

    def eight(v):     # (S, [G,] N) -> (S, 8 G, N): a tile to turn a group
        v = v.astype(_F32)
        if G == 1:
            return jnp.broadcast_to(v[:, None], (S, SUBLANES, N))
        return jnp.broadcast_to(v[:, :, None], (S, G, SUBLANES, N)).reshape(
            S, G * SUBLANES, N)

    rows = pl.BlockSpec((1, 1, hb, lanes), lambda s, j, *_: (s, j, 0, 0))
    # block j's rows of heads start at j * hb: its groups, gb a block
    shared = pl.BlockSpec(
        (1, gb * SUBLANES, N),
        lambda s, j, *_: (s, j * hb // (per_group * gb) if G > 1 else 0, 0))
    entry = pl.BlockSpec((1, hb, N, lanes),
                         lambda s, j, at, *_: (at[s], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the slots' entries, in SMEM
        grid=(S, blocks),
        in_specs=[rows, rows, shared, shared, entry],
        out_specs=[rows, entry],
    )
    y, pool = pl.pallas_call(
        functools.partial(_kernel, hb=hb, per_group=min(per_group, hb)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, blocks, hb, lanes), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (the pool, after the prefetched entries and a, x, B,
        # C) is output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssd_step",
        interpret=interpret,
    )(at.astype(jnp.int32), by_block(a), by_block(x), eight(B), eight(C),
      pool)
    return y.reshape(S, R, lanes), pool
