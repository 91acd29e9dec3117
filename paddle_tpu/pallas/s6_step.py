"""Mamba-1's (S6, the selective scan) one-token state update over a
decode step's slots (``models/phi4_flash.py:step_s6``), for ONE
state-space layer, as one Pallas call over the state pool where it
lies.

A sibling of ``ssd_step.py`` on its pattern, not a second rule of that
kernel: the pool seen flat, each slot's entry index *scalar-prefetched*,
the pool's in- and out-``BlockSpec`` picking the slot's entry straight
from it, the pool aliased input to output.  So an entry moves HBM ->
VMEM -> HBM once and every entry no slot addresses is untouched.  What
differs is the rule.  SSD decays a head's whole state by ONE scalar a
row, which arrives spread over the head's lanes; S6 decays every
(state index, channel) pair by its own ``exp(dt_c A_nc)``, ``dt`` a
vector over the channels: a slot's decay is as large as its state, so
it is made here, in VMEM, from the slot's ``dt`` row and the layer's
``A``, and never lies in HBM (64 slots' decays would be the bytes of
their states over again).

**The layout: the state index down the rows, the channels along the
lanes.**  An entry is ``(N, C)`` float32 (16 x 5,120 as published: two
sublane tiles, forty lane tiles, nothing padded).  Then ``dt``, ``dt
x`` arrive, and ``y`` leaves, along the lanes as they lie in the step's
rows, the write is ``B``'s column times ``dt x``'s row, and ``y = S_new
C`` reduces over the ROWS of the block.  ``B`` and ``C`` are turned to
columns once a grid step (``ssd_step.py`` says why eight copies).

Per block of channels, in float32 and in ``step_s6``'s order: ``S <-
exp(dt A) S + B (dt x)^T`` and ``y = S_new C`` from that one pass over
the block in VMEM.  ``dt x`` and the skip ``D x`` are the caller's.

Slots seated nowhere all address the null entry 0 (``gated_delta.py``
says why that harms nobody).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas.gated_delta import BLOCK_BYTES, LANES, SUBLANES

_F32 = jnp.float32


def channel_block(d_state: int, channels: int):
    """Channels a grid step takes: the most whole rows of lanes that
    divide ``channels`` and keep the block ``(d_state, cb)`` float32
    within ``BLOCK_BYTES`` (all 5,120 at a state of 16: 328 KB); None
    where one row of lanes is already over it."""
    fit = [cb for cb in range(LANES, channels + 1, LANES)
           if channels % cb == 0 and d_state * cb * 4 <= BLOCK_BYTES]
    return max(fit, default=None)


def fits(state_dtype, d_state: int, channels: int) -> bool:
    """Float32 entries ``(d_state, channels)`` of whole lanes whose
    state size is whole tiles of 8, in blocks of ``channel_block``."""
    return (jnp.dtype(state_dtype) == _F32 and channels % LANES == 0
            and d_state % SUBLANES == 0
            and channel_block(d_state, channels) is not None)


def _kernel(at_ref, dt_ref, x_ref, a_ref, b_ref, c_ref, pool_ref, y_ref,
            out_ref):
    """One (slot, block of channels) grid step.  ``dt_ref``, ``x_ref``,
    ``y_ref`` (1, 1, 1, cb); ``a_ref`` (N, cb), the layer's ``A``;
    ``b_ref``, ``c_ref`` (1, 8, N), the slot's row eight times;
    ``pool_ref``, ``out_ref`` (1, N, cb), the slot's entry."""
    shape = pool_ref.shape[1:]                              # (N, cb)
    B = jnp.broadcast_to(b_ref[0].T[:, :1], shape)          # B[n] a row
    C = jnp.broadcast_to(c_ref[0].T[:, :1], shape)
    new = (jnp.exp(dt_ref[0, 0] * a_ref[...]) * pool_ref[0]
           + B * x_ref[0, 0])
    out_ref[0] = new
    y_ref[0, 0] = jnp.sum(new * C, axis=0, keepdims=True)


def s6_step(pool, at, dt, x, A, B, C, interpret: bool = False):
    """``pool`` (entries, N, C) float32; ``at`` (S,) the entry of each
    slot; ``dt`` (S, C) the step's step sizes; ``x`` (S, C), already
    times ``dt``; ``A`` (N, C), negative; ``B``, ``C`` (S, N) -> (y (S,
    C) = S_new C, the pool with the S entries advanced one row).  The
    pool is aliased input to output: donate it."""
    _, N, ch = pool.shape
    S = at.shape[0]
    cb = channel_block(N, ch)
    blocks = ch // cb

    def by_block(v):            # (S, C) -> (S, C / cb, 1, cb)
        return v.astype(_F32).reshape(S, blocks, 1, cb)

    def eight(v):               # (S, N) -> (S, 8, N): a tile to turn
        return jnp.broadcast_to(v.astype(_F32)[:, None], (S, SUBLANES, N))

    rows = pl.BlockSpec((1, 1, 1, cb), lambda s, j, *_: (s, j, 0, 0))
    rates = pl.BlockSpec((N, cb), lambda s, j, *_: (0, j))
    shared = pl.BlockSpec((1, SUBLANES, N), lambda s, j, *_: (s, 0, 0))
    entry = pl.BlockSpec((1, N, cb), lambda s, j, at, *_: (at[s], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,        # the slots' entries, in SMEM
        grid=(S, blocks),
        in_specs=[rows, rows, rates, shared, shared, entry],
        out_specs=[rows, entry],
    )
    y, pool = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, blocks, 1, cb), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (the pool, after the prefetched entries and dt, x,
        # A, B, C) is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="s6_step",
        interpret=interpret,
    )(at.astype(jnp.int32), by_block(dt), by_block(x), A.astype(_F32),
      eight(B), eight(C), pool)
    return y.reshape(S, ch), pool
