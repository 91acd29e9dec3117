"""The gated delta rule's one-token step over a decode step's slots
(``models/olmo_hybrid.py:step_gated_delta``), for ONE linear layer, as
one Pallas call over the state pool where it lies.

The pool is seen flat, ``(entries, H, d_v, wide)`` float32, and each
slot's entry index is *scalar-prefetched* (the page tables' pattern,
``decode/attention.py``): the pool's in- and out-``BlockSpec`` pick the
slot's entry straight from it, and the pool is aliased input to output.
So an entry moves HBM -> VMEM -> HBM once, ``hb`` heads a grid step, the
pipeline fetching the next block under this one's arithmetic, and every
entry no slot addresses is untouched.

Per head, in float32 and in ``step_gated_delta``'s order: ``S k`` and
``S q`` from one pass over the block in VMEM (multiply, reduce along
the lanes), ``u = beta (v - alpha S k)``, the block written back as
``alpha S + u k^T`` and ``o = alpha S q + u (k.q)``.  ``S k``, ``u``
and ``o`` run along the block's rows (sublanes) where ``v`` arrives and
``o`` leaves along lanes: the two are turned in VMEM, ``hb`` heads at a
time.

Slots seated nowhere all address the null entry 0: several grid steps
then read and write one block, and a fetch may precede the write before
it.  Entry 0 alone is affected; no live slot reads it (a seated
sequence's entry is written whole by its prefill), and the rows ``o``
of a slot that is not live are read by nobody (``session._decide``
indexes live slots only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
LANES, SUBLANES = 128, 8
# A grid step's block of the pool: in and out, each double-buffered, is
# four of these in VMEM beside the 16 MiB a kernel may use by default.
BLOCK_BYTES = 3 << 19


def head_block(heads: int, d_v: int, wide: int,
               block_bytes: int = BLOCK_BYTES):
    """Heads a grid step takes: the most that divide ``heads`` and keep
    the block within ``block_bytes`` (15 of 30 at (192, 128): 1.47 MB);
    None where one head is already over it."""
    fit = [hb for hb in range(1, heads + 1)
           if heads % hb == 0 and hb * d_v * wide * 4 <= block_bytes]
    return max(fit, default=None)


def fits(state_dtype, heads: int, d_v: int, wide: int,
         block_bytes: int = BLOCK_BYTES) -> bool:
    """Float32 entries whose rows are whole lanes (``wide``, the key
    width as stored, a multiple of 128) and whose ``d_v`` rows are whole
    tiles of 8, in blocks of ``head_block`` heads."""
    return (jnp.dtype(state_dtype) == _F32 and wide % LANES == 0
            and d_v % SUBLANES == 0
            and head_block(heads, d_v, wide, block_bytes) is not None)


def _kernel(at_ref, alpha_ref, beta_ref, q_ref, k_ref, v_ref, pool_ref,
            o_ref, out_ref, o_cols, *, heads, hb):
    """One (slot, head block) grid step.  ``alpha_ref``, ``beta_ref``:
    (S * H,) in SMEM; ``q_ref``, ``k_ref`` (1, 1, hb, wide); ``v_ref``,
    ``o_ref`` (1, 1, hb, d_v); ``pool_ref``, ``out_ref`` (1, hb, d_v,
    wide), the slot's entry; ``o_cols`` (d_v, hb) scratch."""
    first = pl.program_id(0) * heads + pl.program_id(1) * hb
    v_cols = v_ref[0, 0].T                                  # (d_v, hb)
    for h in range(hb):
        alpha, beta = alpha_ref[first + h], beta_ref[first + h]
        state = pool_ref[0, h]                              # (d_v, wide)
        k = k_ref[0, 0, h:h + 1, :]                         # (1, wide)
        q = q_ref[0, 0, h:h + 1, :]
        Sk = jnp.sum(state * k, axis=-1, keepdims=True)     # (d_v, 1)
        Sq = jnp.sum(state * q, axis=-1, keepdims=True)
        u = beta * (v_cols[:, h:h + 1] - alpha * Sk)
        out_ref[0, h] = alpha * state + u * k
        o_cols[:, h:h + 1] = alpha * Sq + u * jnp.sum(
            k * q, axis=-1, keepdims=True)
    o_ref[0, 0] = o_cols[...].T


def gated_delta_step(pool, at, q, k, v, g, beta, interpret: bool = False):
    """``pool`` (N, H, d_v, wide) float32; ``at`` (S,) the entry of each
    slot; ``q``, ``k`` (S, H, wide), zero beyond the key width; ``v``
    (S, H, d_v); ``g`` (the log of the decay), ``beta`` (S, H) -> (o
    (S, H, d_v), the pool with the S entries advanced one row).  The
    pool is aliased input to output: donate it."""
    _, H, dv, wide = pool.shape
    S = at.shape[0]
    hb = head_block(H, dv, wide)
    blocks = H // hb

    def by_block(x):            # (S, H, w) -> (S, H / hb, hb, w)
        return x.astype(_F32).reshape(S, blocks, hb, x.shape[-1])

    def rows(w):
        return pl.BlockSpec((1, 1, hb, w), lambda s, j, *_: (s, j, 0, 0))

    entry = pl.BlockSpec((1, hb, dv, wide),
                         lambda s, j, at, *_: (at[s], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # entries, alpha and beta land in SMEM
        grid=(S, blocks),
        in_specs=[rows(wide), rows(wide), rows(dv), entry],
        out_specs=[rows(dv), entry],
        scratch_shapes=[pltpu.VMEM((dv, hb), _F32)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_kernel, heads=H, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, blocks, hb, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (the pool, after the three prefetched and q, k, v)
        # is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="gated_delta_step",
        interpret=interpret,
    )(at.astype(jnp.int32), jnp.exp(g).astype(_F32).reshape(-1),
      beta.astype(_F32).reshape(-1), by_block(q), by_block(k), by_block(v),
      pool)
    return o.reshape(S, H, dv), pool
