"""Kimi Delta Attention (KDA, arXiv:2510.26692): the delta rule whose
decay is a VECTOR over a head's key channels, ``S <- S Diag(alpha_t)``
before the write, where ``gated_delta*.py``'s scale the whole state by
one number a head.  Two kernels, each ONE Pallas call a layer, siblings
of those two files (their layouts, their grids, their tiles):

``kda_step``: a decode step's slots on their state entries
(``models/ling_hybrid.py:step_kda``).  ``gated_delta.py``'s call to the
letter but for the decay, which arrives as a row of ``wide`` lanes a
head beside ``q`` and ``k`` (a block in VMEM) where a scalar stood in
SMEM: the pool seen flat ``(entries, H, d_v, wide)`` float32, each
slot's entry scalar-prefetched, read once and written once where it
lies, ``hb`` heads a grid step (all 32 at the cell's shape).

``kda_chunked``: a prefill bucket's rows, chunked
(``models/ling_hybrid.py:chunked_kda``).  ``gated_delta_chunked.py``'s
grid (blocks of heads x the bucket's chunks of ``CHUNK`` rows in order,
the state ``(d_k, d_v)`` a head in VMEM scratch from chunk to chunk)
and its solve (``_inverse_by_doubling``), under another algebra inside
the chunk.  With ``G_t`` the running sum of the log-decays from the
chunk's first row, a vector over the key channels, the in-chunk
matrices are

    A[t, s] = beta_t sum_c k_t[c] k_s[c] e^(G_t[c] - G_s[c])   (s < t)
    M[t, s] =        sum_c q_t[c] k_s[c] e^(G_t[c] - G_s[c])   (s <= t)

which factor through no scalar a row pair; as ``(k e^G)(k e^-G)^T``
the second factor leaves float32 once ``-G`` passes 88.  A token's
log-decay is bounded below (``kda_lower_bound`` -5: ``fits()`` is told
the bound), so both are built a block of ``SUB`` = 16 query rows at a
time against ONE reference row ``R`` (``G`` at the block's middle row):
``(x_t e^(G_t - R)) . (k_s e^(min(R - G_s, REACH)))``.  Inside the
block both exponents lie within ``8 x 5 = 40 = REACH`` of zero; for a
key row before the block ``R - G_s`` only falls; behind the query row
the product is masked, and the clamp keeps it finite.  (Against the row
BEFORE the block the factors span e^-80 .. e^80: in range, but e^-80
times a small entry of ``q`` is subnormal, is flushed, and a head at
the bound read 4e-3 off the recurrence; from the middle it reads 2e-7.)
Every kept term is at most ``|x||k|``: nothing large is cancelled.
Then, as the scalar rule's kernel,

    U = (I + A)^-1 (beta V - (beta K e^G) S_0^T)
    O = (Q e^G) S_0^T + M U
    S_C^T = e^(G_C) S_0^T + (K e^(G_C - G))^T U

all float32, every product at ``Precision.HIGHEST``.  ``beta`` reaches
the kernel inside ``beta k`` and ``beta v``; a row with ``g = 0, beta =
0`` (a bucket's padding) leaves the state as it was.  What a grid step
is handed (``_relayout``, in XLA beside the call): ``q``, ``beta k``,
``beta v`` and ``G`` head-major ``(hb, C, .)``, and ``k`` and ``G``
transposed ``(hb, d_k, C)``, so that every product is a plain ``A @ B``
and a reference row is a row of the one and a column of the other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas import gated_delta as _gd
from paddle_tpu.pallas.gated_delta import LANES, SUBLANES
from paddle_tpu.pallas.gated_delta_chunked import (VMEM_BYTES, _dot,
                                                   _inverse_by_doubling)

_F32 = jnp.float32
CHUNK = 128                      # rows a chunk (the solve rolls 128 lanes)
# A grid step's block of the step's pool, in and out, each double-buffered:
# four of these in VMEM.  All 32 heads of (128, 128) a step read 6% faster
# than ``gated_delta.BLOCK_BYTES``' 16 (0.911 / 0.972 ms a layer of 128
# slots where a kernel that only copies reads 0.877; PERF.md section 6,
# PR 55).
STEP_BLOCK_BYTES = 2 << 20
SUB = 16                         # query rows that share a reference row
REACH = 40.0                     # the most |R - G_s| inside a block of SUB
HEAD_BLOCK = 2                   # heads a grid step of the chunked call


# -- the step ----------------------------------------------------------------


def step_fits(state_dtype, heads: int, d_v: int, wide: int) -> bool:
    """``gated_delta.fits`` at this step's block: float32 entries of
    whole lanes and whole tiles of 8 rows, in blocks of heads within
    ``STEP_BLOCK_BYTES``."""
    return _gd.fits(state_dtype, heads, d_v, wide, STEP_BLOCK_BYTES)


def _step_kernel(at_ref, beta_ref, q_ref, k_ref, a_ref, v_ref, pool_ref,
                 o_ref, out_ref, o_cols, *, heads, hb):
    """One (slot, head block) grid step.  ``beta_ref`` (S * H,) in SMEM;
    ``q_ref``, ``k_ref``, ``a_ref`` (the decay) (1, 1, hb, wide);
    ``v_ref``, ``o_ref`` (1, 1, hb, d_v); ``pool_ref``, ``out_ref`` (1,
    hb, d_v, wide), the slot's entry; ``o_cols`` (d_v, hb) scratch."""
    first = pl.program_id(0) * heads + pl.program_id(1) * hb
    v_cols = v_ref[0, 0].T                                  # (d_v, hb)
    for h in range(hb):
        beta = beta_ref[first + h]
        alpha = a_ref[0, 0, h:h + 1, :]                     # (1, wide)
        k = k_ref[0, 0, h:h + 1, :]
        q = q_ref[0, 0, h:h + 1, :]
        state = pool_ref[0, h]                              # (d_v, wide)
        # S Diag(alpha) read with k and with q: the decay rides on the
        # two rows, and the block is multiplied by it once, as it leaves
        Sk = jnp.sum(state * (alpha * k), axis=-1, keepdims=True)
        Sq = jnp.sum(state * (alpha * q), axis=-1, keepdims=True)
        u = beta * (v_cols[:, h:h + 1] - Sk)                # (d_v, 1)
        out_ref[0, h] = state * alpha + u * k
        o_cols[:, h:h + 1] = Sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    o_ref[0, 0] = o_cols[...].T


def kda_step(pool, at, q, k, v, g, beta, interpret: bool = False):
    """``pool`` (N, H, d_v, wide) float32; ``at`` (S,) the entry of each
    slot; ``q``, ``k`` (S, H, wide); ``v`` (S, H, d_v); ``g`` (S, H,
    wide) the log of the decay a key channel; ``beta`` (S, H) -> (o (S,
    H, d_v), the pool with the S entries advanced one row).  The pool is
    aliased input to output: donate it."""
    _, H, dv, wide = pool.shape
    S = at.shape[0]
    hb = _gd.head_block(H, dv, wide, STEP_BLOCK_BYTES)
    blocks = H // hb

    def by_block(x):            # (S, H, w) -> (S, H / hb, hb, w)
        return x.astype(_F32).reshape(S, blocks, hb, x.shape[-1])

    def rows(w):
        return pl.BlockSpec((1, 1, hb, w), lambda s, j, *_: (s, j, 0, 0))

    entry = pl.BlockSpec((1, hb, dv, wide),
                         lambda s, j, at, *_: (at[s], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # entries and beta land in SMEM
        grid=(S, blocks),
        in_specs=[rows(wide), rows(wide), rows(wide), rows(dv), entry],
        out_specs=[rows(dv), entry],
        scratch_shapes=[pltpu.VMEM((dv, hb), _F32)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=H, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, blocks, hb, dv), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (the pool, after the two prefetched and q, k, the
        # decay, v) is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="kda_step",
        interpret=interpret,
    )(at.astype(jnp.int32), beta.astype(_F32).reshape(-1), by_block(q),
      by_block(k), by_block(jnp.exp(g)), by_block(v), pool)
    return o.reshape(S, H, dv), pool


# -- the chunked prefill -----------------------------------------------------


def head_block(heads: int) -> int:
    """Heads a grid step takes: the most that divide ``heads`` up to
    ``HEAD_BLOCK``."""
    return max(hb for hb in range(1, HEAD_BLOCK + 1) if heads % hb == 0)


def chunked_fits(state_dtype, rows: int, heads: int, d_v: int, d_k: int,
                 lower_bound: float) -> bool:
    """A float32 state, a bucket of whole chunks, keys of whole lanes
    (a reference row is a row of one block and a column of another),
    values in whole tiles of 8, and a log-decay bounded below so that
    half a block of ``SUB`` rows stays within ``REACH``."""
    return (jnp.dtype(state_dtype) == _F32 and heads > 0
            and rows > 0 and rows % CHUNK == 0
            and d_k % LANES == 0 and d_v % SUBLANES == 0
            and 0.0 <= -float(lower_bound) * (SUB // 2) <= REACH)


def _chunk_kernel(q_ref, kb_ref, vb_ref, G_ref, kT_ref, GT_ref, s0_ref,
                  o_ref, s_ref, state):
    """One (head block, chunk) grid step.  ``q_ref``, ``kb_ref`` (beta
    k), ``G_ref`` (hb, C, d_k); ``vb_ref`` (beta v), ``o_ref`` (hb, C,
    d_v); ``kT_ref``, ``GT_ref`` (hb, d_k, C); ``s0_ref``, ``s_ref``
    (hb, d_k, d_v), the state before row 0 and after the last,
    transposed; ``state`` the same shape, scratch."""
    c = pl.program_id(1)
    hb, C, dk = q_ref.shape
    dv = vb_ref.shape[2]

    @pl.when(c == 0)
    def _():
        state[...] = s0_ref[...]

    t = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    q, kb, G = q_ref[...], kb_ref[...], G_ref[...]
    kT, GT = kT_ref[...], GT_ref[...]

    A, M = [], []
    for lo in range(0, C, SUB):
        mid = lo + SUB // 2 - 1
        # Mosaic broadcasts along one axis at a time
        ref_row = jnp.broadcast_to(G[:, mid:mid + 1, :], (hb, SUB, dk))
        ref_col = jnp.broadcast_to(GT[:, :, mid:mid + 1], (hb, dk, C))
        from_ref = jnp.exp(G[:, lo:lo + SUB, :] - ref_row)
        to_ref = jnp.exp(jnp.minimum(ref_col - GT, REACH))
        both = _dot(jnp.concatenate([kb[:, lo:lo + SUB] * from_ref,
                                     q[:, lo:lo + SUB] * from_ref], axis=1),
                    kT * to_ref)                              # (hb, 2 SUB, C)
        A.append(both[:, :SUB])
        M.append(both[:, SUB:])
    A = jnp.where(t > s, jnp.concatenate(A, axis=1), 0.0)
    M = jnp.where(t >= s, jnp.concatenate(M, axis=1), 0.0)
    T = _inverse_by_doubling(A, t, s)
    S = state[...]                                            # (hb, dk, dv)
    e_G = jnp.exp(G)
    read = _dot(jnp.concatenate([kb * e_G, q * e_G], axis=1), S)
    U = _dot(T, vb_ref[...] - read[:, :C])                    # (hb, C, dv)
    o_ref[...] = read[:, C:] + _dot(M, U)
    G_end = GT[:, :, C - 1:C]                                 # (hb, dk, 1)
    e_end = jnp.exp(jnp.broadcast_to(G_end, (hb, dk, dv)))
    to_end = jnp.exp(jnp.broadcast_to(G_end, (hb, dk, C)) - GT)
    state[...] = e_end * S + _dot(kT * to_end, U)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = state[...]


def _relayout(q, k, v, g, beta, state):
    """(T, H, .) rows -> what the grid's blocks pick, head-major: q,
    beta k, beta v, the chunk's running log-decay ``G``; ``k`` and ``G``
    transposed; the state transposed."""
    T, H, dk = q.shape
    G = jnp.cumsum(g.reshape(T // CHUNK, CHUNK, H, dk), axis=1).reshape(
        T, H, dk)
    b = beta[..., None]

    def rows(a):
        return jnp.transpose(a, (1, 0, 2))

    def cols(a):
        return jnp.transpose(a, (1, 2, 0))

    return (rows(q), rows(b * k), rows(b * v), rows(G), cols(k), cols(G),
            jnp.swapaxes(state, 1, 2))


def kda_chunked(q, k, v, g, beta, state, interpret: bool = False):
    """``q``, ``k`` (T, H, d_k) normalised and scaled, ``v`` (T, H,
    d_v), ``g`` (T, H, d_k) the log of the decay a key channel, each at
    least ``-2 REACH / SUB``, ``beta`` (T, H), ``state`` (H, d_v, d_k) as
    it stood before row 0 -> (o (T, H, d_v), the state after row T -
    1): ``chunked_kda``'s contract.  Rows are padded to whole chunks
    with ``g = 0, beta = 0``."""
    T = q.shape[0]
    q, k, v, g, beta, state = (a.astype(_F32)
                               for a in (q, k, v, g, beta, state))
    pad = -T % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    o, new = _over_chunks(*_relayout(q, k, v, g, beta, state),
                          interpret=interpret)
    return jnp.transpose(o[:, :T], (1, 0, 2)), jnp.swapaxes(new, 1, 2)


def _over_chunks(q, kb, vb, G, kT, GT, state, interpret=False):
    """The call, on what ``_relayout`` hands it -> (o (H, T, d_v), the
    state (H, d_k, d_v), transposed as it came)."""
    H, T, dk = q.shape
    C, dv, hb = CHUNK, vb.shape[2], head_block(H)

    def rows(w):
        return pl.BlockSpec((hb, C, w), lambda i, c: (i, c, 0))

    cols = pl.BlockSpec((hb, dk, C), lambda i, c: (i, 0, c))
    whole = pl.BlockSpec((hb, dk, dv), lambda i, c: (i, 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        grid=(H // hb, T // C),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), cols, cols, whole],
        out_specs=[rows(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), _F32),
                   jax.ShapeDtypeStruct((H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        name="kda_chunked",
        interpret=interpret,
    )(q, kb, vb, G, kT, GT, state)
