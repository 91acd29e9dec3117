"""The gated delta rule over a prompt's rows, chunked
(``models/olmo_hybrid.py:chunked_gated_delta``), for ONE linear layer,
as one Pallas call: the prefill's side of ``gated_delta.py``.

Grid: blocks of ``hb`` heads x the bucket's chunks of ``CHUNK`` rows,
the chunks innermost and in order.  A head's state lives in a VMEM
scratch across its chunks, transposed ``(d_k, d_v)`` float32 (keys down
the rows, so that every product below is a plain ``A @ B``): loaded at
chunk 0, written out once after the last chunk.  The pipeline fetches
chunk c + 1's rows under chunk c's arithmetic.

What a grid step is handed, per head (``_relayout``, in XLA beside the
call and under the caller's scope, head-major at the widths the rows
have: nothing is padded): ``q`` (C, d_k); ``v`` (C, d_v); and ``kT``
(wide, C), the keys transposed, keys down the rows, and in the two rows
after them the chunk's running log decay ``G`` and ``beta`` (``wide`` is
``d_k + 2`` rounded up to 128: the rows a 96-wide key leaves of a
square tile).  ``k`` itself, and ``G`` and ``beta`` down a column, are
that block turned in VMEM (the compiler lays the projected keys out
transposed already: one pass writes ``kT``, and ``k`` costs no second
one).

In a chunk, in VMEM, ``chunked_gated_delta``'s mathematics: the decay
matrix ``D = exp(G_t - G_s)`` on and below the diagonal (never the two
factors apart: ``exp(-G_s)`` overflows a fast-decaying head), ``k k^T``
and ``q k^T`` as one product, ``A = beta D k k^T`` strictly below,
then

**the unit-lower solve as an inverse built by doubling**, no row loop:
with ``T`` the inverse of ``I + A``'s diagonal blocks of ``b`` rows,
the inverse of its blocks of ``2b`` is ``T - T A_off T``, ``A_off``
the lower-left ``b x b`` corner of each; ``b`` = 1 (``T = I``, so the
first level is ``I - A_off``), 2 (two rolls on the VPU), 4 ... C / 2:
two (C, C) products a level (``_inverse_by_doubling``).  Every
intermediate is the true inverse of a sub-block, bounded as the
recurrence itself is (a write's transition ``I - beta k k^T`` has norm
<= 1 for beta <= 2): nothing grows to be cancelled.  The finite product
``(I - A)(I + A^2)..(I + A^(C/2))`` is not so: with equal keys and beta
= 2 (a run of one token) ``A^32`` has entries of 1e27 that must cancel
to a bounded answer; forward substitution on 16-row blocks needs a row
loop over strided sublanes that the VPU does badly.  An inverse times a
right hand side is still not backward stable as substitution is: on
that worst case the kernel reads 5e-5 of the largest value where the
XLA form's solve reads 5e-6 (``tests/test_gated_delta_chunked.py``); on
drawn rows the two agree to 8e-7 and read the same 8e-6 against a
float32 recurrence (on the chip, PERF.md section 6).

Then ``U = T (beta V - beta e^G K S_0^T)``, ``O = e^G Q S_0^T + (D q
k^T) U`` and ``S_C^T = e^(G_C) S_0^T + (e^(G_C - G) K)^T U``.  All
float32, every product at ``Precision.HIGHEST`` (Mosaic's
``contract_precision<fp32>``), as the XLA form.  A row with ``g = 0,
beta = 0`` (a bucket's padding) leaves the state as it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.pallas.gated_delta import LANES, SUBLANES

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 128                      # rows a chunk: kT's block is (wide, CHUNK)
AUX = 2                          # rows after the keys: G and beta
HEAD_BLOCK = 3                   # heads a grid step, at most
VMEM_BYTES = 32 << 20


def key_width(d_k: int) -> int:
    """The rows of ``kT``: ``d_k`` and the two scalars' after them,
    rounded up to 128 (96 -> 128), so that the block turns as whole
    tiles."""
    return -(-(int(d_k) + AUX) // LANES) * LANES


def head_block(heads: int) -> int:
    """Heads a grid step takes: the most that divide ``heads`` up to
    ``HEAD_BLOCK`` (3 of 30)."""
    return max(hb for hb in range(1, HEAD_BLOCK + 1) if heads % hb == 0)


def fits(state_dtype, rows: int, heads: int, d_v: int, d_k: int) -> bool:
    """A float32 state, a bucket of whole chunks, keys and values in
    whole tiles of 8 (the keys run down the rows of ``kT`` and of the
    state; every row dtype is cast)."""
    return (jnp.dtype(state_dtype) == _F32 and heads > 0
            and rows > 0 and rows % CHUNK == 0
            and d_k % SUBLANES == 0 and d_v % SUBLANES == 0)


def _dot(a, b):
    """(hb, m, k) @ (hb, k, n), a head at a time, float32 at six
    passes."""
    return jnp.einsum("hmk,hkn->hmn", a, b, precision=_HIGHEST,
                      preferred_element_type=_F32)


def _inverse_by_doubling(A, t, s):
    """``(I + A)^-1`` for ``A`` (hb, C, C) strictly lower, ``t``, ``s``
    its row and column indices: the inverses of the diagonal blocks of
    b rows doubled level by level, ``T <- T - T A_off T`` with ``A_off``
    the lower-left b x b corner of each block of 2b.  b = 1: ``T = I``,
    so the level is ``I - A_1``.  b = 2 on the VPU: ``T = I - A_1`` has
    one entry a row beside its diagonal, so ``T X`` and ``X T`` are
    ``X`` less a rolled ``X`` times that entry down the rows, resp.
    along the columns (on the chip the kernel is 8% shorter so than
    with two more products).  From b = 4 two products a level."""
    C = A.shape[1]

    def corner(lb):
        tb, sb = t >> lb, s >> lb
        return jnp.where(((tb ^ sb) == 1) & ((tb & 1) == 1), A, 0.0)

    A1, X = corner(0), corner(1)
    down = jnp.sum(A1, axis=2, keepdims=True)    # (hb, C, 1): A[t, t - 1]
    along = jnp.sum(A1, axis=1, keepdims=True)   # (hb, 1, C): A[s + 1, s]
    X = X - down * pltpu.roll(X, 1, 1)           # (I - A_1) X
    X = X - pltpu.roll(X, C - 1, 2) * along      # ... (I - A_1)
    T = (t == s).astype(_F32) - A1 - X
    for lb in range(2, C.bit_length() - 1):
        T = T - _dot(T, _dot(corner(lb), T))
    return T


def _kernel(q_ref, kT_ref, v_ref, s0_ref, o_ref, s_ref, state):
    """One (head block, chunk) grid step.  ``q_ref`` (hb, C, d_k);
    ``kT_ref`` (hb, wide, C); ``v_ref``, ``o_ref`` (hb, C, d_v);
    ``s0_ref``, ``s_ref`` (hb, d_k, d_v), the state before row 0 and
    after the last, transposed; ``state`` the same shape, scratch.

    The block's heads go through every product side by side (one
    batched ``dot`` a stage): a chunk is a chain of some twenty
    products, each waiting for the one before, and the MXU fills the
    wait for one head's with the others'."""
    c = pl.program_id(1)
    hb, C, dk = q_ref.shape
    dv = v_ref.shape[2]

    @pl.when(c == 0)
    def _():
        state[...] = s0_ref[...]

    t = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)

    kT = kT_ref[...]
    kx = jnp.swapaxes(kT, 1, 2)                                # (hb, C, wide)
    G_c, beta_c = kx[:, :, dk:dk + 1], kx[:, :, dk + 1:dk + 2]   # (hb, C, 1)
    G_r = kT[:, dk:dk + 1, :]                                    # (hb, 1, C)
    k, kT = kx[:, :, :dk], kT[:, :dk, :]
    q = q_ref[...]
    D = jnp.where(t >= s, jnp.exp(jnp.minimum(G_c - G_r, 0.0)), 0.0)
    both = _dot(jnp.concatenate([k, q], axis=1), kT)             # (hb, 2C, C)
    A = jnp.where(t > s, beta_c * D * both[:, :C], 0.0)
    M = D * both[:, C:]
    T = _inverse_by_doubling(A, t, s)
    S = state[...]                                               # (hb, dk, dv)
    e_c = jnp.exp(G_c)
    read = _dot(jnp.concatenate([beta_c * e_c * k, e_c * q], axis=1), S)
    U = _dot(T, beta_c * v_ref[...] - read[:, :C])               # (hb, C, dv)
    o_ref[...] = read[:, C:] + _dot(M, U)
    # (1, 1) -> a row first: Mosaic broadcasts along one axis at a time
    G_end = G_r[:, :, C - 1:C]
    e_end = jnp.exp(jnp.broadcast_to(G_end, (hb, 1, dv)))
    to_end = jnp.exp(jnp.broadcast_to(G_end, (hb, 1, C)) - G_r)
    state[...] = e_end * S + _dot(kT * to_end, U)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[...] = state[...]


def _relayout(q, k, v, g, beta, state):
    """(T, H, .) rows -> what the grid's blocks pick, head-major; the
    keys transposed with ``G`` and ``beta`` in the rows after them; the
    state transposed."""
    T, H, dk = q.shape
    G = jnp.cumsum(g.reshape(T // CHUNK, CHUNK, H), axis=1).reshape(T, H)
    kx = jnp.concatenate(
        [k, G[..., None], beta[..., None],
         jnp.zeros((T, H, key_width(dk) - dk - AUX), _F32)], axis=-1)
    return (jnp.transpose(q, (1, 0, 2)), jnp.transpose(kx, (1, 2, 0)),
            jnp.transpose(v, (1, 0, 2)), jnp.swapaxes(state, 1, 2))


def gated_delta_chunked(q, k, v, g, beta, state, interpret: bool = False):
    """``q``, ``k`` (T, H, d_k) normalised and scaled, ``v`` (T, H,
    d_v), ``g`` (the log of the decay), ``beta`` (T, H), ``state`` (H,
    d_v, d_k) as it stood before row 0 -> (o (T, H, d_v), the state
    after row T - 1): ``chunked_gated_delta``'s contract.  Rows are
    padded to whole chunks with ``g = 0, beta = 0``."""
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


def _over_chunks(q, kT, v, state, interpret=False):
    """The call, on what ``_relayout`` hands it -> (o (H, T, d_v), the
    state (H, d_k, d_v), transposed as it came)."""
    H, T, dk = q.shape
    C, wide, dv, hb = CHUNK, kT.shape[1], v.shape[2], head_block(H)

    def rows(w):
        return pl.BlockSpec((hb, C, w), lambda i, c: (i, c, 0))

    whole = pl.BlockSpec((hb, dk, dv), lambda i, c: (i, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(H // hb, T // C),
        in_specs=[rows(dk),
                  pl.BlockSpec((hb, wide, C), lambda i, c: (i, 0, c)),
                  rows(dv), whole],
        out_specs=[rows(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), _F32),
                   jax.ShapeDtypeStruct((H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        name="gated_delta_chunked",
        interpret=interpret,
    )(q, kT, v, state)
