"""Blocked online-softmax (flash) attention kernels.

The reference era predates transformer attention entirely (its
attention is seq2seq additive attention built from gserver layers); the
CUDA analog of this file is the hand-written softmax/sequence kernels
(paddle/cuda/src/hl_cuda_sequence.cu) generalized to the modern fused
attention.  TPU design:

- forward: grid ``(B*H, S/blk_q, S/blk_k)``, K/V innermost.  The
  running max ``m``, normalizer ``l`` and output accumulator live in
  VMEM scratch across the K sweep, so the ``S x S`` score matrix never
  exists in HBM — the same VMEM-residency trick as ``pallas/lstm.py``.
  Scores/accumulation in f32 on the MXU regardless of input dtype.
  Causal masking skips the strictly-upper K blocks' FLOPs entirely and
  element-masks the diagonal blocks.
- backward: two kernels (the standard split): ``dq`` accumulates over
  K blocks on a ``(BH, nq, nk)`` grid; ``dk/dv`` accumulate over Q
  blocks on a ``(BH, nk, nq)`` grid.  Both recompute ``p`` from the
  saved per-row logsumexp (no S x S residual).

Used by ``ops/attention_ops.py`` local attention and as the per-shard
chunk kernel of ring attention (parallel/ring_attention.py) via
``flash_attention_with_lse`` — chunks merge in log-sum-exp space, and
the lse cotangent folds into the backward's delta term so the ring
gradient stays exact.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_F32 = jnp.float32
_NEG_INF = -1e30  # large-but-finite: avoids inf-inf NaNs in corrections


# blk_q / blk_k default to the largest power-of-two divisor of S up to
# this.  Not the best on this chip: at the LM training cell's shape the
# sweep read (1024, 1024) 30% under it forward, 17% forward + backward
# (PERF.md §6, PR 44; ROADMAP S3 has the claim to make with it)
BLOCK_PREF = 512


def _pick_block(s: int) -> int:
    b = min(BLOCK_PREF, s)
    while b > 8 and s % b != 0:
        b //= 2
    return b if s % b == 0 else 0


def _blocks_ok(S: int, Sk: int, D: int, blk_q: int, blk_k: int) -> bool:
    """Validity of an explicit (blk_q, blk_k) pair at an actual shape:
    divisibility plus the same VMEM residency model as ``fits``."""
    if blk_q < 128 or blk_k < 128 or S % blk_q or Sk % blk_k:
        return False
    resident = (blk_q + 2 * blk_k) * D * 2 + blk_q * D * 4 \
        + blk_q * blk_k * 4
    return resident <= 12 * 1024 * 1024


def _resolve_blocks(S, Sk, D, blk_q=None, blk_k=None):
    """An explicit (blk_q, blk_k) where it is valid at this shape, else
    the ``_pick_block`` preference."""
    blk_q = blk_q or _pick_block(S)
    blk_k = blk_k or _pick_block(Sk)
    if not _blocks_ok(S, Sk, D, blk_q, blk_k):
        blk_q, blk_k = _pick_block(S), _pick_block(Sk)
    return blk_q, blk_k


def fits(B: int, H: int, S: int, D: int) -> bool:
    blk = _pick_block(S)
    if blk < 128 or D > 256 or D % 8 != 0:
        return False
    # VMEM: q,k,v blocks + f32 acc + scores
    resident = blk * D * 2 * 3 + blk * D * 4 + blk * blk * 4
    return resident <= 12 * 1024 * 1024


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, blk_q, blk_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(_F32)
        k = k_ref[0].astype(_F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:, 0:1] = l_scr[:, 0:1] * corr + jnp.sum(p, axis=1,
                                                       keepdims=True)
        m_scr[:, 0:1] = m_new
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, pl.ds(qi, 1), :] = (
            m_scr[:, 0:1] + jnp.log(l)).reshape(1, -1)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret",
                                             "blk_q", "blk_k"))
def _flash_fwd_impl(q, k, v, causal: bool, scale: float,
                    interpret: bool = False, blk_q: int = None,
                    blk_k: int = None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    blk_q, blk_k = _resolve_blocks(S, Sk, D, blk_q, blk_k)
    nq, nk = S // blk_q, Sk // blk_k
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, nq, blk_q), lambda b, i, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, nq, blk_q), _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), _F32),
            pltpu.VMEM((blk_q, 1), _F32),
            pltpu.VMEM((blk_q, D), _F32),
        ],
        # qi must NOT be "parallel": every qi writes its own row slice
        # of the shared (1, nq, blk_q) lse block, and a megacore split
        # over qi would flush two partially-written private copies of
        # that block (BH carries the core-level parallelism instead)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse.reshape(BH, S)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, blk_q, blk_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(_F32)
        k = k_ref[0].astype(_F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        lse_col = lse_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)
        p = jnp.exp(s - lse_col)
        dp = jax.lax.dot_general(
            do_ref[0].astype(_F32), v_ref[0].astype(_F32),
            (((1,), (1,)), ((), ())), preferred_element_type=_F32)
        ds = p * (dp - delta_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=_F32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, blk_q, blk_k, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = ki * blk_k <= qi * blk_q + blk_q - 1

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(_F32)
        k = k_ref[0].astype(_F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=_F32) * scale
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        lse_col = lse_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)
        p = jnp.exp(s - lse_col)                      # (blk_q, blk_k)
        do = do_ref[0].astype(_F32)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=_F32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(_F32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)
        ds = p * (dp - delta_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=_F32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def _flash_bwd_impl(q, k, v, o, lse, do, causal: bool, scale: float,
                    interpret: bool = False, dlse=None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    # the default blocks, as the forward it differentiates ran with
    # (only ``_flash_fwd_impl`` takes a pair, and nothing differentiates
    # that); lse arrives flat (BH, S) and is reshaped to these
    blk_q, blk_k = _resolve_blocks(S, Sk, D)
    nq, nk = S // blk_q, Sk // blk_k
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1)  # (BH, S)
    if dlse is not None:
        # joint (out, lse) cotangent: d lse/d s = p, so the lse
        # cotangent folds into the delta term of ds = p*(dp - delta)
        delta = delta - dlse.astype(_F32)
    lse3 = lse.reshape(BH, nq, blk_q)
    delta3 = delta.reshape(BH, nq, blk_q)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, nq, blk_q), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, nq, blk_q), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          blk_q=blk_q, blk_k=blk_k, nq=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, nq, blk_q), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((1, nq, blk_q), lambda b, j, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_k, D), _F32),
                        pltpu.VMEM((blk_k, D), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = False, scale: float = None,
                    interpret: bool = False):
    """q, k, v: (BH, S, D) -> out (BH, S, D).

    Callers with (B, H, S, D) reshape to (B*H, S, D) first (free).
    Thin wrapper over ``flash_attention_with_lse`` (the lse output's
    cotangent is simply zero here).
    """
    out, _lse = flash_attention_with_lse(q, k, v, causal, scale,
                                         interpret)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: float = None, interpret: bool = False):
    """Like ``flash_attention`` but also returns the per-row logsumexp
    (BH, S) — the quantity ring attention needs to merge per-chunk
    results exactly.  Differentiable in BOTH outputs (the lse cotangent
    folds into the backward's delta term)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_fwd_impl(q, k, v, causal, scale, interpret)


def _fa_lse_fwd(q, k, v, causal, scale, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse = _flash_fwd_impl(q, k, v, causal, scale, interpret)
    return (out, lse), (q, k, v, out, lse)


def _fa_lse_bwd(causal, scale, interpret, res, cots):
    q, k, v, out, lse = res
    do, dlse = cots
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # an all-zeros lse cotangent (the flash_attention wrapper's case)
    # folds into delta as a no-op, so no special-casing is needed
    dq, dk, dv = _flash_bwd_impl(q, k, v, out, lse, do, causal, scale,
                                 interpret, dlse=dlse)
    return dq, dk, dv


flash_attention_with_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)
