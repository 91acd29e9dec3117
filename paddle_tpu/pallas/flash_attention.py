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
  The two products whose operands are both loaded from refs (``q k^T``
  and, in the backward, ``do v^T``) take them as stored, so bfloat16
  operands reach the MXU as bfloat16 and float32 ones as float32; every
  product accumulates in float32, and the softmax's statistics, the
  output accumulator, ``lse`` and ``delta`` are float32 whatever the
  input dtype.  (On this chip that is no faster than widening first:
  Mosaic runs a float32 product at its default precision as one
  bfloat16 pass, PERF.md §6, PR 46.)
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
_NT = (((1,), (1,)), ((), ()))   # a b^T: contract both operands' lanes
_TN = (((0,), (0,)), ((), ()))   # a^T b: contract both operands' rows


# blk_q / blk_k default to the largest power-of-two divisors of S / Sk
# up to this that the kernel can hold (``_resident``).  From the sweep
# on the chip (PERF.md §6, PR 46): (1024, 1024) reads 6-36% under
# (512, 512) forward at every shape a cell runs from 1,024 rows up, a
# 1,024-row bucket run as one block included, and 15% under it forward
# + backward at the LM step's shape; (512, 1024) and (1024, 512) lie
# between or behind.
BLOCK_PREF = 1024

# what Mosaic lets one kernel call hold of this chip's VMEM unless told
# otherwise (16 MiB, its default scoped limit on a v5e) less 1 MiB: at
# float32 heads of 256 lanes ``_resident`` reads up to 0.75 MiB under
# what the compiler counts
VMEM_BUDGET = 15 * 1024 * 1024

# Mosaic keeps none of s, p, dp and ds whole: bisecting the limit it is
# given (a described v5e) reads, beside the blocks and the scratch,
# 1.25-1.6 float32 (blk_q, blk_k) tiles in the forward kernel and
# 0.75-1.2 in the two backward ones (PERF.md §6, PR 46)
_TILES_IN_FLIGHT = {"fwd": 2.0, "dq": 1.5, "dkv": 1.5}
KERNELS = tuple(_TILES_IN_FLIGHT)


def _pick_block(s: int, pref: int = BLOCK_PREF) -> int:
    b = min(pref, s)
    while b > 8 and s % b != 0:
        b //= 2
    return b if s % b == 0 else 0


def _resident(kernel: str, blk_q: int, blk_k: int, D: int,
              itemsize: int) -> int:
    """Bytes of VMEM one grid step of ``kernel`` holds: every block it
    reads or writes twice (the pipeline's two buffers) at the operands'
    itemsize and a head laid out in whole 128-lane tiles, its float32
    scratch (a ``(rows, 1)`` column takes a whole tile of lanes), and
    the ``(blk_q, blk_k)`` float32 temporaries in flight."""
    lanes = -(-D // 128) * 128
    q_blk, k_blk = blk_q * lanes * itemsize, blk_k * lanes * itemsize
    stats = 2 * 8 * blk_q * 4             # an lse / delta row, two buffers
    if kernel == "fwd":                    # q k v -> o, lse; acc, m, l
        blocks = 2 * (2 * q_blk + 2 * k_blk) + stats
        scratch = blk_q * lanes * 4 + 2 * blk_q * 128 * 4
    elif kernel == "dq":                   # q k v do lse delta -> dq; acc
        blocks = 2 * (3 * q_blk + 2 * k_blk) + 2 * stats
        scratch = blk_q * lanes * 4
    else:                                  # ... -> dk, dv; their two accs
        blocks = 2 * (2 * q_blk + 4 * k_blk) + 2 * stats
        scratch = 2 * blk_k * lanes * 4
    tiles = int(_TILES_IN_FLIGHT[kernel] * blk_q * blk_k * 4)
    return blocks + scratch + tiles


def _blocks_ok(S: int, Sk: int, D: int, blk_q: int, blk_k: int,
               itemsize: int = 4, kernel: str = "fwd") -> bool:
    """Validity of a (blk_q, blk_k) pair at an actual shape: whole
    blocks of 128 rows and up that ``kernel`` can hold."""
    if blk_q < 128 or blk_k < 128 or S % blk_q or Sk % blk_k:
        return False
    return _resident(kernel, blk_q, blk_k, D, itemsize) <= VMEM_BUDGET


def _resolve_blocks(S, Sk, D, itemsize=4, blk_q=None, blk_k=None,
                    kernel="fwd"):
    """An explicit (blk_q, blk_k) where it is valid at this shape, else
    the largest default pair ``kernel`` can hold ((0, 0) if none)."""
    pref, default = BLOCK_PREF, (0, 0)
    while pref >= 128 and not default[0]:
        pair = _pick_block(S, pref), _pick_block(Sk, pref)
        if _blocks_ok(S, Sk, D, *pair, itemsize, kernel):
            default = pair
        pref //= 2
    pair = blk_q or default[0], blk_k or default[1]
    return pair if _blocks_ok(S, Sk, D, *pair, itemsize, kernel) else default


def fits(B: int, H: int, S: int, D: int) -> bool:
    """Whether all three kernels have a block pair at this shape, at
    float32 operands, the widest (narrower ones only take larger pairs)."""
    if D > 256 or D % 8 != 0:
        return False
    return all(_resolve_blocks(S, S, D, kernel=k)[0] for k in KERNELS)


def fits_forward(S: int, Sk: int, D: int, itemsize: int = 4) -> bool:
    """Whether the forward kernel alone has a block pair for ``S`` query
    rows over ``Sk`` key rows (a prompt chunk over its cached rows:
    ``decode/attention.py:prompt_chunk_attention``)."""
    return (D <= 256 and D % 8 == 0
            and bool(_resolve_blocks(S, Sk, D, itemsize)[0]))


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
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
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
    blk_q, blk_k = _resolve_blocks(S, Sk, D, q.dtype.itemsize, blk_q, blk_k)
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
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=_F32) * scale
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        lse_col = lse_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)
        p = jnp.exp(s - lse_col)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=_F32)
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
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=_F32) * scale
        if causal:
            q_pos = qi * blk_q + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 0)
            k_pos = ki * blk_k + lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        lse_col = lse_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)
        p = jnp.exp(s - lse_col)                      # (blk_q, blk_k)
        # p and ds are computed float32 values: their products with do
        # and q keep float32 operands (which this chip's MXU rounds to
        # bfloat16 at the default precision anyway: PERF.md §7)
        dv_scr[...] += jax.lax.dot_general(
            p, do_ref[0].astype(_F32), _TN, preferred_element_type=_F32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=_F32)
        ds = p * (dp - delta_ref[0, pl.ds(qi, 1), :].reshape(-1, 1)) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q_ref[0].astype(_F32), _TN, preferred_element_type=_F32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def _flash_bwd_impl(q, k, v, o, lse, do, causal: bool, scale: float,
                    interpret: bool = False, dlse=None):
    BH, S, D = q.shape
    Sk = k.shape[1]
    delta = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1)  # (BH, S)
    if dlse is not None:
        # joint (out, lse) cotangent: d lse/d s = p, so the lse
        # cotangent folds into the delta term of ds = p*(dp - delta)
        delta = delta - dlse.astype(_F32)

    def blocked(kernel):
        """A kernel's default pair (only ``_flash_fwd_impl`` takes one,
        and nothing differentiates that), its grid's two counts, and
        lse and delta, flat (BH, S), reshaped to its q blocks."""
        blk_q, blk_k = _resolve_blocks(S, Sk, D, q.dtype.itemsize,
                                       kernel=kernel)
        nq = S // blk_q
        return (blk_q, blk_k, nq, Sk // blk_k,
                lse.reshape(BH, nq, blk_q), delta.reshape(BH, nq, blk_q))

    blk_q, blk_k, nq, nk, lse3, delta3 = blocked("dq")
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

    blk_q, blk_k, nq, nk, lse3, delta3 = blocked("dkv")
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
