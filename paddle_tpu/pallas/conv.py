"""Implicit-GEMM conv kernels for the MXU (reference analog: the cuDNN
bindings behind paddle/cuda/src/hl_cuda_cudnn.cc and the implicit-GEMM
fallback paddle/function/GemmConvOp.cpp — redone as Pallas row-block
kernels instead of im2col-through-HBM).

Design (stride-1 SAME convs, NHWC, the ResNet-50 3x3 family):

- forward: grid ``(NB, OH, KH)``, KH innermost.  Each step loads one
  padded input row slab ``(bb, 1, Wp, C)`` for a batch block and
  accumulates the KW shifted ``(bb*OW, C) @ (C, O)`` products into an
  f32 VMEM accumulator; the accumulator flushes to the output row when
  kh == KH-1.  M = bb*OW keeps the MXU pipelined even where W alone
  (7..56) could not.
- backward-input: the same forward kernel applied to the padded
  cotangent with the spatially-flipped, channel-transposed filter
  (conv_transpose identity for stride 1).
- backward-filter: grid ``(KH, NB, OH)``, OH innermost.  Each step
  contracts the x row slab against the cotangent row over M = bb*OW
  into a per-kh ``(KW*C, O)`` f32 accumulator (reset at the first
  (batch, row) step, flushed at the last).

Whole-filter blocks use constant index maps so Pallas keeps them
resident in VMEM across grid steps instead of re-copying.  Batch
blocks are sized so the working set (with sub-128 channel dims padded
to full lanes) stays under the ~16 MB scoped-vmem budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_VMEM_BUDGET = 9 * 1024 * 1024


def _lanes(c):
    return max(c, 128)


def _fwd_vmem(bb, w, wp, c, o, kh, kw, fold_kw=False):
    vmem = (2 * bb * wp * _lanes(c) * 2      # double-buffered x slab
            + bb * w * _lanes(o) * 4         # f32 accumulator
            + 2 * bb * w * _lanes(o) * 2     # double-buffered out row
            + kh * kw * c * _lanes(o) * 2)   # resident filter
    if fold_kw:
        vmem += bb * w * kw * c * 2          # staged K=KW*C patch
    return vmem


def fwd_block_ok(bb, n, w, wp, c, o, kh, kw, fold_kw=False) -> bool:
    """Validity of an explicit forward batch block at an actual shape
    (the tuning DB's configs are bucket-keyed, so dispatch re-checks)."""
    return (bb >= 8 and n % bb == 0
            and _fwd_vmem(bb, w, wp, c, o, kh, kw, fold_kw)
            <= _VMEM_BUDGET)


def _fwd_batch_block(n, w, wp, c, o, kh, kw, fold_kw=False):
    """Largest divisor-of-n batch block whose fwd working set fits
    (x slab and out row double-buffered, resident filter, f32 acc).
    Returns None when even the smallest block exceeds VMEM — the
    caller must fall back to the XLA emitter."""
    for bb in sorted((d for d in range(8, n + 1) if n % d == 0),
                     reverse=True):
        if _fwd_vmem(bb, w, wp, c, o, kh, kw, fold_kw) <= _VMEM_BUDGET:
            return bb
    return None


def _dw_batch_block(n, ow, wp, c, o, kh, kw):
    for bb in sorted((d for d in range(8, n + 1) if n % d == 0),
                     reverse=True):
        vmem = (2 * bb * wp * _lanes(c) * 2 + 2 * bb * ow * _lanes(o) * 2
                + kw * c * _lanes(o) * 4 + kh * kw * c * _lanes(o) * 4)
        if vmem <= _VMEM_BUDGET:
            return bb
    return None


def fits(n, h, w, c, o, kh, kw, stride, padding) -> bool:
    """Kernel applicability: stride-1 SAME square convs with
    MXU-friendly channel counts and a batch block that fits VMEM in
    every direction (fwd, bwd-input, bwd-filter)."""
    if stride != 1 or kh != kw or kh % 2 == 0:
        return False
    if padding != kh // 2:
        return False
    if c % 64 or o % 64 or n % 8:
        return False
    wp = w + 2 * padding
    return (_fwd_batch_block(n, w, wp, c, o, kh, kw) is not None
            and _fwd_batch_block(n, w, wp, o, c, kh, kw) is not None
            and _dw_batch_block(n, w, wp, c, o, kh, kw) is not None)


def _fwd_kernel(x_ref, w_ref, o_ref, *rest, kh_steps, kw_steps, ow,
                fold_kw, with_stats=False):
    """Forward conv; with ``with_stats`` the per-channel BN sum /
    sum-of-squares accumulate in the flush epilogue while the f32
    output block is still in VMEM (the round-5 epilogue-fusion
    experiment) — stats outputs are revisited every step, so the grid
    must then be fully sequential."""
    if with_stats:
        sum_ref, sq_ref, acc_ref, *scratch = rest
    else:
        sum_ref = sq_ref = None
        acc_ref, *scratch = rest
    kh = pl.program_id(2)

    @pl.when(kh == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    if with_stats:
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
                 & (kh == 0))
        def _init_stats():
            sum_ref[:] = jnp.zeros_like(sum_ref)
            sq_ref[:] = jnp.zeros_like(sq_ref)

    row = x_ref[:, 0]                       # (bb, Wp, C)
    b = row.shape[0]
    c = row.shape[-1]
    if fold_kw:
        (patch_ref,) = scratch
        # one MXU pass with K = KW*C: the kw shifts happen either way,
        # folding them into the contraction amortizes MXU setup.
        # Mosaic cannot concat sublane-shifted vectors, so the shifted
        # slices are staged through a scratch buffer lane-block-wise.
        for kw in range(kw_steps):
            patch_ref[:, :, kw * c:(kw + 1) * c] = row[:, kw:kw + ow]
        patch = patch_ref[:].reshape(b * ow, kw_steps * c)
        wk = w_ref[kh].reshape(kw_steps * c, -1)
        acc_ref[:] += jnp.dot(patch, wk,
                              preferred_element_type=jnp.float32)
    else:
        for kw in range(kw_steps):
            patch = row[:, kw:kw + ow].reshape(b * ow, -1)
            acc_ref[:] += jnp.dot(patch, w_ref[kh, kw],
                                  preferred_element_type=jnp.float32)

    @pl.when(kh == kh_steps - 1)
    def _flush():
        acc = acc_ref[:]
        o_ref[:, 0] = acc.reshape(b, ow, -1).astype(o_ref.dtype)
        if with_stats:
            sum_ref[:] += jnp.sum(acc, axis=0, keepdims=True)
            sq_ref[:] += jnp.sum(acc * acc, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("padding", "interpret",
                                             "fold_kw", "with_stats",
                                             "bb"))
def _conv_fwd_impl(x, w, padding: int, interpret: bool = False,
                   fold_kw: bool = None, with_stats: bool = False,
                   bb: int = None):
    n, h, wd, c = x.shape
    kh, kw, c2, o = w.shape
    assert c == c2, (x.shape, w.shape)
    p = padding
    xp = jnp.pad(x, [(0, 0), (p, p), (p, p), (0, 0)])
    wp = wd + 2 * p
    # tunables (pallas/tuning): the forward batch block bb and the
    # fold_kw layout choice (one K=KW*C MXU pass vs KW shifted passes).
    # Explicit args win (the tuner pins candidates this way); a tuned
    # bb must re-validate against this actual shape before it replaces
    # the divisor heuristic.
    if fold_kw is None or bb is None:
        from paddle_tpu.pallas import tuning

        cfg = tuning.lookup("conv", (n, h, wd, c, o, kh),
                            x.dtype.name) or {}
        if fold_kw is None:
            fold_kw = bool(cfg.get("fold_kw", False))
        if bb is None:
            bb = cfg.get("bb")
    if bb is not None and not fwd_block_ok(bb, n, wd, wp, c, o, kh, kw,
                                           fold_kw):
        bb = None
    if bb is None:
        bb = _fwd_batch_block(n, wd, wp, c, o, kh, kw, fold_kw=fold_kw)
    assert bb is not None, (
        f"conv working set exceeds VMEM at every batch block "
        f"({x.shape} w={w.shape}); gate calls behind fits()")
    scratch = [pltpu.VMEM((bb * wd, o), jnp.float32)]
    if fold_kw:
        scratch.append(pltpu.VMEM((bb, wd, kw * c), x.dtype))
    out_specs = pl.BlockSpec((bb, 1, wd, o), lambda b, oh, k: (b, oh, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n, h, wd, o), x.dtype)
    if with_stats:
        out_specs = [out_specs,
                     pl.BlockSpec((1, o), lambda b, oh, k: (0, 0)),
                     pl.BlockSpec((1, o), lambda b, oh, k: (0, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((1, o), jnp.float32),
                     jax.ShapeDtypeStruct((1, o), jnp.float32)]
    # stats outputs are revisited every grid step -> fully sequential
    semantics = (("arbitrary",) * 3 if with_stats
                 else ("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, kh_steps=kh, kw_steps=kw, ow=wd,
                          fold_kw=fold_kw, with_stats=with_stats),
        grid=(n // bb, h, kh),
        in_specs=[
            pl.BlockSpec((bb, 1, wp, c), lambda b, oh, k: (b, oh + k, 0, 0)),
            pl.BlockSpec((kh, kw, c, o), lambda b, oh, k: (0, 0, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        name="conv2d_fwd",
        interpret=interpret,
    )(xp, w)


def _dw_kernel(x_ref, g_ref, dw_ref, acc_ref, *, nb_steps, oh_steps,
               kw_steps, ow):
    b_i = pl.program_id(1)
    oh = pl.program_id(2)

    @pl.when(jnp.logical_and(b_i == 0, oh == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    row = x_ref[:, 0]                       # (bb, Wp, C)
    gg = g_ref[:, 0]                        # (bb, OW, O)
    b = row.shape[0]
    c = row.shape[-1]
    gflat = gg.reshape(b * ow, -1)
    for kw in range(kw_steps):
        patch = row[:, kw:kw + ow].reshape(b * ow, c)
        acc_ref[kw * c:(kw + 1) * c] += lax.dot_general(
            patch, gflat, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(b_i == nb_steps - 1, oh == oh_steps - 1))
    def _flush():
        dw_ref[0] = acc_ref[:].reshape(
            kw_steps, c, -1).astype(dw_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kernel", "padding",
                                             "interpret"))
def _conv_dw_impl(x, g, kernel: int, padding: int, interpret: bool = False):
    n, h, wd, c = x.shape
    _, oh, ow, o = g.shape
    kh = kw = kernel
    p = padding
    xp = jnp.pad(x, [(0, 0), (p, p), (p, p), (0, 0)])
    wp = wd + 2 * p
    bb = _dw_batch_block(n, ow, wp, c, o, kh, kw)
    assert bb is not None, (
        f"conv-dw working set exceeds VMEM at every batch block "
        f"({x.shape} g={g.shape}); gate calls behind fits()")
    return pl.pallas_call(
        functools.partial(_dw_kernel, nb_steps=n // bb, oh_steps=oh,
                          kw_steps=kw, ow=ow),
        grid=(kh, n // bb, oh),
        in_specs=[
            pl.BlockSpec((bb, 1, wp, c), lambda k, b, r: (b, r + k, 0, 0)),
            pl.BlockSpec((bb, 1, ow, o), lambda k, b, r: (b, r, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kw, c, o), lambda k, b, r: (k, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kh, kw, c, o), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kw * c, o), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="conv2d_bwd_dw",
        interpret=interpret,
    )(xp, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d_nhwc(x, w, padding: int, interpret: bool = False):
    """Stride-1 SAME NHWC conv, implicit-GEMM Pallas kernels end to end
    (forward + both backwards).  x (N, H, W, C), w (KH, KW, C, O)."""
    return _conv_fwd_impl(x, w, padding, interpret)


@functools.partial(jax.jit, static_argnames=("padding", "interpret"))
def conv2d_bn_stats_nhwc(x, w, padding: int, interpret: bool = False):
    """Fused conv + BN-statistics forward (the epilogue-fusion
    experiment; forward-only — training would pair it
    with the round-4 backward kernels): returns (out, mean, var) with
    the (O,) biased batch statistics over (N, H, W), exactly what
    batch_norm training consumes."""
    n, h, wd, _ = x.shape
    o = w.shape[-1]
    out, s_, sq = _conv_fwd_impl(x, w, padding, interpret,
                                 with_stats=True)
    cnt = jnp.float32(n * h * wd)
    mean = (s_ / cnt).reshape(o)
    var = (sq / cnt).reshape(o) - mean * mean
    return out, mean, var


def _conv_fwd_rule(x, w, padding, interpret):
    return _conv_fwd_impl(x, w, padding, interpret), (x, w)


def _conv_bwd_rule(padding, interpret, res, g):
    x, w = res
    kh = w.shape[0]
    # dx: conv of g with the spatially-flipped, channel-swapped filter
    w_flip = jnp.flip(w, (0, 1)).swapaxes(2, 3)
    dx = _conv_fwd_impl(g, w_flip.astype(g.dtype), kh - 1 - padding,
                        interpret)
    dw = _conv_dw_impl(x, g, kh, padding, interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv2d_nhwc.defvjp(_conv_fwd_rule, _conv_bwd_rule)
