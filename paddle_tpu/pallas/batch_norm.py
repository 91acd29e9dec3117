"""Fused batch-norm kernels.

Reference analog: paddle/cuda/src/hl_batch_norm.cu and
paddle/operators/batch_norm_op.cu (cuDNN spatial BN) — the era's
hand-written BN statistics + normalize kernels.

TPU redesign: one ``pallas_call`` per direction over a channel-minor
``(R, C)`` view (R = N*H*W), with a *two-phase sequential grid*:

- forward: phase 0 streams row-blocks accumulating per-channel
  ``sum``/``sum(x^2)`` into an f32 VMEM scratch (the only pass over x
  the statistics cost); phase 1 re-streams x and writes the normalized
  output in the same kernel — mean/var never round-trip HBM, and the
  affine (gamma, beta) is folded into one multiply-add per element.
- backward: phase 0 accumulates ``dbeta = sum(dy)`` and
  ``dgamma = sum(dy * xhat)``; phase 1 emits
  ``dx = gamma*inv*(dy - dbeta/R - xhat*dgamma/R)``.

Minimum HBM traffic for exact BN (3 passes fwd, 5 passes bwd) in
exactly 2 kernels.  All f32 accumulation regardless of activation
dtype.  ``interpret=True`` runs the same kernels on CPU for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_F32 = jnp.float32


def _pick_row_block(rows: int, cols: int, budget: int = 1 << 19) -> int:
    """Largest divisor of ``rows`` that is a multiple of 8 with
    block elements <= budget (VMEM sizing)."""
    cap = max(8, budget // max(cols, 1))
    best = 0
    d = 8
    while d * d <= rows:
        if rows % d == 0:
            if d % 8 == 0 and d <= cap:
                best = max(best, d)
            q = rows // d
            if q % 8 == 0 and q <= cap:
                best = max(best, q)
        d += 1
    if rows % 8 == 0 and rows <= cap:
        best = max(best, rows)
    return best


def fits(rows: int, cols: int) -> bool:
    return (rows >= 8 and cols <= 8192 and
            _pick_row_block(rows, cols) >= 8)


def block_ok(rows: int, cols: int, rt: int) -> bool:
    """Validity of an explicit row block at an actual shape: the
    divisibility/alignment the kernel grid needs plus a hard VMEM cap
    (x block + y block + f32 temps, ~12MB)."""
    return (rt >= 8 and rt % 8 == 0 and rows % rt == 0
            and rt * cols <= 1 << 20)


def _resolve_row_block(rows, cols, dtype, budget: int = 1 << 19,
                       block_rows: int = None):
    """Explicit block first, then the tuned forward row block from the
    tuning DB when valid at this shape, else the historical divisor
    heuristic."""
    if block_rows is not None and block_ok(rows, cols, block_rows):
        return block_rows
    from paddle_tpu.pallas import tuning

    cfg = tuning.lookup("batch_norm", (rows, cols), dtype) or {}
    rt = cfg.get("block_rows")
    if rt and block_ok(rows, cols, rt):
        return rt
    return _pick_row_block(rows, cols, budget)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _bn_fwd_kernel(x_ref, gamma_ref, beta_ref, y_ref, mean_ref, var_ref,
                   acc_ref, *, rows: int, eps: float):
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p == 0)
    def _accumulate():
        xb = x_ref[...].astype(_F32)
        acc_ref[0:1, :] += jnp.sum(xb, axis=0, keepdims=True)
        acc_ref[1:2, :] += jnp.sum(xb * xb, axis=0, keepdims=True)

    @pl.when(p == 1)
    def _normalize():
        inv_r = 1.0 / rows
        m = acc_ref[0:1, :] * inv_r
        v = acc_ref[1:2, :] * inv_r - m * m
        inv = lax.rsqrt(v + eps)
        # fold the affine in f32: y = x*a + b, one mul+add per element
        a = gamma_ref[0:1, :].astype(_F32) * inv
        b = beta_ref[0:1, :].astype(_F32) - m * a
        xb = x_ref[...].astype(_F32)
        y_ref[...] = (xb * a + b).astype(y_ref.dtype)
        mean_ref[0:1, :] = m
        var_ref[0:1, :] = v


@functools.partial(jax.jit, static_argnames=("eps", "interpret",
                                             "block_rows"))
def _bn_fwd_impl(x2d, gamma, beta, eps: float, interpret: bool = False,
                 block_rows: int = None):
    R, C = x2d.shape
    Rt = _resolve_row_block(R, C, x2d.dtype.name, block_rows=block_rows)
    grid = (2, R // Rt)
    y, mean, var = pl.pallas_call(
        functools.partial(_bn_fwd_kernel, rows=R, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Rt, C), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Rt, C), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), x2d.dtype),
            jax.ShapeDtypeStruct((1, C), _F32),
            jax.ShapeDtypeStruct((1, C), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((2, C), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="batch_norm_fwd",
        interpret=interpret,
    )(x2d, gamma.reshape(1, C), beta.reshape(1, C))
    return y, mean.reshape(C), var.reshape(C)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bn_bwd_kernel(x_ref, dy_ref, gamma_ref, mean_ref, inv_ref,
                   dx_ref, dgamma_ref, dbeta_ref, acc_ref, *, rows: int):
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m = mean_ref[0:1, :]
    inv = inv_ref[0:1, :]

    @pl.when(p == 0)
    def _accumulate():
        xb = x_ref[...].astype(_F32)
        dyb = dy_ref[...].astype(_F32)
        xhat = (xb - m) * inv
        acc_ref[0:1, :] += jnp.sum(dyb, axis=0, keepdims=True)
        acc_ref[1:2, :] += jnp.sum(dyb * xhat, axis=0, keepdims=True)

    @pl.when(p == 1)
    def _dx():
        inv_r = 1.0 / rows
        dbeta = acc_ref[0:1, :]
        dgamma = acc_ref[1:2, :]
        g = gamma_ref[0:1, :].astype(_F32)
        xb = x_ref[...].astype(_F32)
        dyb = dy_ref[...].astype(_F32)
        xhat = (xb - m) * inv
        dx = (g * inv) * (
            dyb - (dbeta * inv_r) - xhat * (dgamma * inv_r))
        dx_ref[...] = dx.astype(dx_ref.dtype)
        dgamma_ref[0:1, :] = dgamma
        dbeta_ref[0:1, :] = dbeta


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bn_bwd_impl(x2d, dy2d, gamma, mean, inv, interpret: bool = False):
    R, C = x2d.shape
    Rt = _pick_row_block(R, C, budget=1 << 18)  # two streams resident
    grid = (2, R // Rt)
    dx, dgamma, dbeta = pl.pallas_call(
        functools.partial(_bn_bwd_kernel, rows=R),
        grid=grid,
        in_specs=[
            pl.BlockSpec((Rt, C), lambda p, i: (i, 0)),
            pl.BlockSpec((Rt, C), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Rt, C), lambda p, i: (i, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), dy2d.dtype),
            jax.ShapeDtypeStruct((1, C), _F32),
            jax.ShapeDtypeStruct((1, C), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((2, C), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="batch_norm_bwd",
        interpret=interpret,
    )(x2d, dy2d, gamma.reshape(1, C), mean.reshape(1, C), inv.reshape(1, C))
    return dx, dgamma.reshape(C), dbeta.reshape(C)


# ---------------------------------------------------------------------------
# differentiable entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def batch_norm_train(x2d, gamma, beta, eps: float = 1e-5,
                     interpret: bool = False):
    """Training-mode BN over a channel-minor ``(R, C)`` view.

    Returns ``(y, batch_mean, batch_var)`` with f32 statistics.
    Differentiable w.r.t. ``x2d``, ``gamma``, ``beta``.
    """
    y, mean, var = _bn_fwd_impl(x2d, gamma, beta, eps, interpret)
    return y, mean, var


def _bn_train_fwd(x2d, gamma, beta, eps, interpret):
    y, mean, var = _bn_fwd_impl(x2d, gamma, beta, eps, interpret)
    inv = lax.rsqrt(var + eps)
    return (y, mean, var), (x2d, gamma, mean, inv)


def _bn_train_bwd(eps, interpret, res, cots):
    x2d, gamma, mean, inv = res
    dy, dmean, dvar = cots
    # batch statistics are consumed as aux outputs (running averages),
    # treated as non-differentiable targets like the reference's
    # MeanOut/VarianceOut slots
    del dmean, dvar
    dx, dgamma, dbeta = _bn_bwd_impl(x2d, dy, gamma, mean, inv, interpret)
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype))


batch_norm_train.defvjp(_bn_train_fwd, _bn_train_bwd)
