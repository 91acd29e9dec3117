"""Blocked MXU matmul kernel (reference analog: the cuBLAS path behind
paddle/operators/math/math_function.cc gemm).

Grid (M/bm, N/bn, K/bk); fp32 accumulation in VMEM scratch; bf16 or
f32 operands.  K is innermost so the accumulator lives across the K
steps of one (i, j) tile."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The one place the guessed tile lives (ISSUE 16: `fits` and `matmul`
# used to repeat bm=256, bk=512, bn=256 independently — a tuned default
# could desync the fits check from dispatch).  The tuning database
# (pallas/tuning) overrides these per (shape-bucket, dtype, device).
DEFAULT_CONFIG = {"bm": 256, "bk": 512, "bn": 256}


def _resolve_blocks(m, k, n, dtype, bm, bk, bn):
    """Fill unset block dims from the tuning DB, else the defaults.

    A tuned config is validated against the ACTUAL shape (the DB keys
    by bucket, so a bucket-valid config may not divide this shape) and
    dropped back to the defaults when it doesn't fit.
    """
    if bm is not None and bk is not None and bn is not None:
        return bm, bk, bn
    from paddle_tpu.pallas import tuning

    cfg = tuning.lookup("matmul", (m, k, n), dtype) or {}
    got = (bm or cfg.get("bm", DEFAULT_CONFIG["bm"]),
           bk or cfg.get("bk", DEFAULT_CONFIG["bk"]),
           bn or cfg.get("bn", DEFAULT_CONFIG["bn"]))
    if cfg and not fits(m, k, n, *got):
        got = (bm or DEFAULT_CONFIG["bm"], bk or DEFAULT_CONFIG["bk"],
               bn or DEFAULT_CONFIG["bn"])
    return got


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        x_ref[:], y_ref[:], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def fits(m, k, n, bm=None, bk=None, bn=None) -> bool:
    bm = bm or DEFAULT_CONFIG["bm"]
    bk = bk or DEFAULT_CONFIG["bk"]
    bn = bn or DEFAULT_CONFIG["bn"]
    return m % bm == 0 and k % bk == 0 and n % bn == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def matmul(x, y, bm: int = None, bk: int = None, bn: int = None,
           interpret: bool = False):
    """Unset block dims resolve through the tuning DB (pallas/tuning),
    falling back to ``DEFAULT_CONFIG`` — explicit args always win."""
    return _matmul_impl(x, y, bm, bk, bn, interpret)


def _matmul_fwd(x, y, bm, bk, bn, interpret):
    return _matmul_impl(x, y, bm, bk, bn, interpret), (x, y)


def _matmul_bwd(bm, bk, bn, interpret, res, g):
    x, y = res
    # dX = g @ Y^T, dY = X^T @ g — via XLA (transposed tilings differ)
    gx = jnp.dot(g, y.T, preferred_element_type=jnp.float32).astype(x.dtype)
    gy = jnp.dot(x.T, g, preferred_element_type=jnp.float32).astype(y.dtype)
    return gx, gy


matmul.defvjp(_matmul_fwd, _matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def _matmul_impl(x, y, bm: int = None, bk: int = None, bn: int = None,
                 interpret: bool = False):
    m, k = x.shape
    k2, n = y.shape
    bm, bk, bn = _resolve_blocks(m, k, n, x.dtype.name, bm, bk, bn)
    assert k == k2 and fits(m, k, n, bm, bk, bn), (x.shape, y.shape)
    k_steps = k // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="matmul_tiled",
        interpret=interpret,
    )(x, y)
