"""Fused row softmax kernel (reference analog: paddle/operators/math/
softmax.cc + the cudnn softmax path): one pass per row block — max,
exp, sum, divide — entirely in VMEM, single HBM read/write."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


# rows a grid step; from an earlier setup, not re-measured on this chip
BLOCK_ROWS = 256


def fits(rows, cols, block_rows=None, itemsize=4) -> bool:
    # VMEM budget: in block + out block + fp32 temps must coexist in
    # ~16MB/core; cap a block's footprint at 2MB so 4-5 live copies fit
    block_rows = block_rows or BLOCK_ROWS
    block_bytes = block_rows * cols * max(itemsize, 4)
    return (rows % block_rows == 0 and cols % 128 == 0
            and block_bytes <= 2 * 1024 * 1024)


def _resolve_block_rows(rows, cols, block_rows):
    """An explicit block where it fits this shape, else the default."""
    if block_rows is not None and fits(rows, cols, block_rows):
        return block_rows
    return BLOCK_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def softmax(x, block_rows: int = None, interpret: bool = False):
    """Row softmax of a 2-D ``x``, ``block_rows`` rows a grid step
    (``BLOCK_ROWS`` when unset or when the explicit block does not fit
    the shape)."""
    return _softmax_impl(x, block_rows, interpret)


def _softmax_fwd(x, block_rows, interpret):
    out = _softmax_impl(x, block_rows, interpret)
    return out, out


def _softmax_bwd(block_rows, interpret, out, g):
    # d/dx softmax: s * (g - sum(g * s))
    inner = jnp.sum(g * out, axis=-1, keepdims=True)
    return (out * (g - inner),)


softmax.defvjp(_softmax_fwd, _softmax_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _softmax_impl(x, block_rows: int = None, interpret: bool = False):
    rows, cols = x.shape
    block_rows = _resolve_block_rows(rows, cols, block_rows)
    assert fits(rows, cols, block_rows), x.shape
    return pl.pallas_call(
        _softmax_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="softmax_rows",
        interpret=interpret,
    )(x)
