"""Embedding gather kernel (reference analog: operators/
lookup_table_op.cu LookupTable kernel).

Classic scalar-prefetch gather: ids are prefetched to SMEM, and each
grid step's *index map* uses them to choose which table row block to
DMA — the copy engine does the gather, no VMEM-side indexing."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(ids_ref, w_ref, o_ref):
    o_ref[:] = w_ref[:]


def fits(n, dim) -> bool:
    return dim % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gather_rows(w, ids, interpret: bool = False):
    return _gather_impl(w, ids, interpret)


def _gather_fwd(w, ids, interpret):
    # residuals must be JAX types (a np.dtype is not): keep ids + the
    # static shape; the cotangent g already has w's dtype (out = w[ids])
    return _gather_impl(w, ids, interpret), (ids, w.shape)


def _gather_bwd(interpret, res, g):
    ids, wshape = res
    gw = jnp.zeros(wshape, g.dtype).at[ids].add(g)
    return gw, None


gather_rows.defvjp(_gather_fwd, _gather_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_impl(w, ids, interpret: bool = False):
    """w: (V, D), ids: (N,) int32 -> (N, D)."""
    n = ids.shape[0]
    v, d = w.shape
    assert fits(n, d), (n, d)
    # (V, 1, D) rows: a (1, 1, D) block's trailing dims match the array,
    # satisfying the mosaic tiling rule while the index map gathers rows
    w3 = w.reshape(v, 1, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, ids_ref: (ids_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i, ids_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), w.dtype),
        name="embedding_gather",
        interpret=interpret,
    )(ids.astype(jnp.int32), w3)
    return out.reshape(n, d)
