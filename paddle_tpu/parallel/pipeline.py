"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh
axis, the scaling-book recipe done with ``shard_map`` + ``lax.scan`` +
``lax.ppermute``.

The reference's closest capability is layer-placement model parallelism
(ParallelNeuralNetwork.h:34,61-63: per-layer deviceId dispatch across
threads).  The TPU-native version: identical layer blocks' parameters
are *stacked* on a leading dim and sharded over the ``pp`` axis, so
each chip holds a contiguous stage of layers; activations hop stages
over ICI via ppermute while microbatches stream through, and the whole
schedule — bubbles included — is one compiled XLA program.
Reverse-mode AD through scan+ppermute yields the 1F1B-ish backward
schedule automatically.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _pipeline_local(layer_fn, stacked_params, x_mb, n_microbatch):
    """No-pp fallback: scan microbatches through all layers locally."""

    def through_layers(h):
        def body(h, p):
            return layer_fn(p, h), None

        h, _ = lax.scan(body, h, stacked_params)
        return h

    return lax.map(through_layers, x_mb)


def gpipe(layer_fn: Callable, stacked_params, x, *, mesh, pp_axis: str,
          n_microbatch: int, batch_axis: Optional[str] = None,
          sp_axis: Optional[str] = None):
    """Run ``x`` through L stacked layers, pipelined over ``pp_axis``.

    layer_fn(params_i, h) -> h   (one transformer block, pure jnp; may
        use ``sp_axis`` collectives, e.g. ring attention, when given)
    stacked_params: pytree of (L, ...) arrays, L = total layers.
    x: (B, S, ...) global activations; microbatched on dim 0.

    Microbatch membership contract: rows are assigned round-robin (row
    r lands in microbatch ``r % n_microbatch``), not in contiguous
    chunks as canonical GPipe slices them; the inverse mapping restores
    row order on output.  Per-row layer_fns are unaffected, but any
    batch-coupled computation inside layer_fn (e.g. batch statistics)
    sees different groupings than a contiguous split would produce.

    n_microbatch must divide the batch; the pp axis size must divide L.
    """
    B = x.shape[0]
    assert B % n_microbatch == 0, (B, n_microbatch)
    # Split the batch with the dp-sharded factor MAJOR: (B,..) P(dp,..)
    # -> (B/M, M, ..) keeps dp on dim 0 without data movement, and the
    # swapaxes to microbatch-major is a free dim permutation for GSPMD.
    # Reshaping directly to (M, B/M, ..) would land dp on the microbatch
    # dim and force an involuntary full rematerialization at the
    # shard_map boundary (each microbatch is just a batch partition, so
    # the interleaved assignment is semantically equivalent; the inverse
    # mapping below restores the original row order exactly).
    x_mb = x.reshape((B // n_microbatch, n_microbatch) + x.shape[1:]
                     ).swapaxes(0, 1)

    def un_mb(out):
        return out.swapaxes(0, 1).reshape((B,) + x.shape[1:])

    if mesh is None or pp_axis is None:
        out = _pipeline_local(layer_fn, stacked_params, x_mb, n_microbatch)
        return un_mb(out)

    n_stages = mesh.shape[pp_axis]

    def run(params_local, x_loc):
        # params_local: (L/pp, ...) slices; x_loc: (M, Bm_loc, S_loc, ...)
        s_idx = lax.axis_index(pp_axis)
        M = x_loc.shape[0]
        T = M + n_stages - 1

        def stage_body(h):
            def body(h, p):
                return layer_fn(p, h), None

            h, _ = lax.scan(body, h, params_local)
            return h

        mb_shape = x_loc.shape[1:]
        out0 = jnp.zeros((M,) + mb_shape, x_loc.dtype)
        recv0 = jnp.zeros(mb_shape, x_loc.dtype)

        def step(carry, t):
            recv, out = carry
            # stage 0 injects microbatch t (clamped; masked later)
            inject = x_loc[jnp.clip(t, 0, M - 1)]
            h_in = jnp.where(s_idx == 0, inject, recv)
            y = stage_body(h_in)
            # last stage writes finished microbatch t-(S-1)
            w = t - (n_stages - 1)
            valid = jnp.logical_and(s_idx == n_stages - 1,
                                    jnp.logical_and(w >= 0, w < M))
            upd = jnp.where(valid, y, out[jnp.clip(w, 0, M - 1)])
            out = lax.dynamic_update_index_in_dim(
                out, upd, jnp.clip(w, 0, M - 1), 0)
            # hand y to the next stage (no wraparound: last stage's
            # output leaves the ring via the out buffer)
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            recv_next = lax.ppermute(y, pp_axis, perm)
            return (recv_next, out), None

        (recv, out), _ = lax.scan(step, (recv0, out0), jnp.arange(T))
        # replicate the result over pp (only last stage holds it)
        mask = (s_idx == n_stages - 1).astype(out.dtype)
        return lax.psum(out * mask, pp_axis)

    pspec = jax.tree.map(lambda _: P(pp_axis), stacked_params)
    xspec = P(None, batch_axis, sp_axis) if x_mb.ndim >= 3 else P(None, batch_axis)
    mapped = jax.shard_map(run, mesh=mesh, in_specs=(pspec, xspec),
                           out_specs=xspec, check_vma=False)
    out = mapped(stacked_params, x_mb)
    return un_mb(out)
