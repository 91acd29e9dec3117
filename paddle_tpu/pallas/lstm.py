"""Fused whole-sequence LSTM kernel.

Reference analog: paddle/cuda/src/hl_cuda_lstm.cu (hl_lstm.h:42) — the
era's hand-written fused LSTM time step.  The TPU version fuses MORE
than the CUDA one could: a single ``pallas_call`` runs the entire
sequence with the recurrent weight matrix and the (h, c) state resident
in VMEM across all grid steps, so per-step HBM traffic is just the
pre-projected gate block in and the hidden block out.  The XLA
``lax.scan`` lowering re-streams the (H, 4H) weight from HBM every step
and pays per-step kernel overheads — exactly the costs that dominate at
the small (B, H) of the reference's RNN benchmarks.

Forward-only kernel + custom vjp: the forward also writes the activated
gates, so the backward is a reverse ``lax.scan`` of pure elementwise
algebra plus the unavoidable dgates@W^T / h^T@dgates matmuls.

Gate order matches the reference lstm_op.cc: i, f, candidate, o.
Activations fixed to the defaults (sigmoid gates, tanh candidate/cell);
callers with exotic activations fall back to the XLA scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_kernel(xp_ref, w_ref, b_ref, h0_ref, c0_ref,
                 hs_ref, cs_ref, gates_ref, h_s, c_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[:] = h0_ref[:].astype(jnp.float32)
        c_s[:] = c0_ref[:].astype(jnp.float32)

    xt = xp_ref[0].astype(jnp.float32)          # (B, 4H)
    gates = xt + jnp.dot(h_s[:].astype(w_ref.dtype), w_ref[:],
                         preferred_element_type=jnp.float32)
    gates = gates + b_ref[:].astype(jnp.float32)
    d = h_s.shape[-1]
    i = jax.nn.sigmoid(gates[:, 0 * d:1 * d])
    f = jax.nn.sigmoid(gates[:, 1 * d:2 * d])
    g = jnp.tanh(gates[:, 2 * d:3 * d])
    o = jax.nn.sigmoid(gates[:, 3 * d:4 * d])
    c_new = f * c_s[:] + i * g
    h_new = o * jnp.tanh(c_new)
    c_s[:] = c_new
    h_s[:] = h_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)
    gates_ref[0] = jnp.concatenate([i, f, g, o], axis=-1).astype(gates_ref.dtype)


def fits(b, h, vmem_budget=10 * 1024 * 1024) -> bool:
    if b % 8 != 0 or h % 128 != 0:
        return False
    # resident: W (H,4H) f32-ish + x block + gates + 2 state buffers
    resident = 4 * h * 4 * h + 4 * b * 4 * h * 2 + 4 * b * h * 4
    return resident <= vmem_budget


@functools.partial(jax.jit, static_argnames=("interpret",))
def _lstm_seq_impl(xproj, w, bias, h0, c0, interpret: bool = False):
    T, B, H4 = xproj.shape
    H = H4 // 4
    return pl.pallas_call(
        _lstm_kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
            pl.BlockSpec((H, H4), lambda t: (0, 0)),
            pl.BlockSpec((1, H4), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, B, H), lambda t: (t, 0, 0)),
            pl.BlockSpec((1, B, H4), lambda t: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B, H), xproj.dtype),
            jax.ShapeDtypeStruct((T, B, H), xproj.dtype),
            jax.ShapeDtypeStruct((T, B, H4), xproj.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="lstm_fused",
        interpret=interpret,
    )(xproj, w, bias.reshape(1, H4), h0, c0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def lstm_seq(xproj, w, bias, h0, c0, interpret: bool = False):
    """(T, B, 4H) pre-projected gates -> ((T, B, H) hidden, (T, B, H) cell).

    Default activations, no peepholes.  Differentiable.
    """
    hs, cs, _ = _lstm_seq_impl(xproj, w, bias, h0, c0, interpret)
    return hs, cs


def _lstm_seq_fwd(xproj, w, bias, h0, c0, interpret):
    hs, cs, gates = _lstm_seq_impl(xproj, w, bias, h0, c0, interpret)
    return (hs, cs), (gates, hs, cs, w, h0, c0, bias)


def _lstm_seq_bwd(interpret, res, cots):
    gates, hs, cs, w, h0, c0, bias = res
    dhs, dcs = cots
    T, B, H = hs.shape
    f32 = jnp.float32

    h_prev = jnp.concatenate([h0[None], hs[:-1]], axis=0)  # (T, B, H)
    c_prev = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def step(carry, inp):
        dh_next, dc_next = carry                  # grads flowing from t+1
        g4, c_t, c_pr, h_pr, dh_out, dc_out = inp
        i = g4[:, 0 * H:1 * H].astype(f32)
        f = g4[:, 1 * H:2 * H].astype(f32)
        g = g4[:, 2 * H:3 * H].astype(f32)
        o = g4[:, 3 * H:4 * H].astype(f32)
        tanh_c = jnp.tanh(c_t.astype(f32))
        dh = dh_next + dh_out.astype(f32)
        dc = dc_next + dc_out.astype(f32) + dh * o * (1 - tanh_c ** 2)
        do = dh * tanh_c
        di = dc * g
        dg = dc * i
        df = dc * c_pr.astype(f32)
        dgates = jnp.concatenate([
            di * i * (1 - i), df * f * (1 - f),
            dg * (1 - g ** 2), do * o * (1 - o)], axis=-1)
        dh_prev = jnp.dot(dgates.astype(w.dtype), w.T,
                          preferred_element_type=f32)
        dw_t = jnp.dot(h_pr.astype(w.dtype).T, dgates.astype(w.dtype),
                       preferred_element_type=f32)
        return (dh_prev, dc * f), (dgates, dw_t)

    (dh0, dc0), (dxproj, dw_t) = lax.scan(
        step, (jnp.zeros((B, H), f32), jnp.zeros((B, H), f32)),
        (gates, cs, c_prev, h_prev, dhs, dcs), reverse=True)
    dw = jnp.sum(dw_t, axis=0)
    dbias = jnp.sum(dxproj, axis=(0, 1)).reshape(bias.shape)
    return (dxproj.astype(hs.dtype), dw.astype(w.dtype),
            dbias.astype(bias.dtype), dh0.astype(hs.dtype),
            dc0.astype(hs.dtype))


lstm_seq.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)
