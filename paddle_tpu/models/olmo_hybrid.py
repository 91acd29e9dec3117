"""Olmo-Hybrid (allenai/Olmo-Hybrid-7B, ``model_type`` olmo_hybrid)
behind ``/generate``: Gated-DeltaNet layers (the gated delta rule,
arXiv:2412.06464) three of four, a full softmax-attention layer the
fourth, as ONE pipeline stage serves the layers it holds.

The block (OLMo 2/3 convention: the norm follows the sublayer):
``x <- x + RMSNorm(mixer(x))``, ``x <- x + RMSNorm(SwiGLU(x))``, a final
RMSNorm and an untied head; no bias anywhere.

A **linear** layer, per head ``h`` with key width ``d_k`` and value
width ``d_v`` (``u``: the layer's input rows):

    z = [W_q u; W_k u; W_v u], a causal depthwise conv of width 4 over
        each channel (taps on rows t-3..t, zeros before row 0), SiLU
    q_t <- q_t / |q_t| * d_k^-1/2,  k_t <- k_t / |k_t|
    beta_t = 2 sigmoid(W_b u),  alpha_t = exp(-exp(A_log)
        softplus(W_a u + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t;  y_t = W_o [RMSNorm_{d_v}(o_t) * silu(W_g u)]

with the state ``S`` (d_v, d_k) zero before row 0.  Computed two ways,
each by a Pallas kernel where ``paddle_tpu.pallas`` says so and by its
XLA reference elsewhere.  Over a prompt **chunked**: inside a chunk
matmuls and one unit-lower-triangular solve, the state carried chunk to
chunk, no loop over rows; by ONE ``pallas/gated_delta_chunked.py`` call
a layer where ``pallas.use_gated_delta_chunked`` says so (a bucket of
whole 128-row chunks: the state stays in VMEM across a head's chunks
and the solve is an inverse built by doubling, all matmuls), else by
``chunked_gated_delta`` (chunks of 64: for all chunks at once the
products and ``solve_triangular``, then a ``lax.scan`` over the
chunks).  Over a decode step's rows one token on each slot's state: by
ONE Pallas call a layer where ``pallas.use_gated_delta_step`` says so
(``pallas/gated_delta.py``: the slots' entries scalar-prefetched, each
read once and written once where it lies, after ONE
``pallas/conv_step.py`` call over the rows the same entries keep for
the conv), else slot by slot in XLA over ``step_conv`` and
``step_gated_delta``.  The plain recurrence, row by row, is the
benchmark's reference (``perf/reference/olmo_hybrid_block.py``).

A **full** layer: q, k, v of 30 heads of 128, an RMSNorm over the whole
q and the whole k projection (OLMo's), causal softmax, no positional
rotation (``rope_theta`` null).

Two resources a sequence, from the one cache manager
(``decode/paged_kv.py:CacheManager``): its page run, which the full
layers alone write (pools ``(full layers, N, pg, H, dh)``), and ONE
state entry, whatever its length: every linear layer's ``S`` (float32)
and the last three rows that layer's conv saw (pools ``(linear layers,
entries, ...)``, entry 0 the null entry).  Its table row is the page
run's columns, then the entry.  What needs a state as it stood at an
earlier row is refused by name (``UnsupportedOverState``): a prefill
over cached pages, a fork, the speculative verify.  All of that is
``decode/state_entry.py``'s, shared with ``models/granite_hybrid.py``;
this file says how its own layers mix tokens and how a page and an
entry hold them.

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, gates, decay and state; the rows
the conv sees are kept in the weights' dtype, on both paths, so that a
tail written by the prefill is what the step would have kept.  Random
weights only: loading a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode.attention import storage_heads
from paddle_tpu.decode.state_entry import (  # noqa: F401  (re-exported)
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    _pad_axis,
    _pad_last,
    causal_conv,
    conv_over_entries,
    conv_tail,
    step_conv,
    tail_shape,
)
from paddle_tpu.models.exaone_moe import swiglu
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.pallas.gated_delta import gated_delta_step
from paddle_tpu.pallas.gated_delta_chunked import gated_delta_chunked

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"
CHUNK = 64
L2_EPS = 1e-6


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


LANES = 128


def stored_key_width(d_k: int) -> int:
    """The key width a state entry is stored at: ``d_k`` rounded up to
    the chip's 128 lanes, zeros beyond ``d_k``.  The tiles would pad a
    row of 96 floats to 128 anyway; stored so, the pool's row-major
    layout has no padding, the compiler keeps it, a slot's entry is one
    contiguous block (at (30, 192, 96) it laid the entries out
    innermost and sliced the whole pool for a step's gather) and the
    step kernel's blocks of it are whole tiles (its ``fits()`` asks
    for a multiple of 128).  A quarter of what a step moves is this
    padding: four heads' 96 keys packed into 384 lanes would store
    none (PERF.md section 7)."""
    return -(-int(d_k) // LANES) * LANES


def _pad_heads(x, heads):
    """``x`` (..., H, dh) with zero heads appended up to ``heads``."""
    return _pad_axis(x, -2, heads)


def chunked_gated_delta(q, k, v, g, beta, state, chunk=CHUNK):
    """The gated delta rule over T rows, chunked.  ``q``, ``k``
    (T, H, d_k) already normalised and scaled, ``v`` (T, H, d_v), ``g``
    (T, H) the log of the decay, ``beta`` (T, H), ``state`` (H, d_v,
    d_k) as it stood before row 0 -> (o (T, H, d_v), the state after
    row T - 1).  All float32.

    Inside a chunk, with ``G_t`` the running sum of ``g`` and ``u_t =
    beta_t (v_t - alpha_t S_{t-1} k_t)`` the row's write: ``(I + A) U =
    B V - B e^G K S_0^T`` with ``A[t, s] = beta_t e^(G_t - G_s) k_t.k_s``
    strictly below the diagonal, one triangular solve for both right
    hand sides; ``O = e^G Q S_0^T + M U`` with ``M[t, s] = e^(G_t - G_s)
    q_t.k_s`` on and below it; ``S_C = e^(G_C) S_0 + U^T (e^(G_C - G)
    K)``.  Everything but the three terms in ``S_0`` is computed for
    all chunks at once; a scan over the chunks carries the state.  A
    row with ``g = 0, beta = 0`` (a bucket's padding) leaves the state
    as it was."""
    T, H, dk = q.shape
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // C

    def chunks(a):          # (T, H, ...) -> (n, H, C, ...)
        return jnp.moveaxis(a.reshape((n, C) + a.shape[1:]), 2, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                                # (n, H, C)
    t = jnp.arange(C)
    below = t[:, None] >= t[None, :]
    decay = jnp.exp(jnp.where(below, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                      # (n, H, C, C)
    kk = jnp.einsum("nhtk,nhsk->nhts", k, k, precision=_HIGHEST)
    A = jnp.where(t[:, None] > t[None, :],
                  beta[..., None] * decay * kk, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate([beta[..., None] * v,
                           beta[..., None] * eG * k], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=_F32), rhs, lower=True, unit_diagonal=True)
    U0, W = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
    M = decay * jnp.einsum("nhtk,nhsk->nhts", q, k, precision=_HIGHEST)
    Qg = eG * q
    Kd = jnp.exp(G[..., -1:] - G)[..., None] * k
    g_end = jnp.exp(G[..., -1])[..., None, None]              # (n, H, 1, 1)

    def one(S, c):
        U0c, Wc, Mc, Qgc, Kdc, gc = c
        U = U0c - jnp.einsum("hck,hvk->hcv", Wc, S, precision=_HIGHEST)
        O = (jnp.einsum("hck,hvk->hcv", Qgc, S, precision=_HIGHEST)
             + jnp.einsum("hcs,hsv->hcv", Mc, U, precision=_HIGHEST))
        S = gc * S + jnp.einsum("hcv,hck->hvk", U, Kdc, precision=_HIGHEST)
        return S, O

    state, O = jax.lax.scan(one, state.astype(_F32),
                            (U0, W, M, Qg, Kd, g_end))
    o = jnp.moveaxis(O, 1, 2).reshape(n * C, H, -1)
    return o[:T], state


def step_gated_delta(q, k, v, g, beta, state):
    """One row on a state (any leading shape: a slot's, or the slots'):
    ``q``, ``k`` (..., H, d_k), ``v`` (..., H, d_v), ``g``, ``beta``
    (..., H), ``state`` (..., H, d_v, d_k) -> (o (..., H, d_v), the new
    state).  Multiply-reduces, float32; ``o = S_t q`` is taken from the
    old state's two products.  ``pallas/gated_delta.py`` is this, in
    this order, on blocks of the pool in VMEM."""
    alpha = jnp.exp(g)[..., None]                             # (S, H, 1)
    Sk = jnp.sum(state * k[..., None, :], axis=-1)            # (S, H, d_v)
    Sq = jnp.sum(state * q[..., None, :], axis=-1)
    u = beta[..., None] * (v - alpha * Sk)
    new = alpha[..., None] * state + u[..., None] * k[..., None, :]
    o = alpha * Sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, new


@dataclasses.dataclass(frozen=True)
class OlmoHybridBlock(StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side: a
    full layer's mixer is ``PageRunCache``'s over the page run's
    columns of the table and the pools of the full layers alone; a
    linear layer's is the gated delta rule over the sequence's state
    entry.  The cache is ``(k_pool, v_pool, state_pool, conv_pool)``.
    ``at``: the layer this view of the block is (``layer``)."""

    recurrent_kind = LINEAR
    layer_types: tuple = (LINEAR, LINEAR, LINEAR, FULL)
    head_dim: int = 128
    lin_heads: int = 30
    d_k: int = 96
    d_v: int = 192
    eps: float = 1e-6
    full_pages: int = 36         # table columns of the page run
    at: int = 0

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def qkv(self, lp, x, pos, heads):
        """A full layer's: RMSNorm over the whole q and k projections,
        no rotation."""
        split = x.shape[:-1] + (heads, self.head_dim)
        q = rms_norm(_mm(x, lp["wq"]), lp["w_qn"], self.eps).reshape(split)
        k = rms_norm(_mm(x, lp["wk"]), lp["w_kn"], self.eps).reshape(split)
        v = _mm(x, lp["wv"]).reshape(split)
        dtype = lp["wq"].dtype
        return q.astype(dtype), k.astype(dtype), v.astype(dtype)

    def attn_out(self, lp, x, a):
        return x + rms_norm(_mm(a, lp["wo"]), lp["w_mix_norm"], self.eps)

    def mlp(self, lp, x, live):
        m = x.astype(lp["w_gate"].dtype)
        y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + rms_norm(y, lp["w_ff_norm"], self.eps), None

    def head(self, params, x):
        return _mm(rms_norm(x, params["w_f"], self.eps), params["lm_head"])

    # -- a linear layer's pieces --------------------------------------------

    def _projections(self, lp, x):
        """-> (the rows the conv sees, in the weights' dtype; the output
        gate's; log decay ``g`` and ``beta``, (..., H))."""
        z = _mm(x, lp["w_qkv"]).astype(lp["w_qkv"].dtype)
        gate = _mm(x, lp["w_g"])
        beta = 2.0 * jax.nn.sigmoid(_mm(x, lp["w_b"]))
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
            _mm(x, lp["w_a"]) + lp["dt_bias"])
        return z, gate, g, beta

    def _split(self, zc):
        """The conv's output rows -> q, k (..., H, d_k) normalised and
        scaled, v (..., H, d_v)."""
        H, dk, dv = self.lin_heads, self.d_k, self.d_v
        lead = zc.shape[:-1]
        q = l2_normalize(zc[..., :H * dk].reshape(lead + (H, dk)))
        k = l2_normalize(zc[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = zc[..., 2 * H * dk:].reshape(lead + (H, dv))
        return q * dk ** -0.5, k, v

    def _gated_norm(self, lp, o, gate):
        """RMSNorm over each head's d_v channels times silu(gate), the
        heads side by side."""
        y = rms_norm(o, lp["w_on"], self.eps) * jax.nn.silu(
            gate.reshape(o.shape))
        return y.reshape(o.shape[:-2] + (-1,))

    def _lin_out(self, lp, x, y):
        """The output projection and the block's residual."""
        return x + rms_norm(_mm(y, lp["w_o"]), lp["w_mix_norm"], self.eps)

    # -- the cache side of a full layer ---------------------------------------

    def store_prompt(self, pool, rows, flat):
        return super().store_prompt(pool, _pad_heads(rows, pool.shape[3]),
                                    flat)

    def cached_attention(self, k_pool, v_pool, li, q, k, v, flat, tables,
                         lens):
        """The pools hold the full layers alone, at the heads a page is
        stored at (``attention.storage_heads``: q, k and v are padded to
        it with zero heads, whose outputs are dropped), and the table's
        first columns are the page run."""
        H, Hs = q.shape[-2], k_pool.shape[3]
        a, k_pool, v_pool = super().cached_attention(   # under ``attn_full``
            k_pool, v_pool, self.index_in_kind, _pad_heads(q, Hs),
            _pad_heads(k, Hs), _pad_heads(v, Hs), flat,
            tables[:, :self.full_pages], lens)
        return a[..., :H, :], k_pool, v_pool

    # -- the mixers ---------------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        if not self.recurrent:
            return super().prompt_mixer(lp, x, pos, heads, live)
        T = x.shape[0]
        z, gate, g, beta = self._projections(lp, x)
        n = T if live is None else jnp.sum(live.astype(jnp.int32))
        if live is not None:
            # a recurrence sees padding that causal attention hides:
            # rows from n on neither decay the state nor write to it
            g = jnp.where(live[:, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
        with jax.named_scope("lin_attn"):
            with jax.named_scope("lin_attn_conv"):
                zc = jax.nn.silu(causal_conv(z, lp["w_conv"]))
                tail = conv_tail(z, lp["w_conv"].shape[0], n)
            q, k, v = self._split(zc)
            with jax.named_scope("lin_attn_scan"):
                state = jnp.zeros((self.lin_heads, self.d_v, self.d_k), _F32)
                if pk.use_gated_delta_chunked(state.dtype, T, *state.shape):
                    o, state = gated_delta_chunked(
                        q, k, v, g, beta, state,
                        interpret=pk.interpret_mode())
                else:
                    o, state = chunked_gated_delta(q, k, v, g, beta, state)
            y = self._gated_norm(lp, o, gate)
        return self._lin_out(lp, x, y), (state, tail)

    def recurrent_step(self, lp, x, cache, addr):
        k_pool, v_pool, state_pool, conv_pool = cache
        z, gate, g, beta = self._projections(lp, x)
        at = self.entries_of(state_pool, addr)
        advance = (self._advance_by_kernel if pk.use_gated_delta_step(
            state_pool.dtype, *state_pool.shape[2:])
            else self._advance_slot_by_slot)
        with jax.named_scope("lin_attn"):
            o, states, tails = advance(
                lp["w_conv"],
                state_pool.reshape((-1,) + state_pool.shape[2:]),
                conv_pool.reshape((-1,) + conv_pool.shape[2:]),
                at, z, g, beta)
            y = self._gated_norm(lp, o, gate)
        return (self._lin_out(lp, x, y),
                (k_pool, v_pool, states.reshape(state_pool.shape),
                 tails.reshape(conv_pool.shape)))

    def _advance_by_kernel(self, w, states, tails, at, z, g, beta):
        """The step's rows on the slots' entries (``at``, in the pools
        seen flat) -> (o (S, H, d_v), the pools).  The conv over the
        rows the entries keep, then every slot's state advanced, each
        by one call over its pool where it lies
        (``pallas/conv_step.py`` through ``conv_over_entries``,
        ``pallas/gated_delta.py``): no loop over the slots."""
        with jax.named_scope("lin_attn_conv"):
            zc, tails = conv_over_entries(tails, at, z, w)
        q, k, v = self._split(zc)
        with jax.named_scope("lin_attn_state"):
            wide = states.shape[-1]                  # the keys as stored
            o, states = gated_delta_step(
                states, at, _pad_last(q, wide), _pad_last(k, wide), v, g,
                beta, interpret=pk.interpret_mode())
        return o, states, tails

    def _advance_slot_by_slot(self, w, states, tails, at, z, g, beta):
        """The same in plain XLA, the kernels' reference and what runs
        where the state kernel does not fit: one loop over the slots
        for both pools."""
        wide = states.shape[-1]

        def one_slot(pools, slot):
            """One slot's entry read, advanced by its row and written
            back where it lies.  Slot by slot: XLA lowers a gather of
            the step's entries as slices of the whole pool (a 2.9 MB
            entry is no row to the compiler); a slot at a time each
            entry moves in place, read twice and written once."""
            states, tails = pools
            e, z_s, g_s, beta_s = slot
            with jax.named_scope("lin_attn_conv"):
                kept = jax.lax.dynamic_index_in_dim(tails, e, 0, False)
                zc, kept = step_conv(kept.reshape(w.shape[0] - 1, -1), z_s,
                                     w)
                tails = jax.lax.dynamic_update_index_in_dim(
                    tails, kept.reshape(tails.shape[1:]), e, 0)
            q, k, v = self._split(zc)
            with jax.named_scope("lin_attn_state"):
                o, new = step_gated_delta(
                    _pad_last(q, wide), _pad_last(k, wide), v, g_s, beta_s,
                    jax.lax.dynamic_index_in_dim(states, e, 0, False))
                states = jax.lax.dynamic_update_index_in_dim(
                    states, new, e, 0)
            return (states, tails), o

        (states, tails), o = jax.lax.scan(one_slot, (states, tails),
                                          (at, z, g, beta))
        return o, states, tails


@functools.partial(jax.jit, static_argnames=(
    "vocab", "d", "heads", "head_dim", "layer_types", "width", "lin_heads",
    "d_k", "d_v", "conv", "dtype"))
def init_params(key, *, vocab, d, heads, head_dim, layer_types, width,
                lin_heads, d_k, d_v, conv, dtype):
    """Every weight N(0, 0.02) in ``dtype`` (the conv's taps too), every
    norm scale 1; ``dt_bias`` the inverse softplus of a step drawn
    log-uniform in [0.001, 0.1] and ``A_log`` uniform in [log 0.5, 0],
    both float32, so that a head's decay ``alpha`` lies in about
    0.9 .. 0.9995 a token (the projection's part of the step is small
    at these weights); made on the device by this one program."""
    def normal(k, *shape):
        return (jax.random.normal(k, shape, _F32) * 0.02).astype(dtype)

    ones = jnp.ones((d,), dtype)
    ks = jax.random.split(key, 2 + len(layer_types))
    params = {"emb": normal(ks[0], vocab, d), "w_f": ones,
              "lm_head": normal(ks[1], d, vocab), "layers": []}
    H = lin_heads
    for i, kind in enumerate(layer_types):
        lk = jax.random.split(ks[2 + i], 12)
        lp = {"w_mix_norm": ones, "w_ff_norm": ones,
              "w_gate": normal(lk[0], d, width),
              "w_up": normal(lk[1], d, width),
              "w_down": normal(lk[2], width, d)}
        if kind == FULL:
            hd = heads * head_dim
            lp.update(w_qn=jnp.ones((hd,), dtype), w_kn=jnp.ones((hd,), dtype),
                      wq=normal(lk[3], d, hd), wk=normal(lk[4], d, hd),
                      wv=normal(lk[5], d, hd), wo=normal(lk[6], hd, d))
        else:
            step = jnp.exp(jax.random.uniform(
                lk[10], (H,), _F32, np.log(0.001), np.log(0.1)))
            lp.update(
                w_qkv=normal(lk[3], d, H * (2 * d_k + d_v)),
                w_conv=normal(lk[4], conv, H * (2 * d_k + d_v)),
                w_g=normal(lk[5], d, H * d_v), w_b=normal(lk[6], d, H),
                w_a=normal(lk[7], d, H), w_o=normal(lk[8], H * d_v, d),
                w_on=jnp.ones((d_v,), dtype),
                dt_bias=jnp.log(jnp.expm1(step)),
                A_log=jax.random.uniform(lk[11], (H,), _F32,
                                         np.log(0.5), 0.0))
        params["layers"].append(lp)
    return params


class OlmoHybridLM(StateEntryLM):
    """The layers one pipeline stage holds of Olmo-Hybrid over the paged
    skeleton: what ``make_decode_model()`` returns
    (``perf/configs/olmo-hybrid-7b.gen_config.py``).  The reservation,
    the table row and the refusals are ``decode/state_entry.py``'s."""

    def __init__(self, vocab: int = 100352, d_model: int = 3840,
                 num_heads: int = 30, head_dim: int = 128,
                 layer_types: Sequence[str] = (LINEAR, LINEAR, LINEAR, FULL),
                 intermediate_size: int = 11008,
                 linear_num_key_heads: int = 30,
                 linear_num_value_heads: int = 30,
                 linear_key_head_dim: int = 96,
                 linear_value_head_dim: int = 192,
                 linear_conv_kernel_dim: int = 4,
                 rms_norm_eps: float = 1e-6, max_len: int = 4608,
                 num_pages: int = 64, page_size: int = 128,
                 pages_per_seq: int = 36, state_entries: int = 9,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        layer_types = tuple(layer_types)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if linear_num_key_heads != linear_num_value_heads:
            raise ValueError("more value heads than key heads is not "
                             "laid out: one state a head")
        self.dh = int(head_dim)
        self._count_layers(layer_types, LINEAR)
        self.block = OlmoHybridBlock(
            layer_types=layer_types, head_dim=self.dh,
            lin_heads=int(linear_num_key_heads),
            d_k=int(linear_key_head_dim), d_v=int(linear_value_head_dim),
            eps=float(rms_norm_eps), full_pages=self.full_pages)
        dtype = jnp.dtype(dtype)
        self.conv_taps = int(linear_conv_kernel_dim)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, head_dim=self.dh, layer_types=layer_types,
            width=int(intermediate_size), lin_heads=self.block.lin_heads,
            d_k=self.block.d_k, d_v=self.block.d_v, conv=self.conv_taps,
            dtype=dtype)
        b = self.block
        # a page's heads as stored: 30 of bfloat16 are stored as 32
        self.stored_heads = storage_heads(self.heads, dtype)
        self._make_pools(
            num_pages, dtype, int(state_entries),
            (self.stored_heads, self.dh),
            (b.lin_heads, b.d_v, stored_key_width(b.d_k)),
            tail_shape(self.conv_taps,
                       b.lin_heads * (2 * b.d_k + b.d_v)))
