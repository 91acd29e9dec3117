"""Granite-4.0-H (ibm-granite/granite-4.0-h-micro, ``model_type``
granitemoehybrid) behind ``/generate``: Mamba-2 state-space layers
(SSD, arXiv:2405.21060) nine of ten, a grouped-query attention layer
the tenth, no experts (``num_local_experts`` 0: the feed-forward is the
shared SwiGLU alone), whole on one chip.

The block (pre-norm, Granite's four scalar multipliers):

    x0 = embedding_multiplier * E[token]
    h = x + residual_multiplier * mixer(RMSNorm(x))
    x = h + residual_multiplier * SwiGLU(RMSNorm(h))
    logits = E^T RMSNorm(x_L) / logits_scaling          (the head is tied)

A **mamba** layer, ``H`` heads of ``P`` channels, state size ``N``, one
group (``B`` and ``C`` shared by all heads: Granite's ``mamba_n_groups``
1; with ``G`` groups, Nemotron-H's 8, heads ``g H/G .. (g + 1) H/G - 1``
read ``B_g`` and ``C_g``, ``xBC`` holds ``G`` of each and the gated norm
runs over each group's channels on its own: ``models/nemotron_h.py``
stands on this block), ``u`` the normed input:

    [z; xBC; dt] = W_in u;  xBC_t <- silu(conv4(xBC)_t + b)  (depthwise,
        causal, zeros before row 0);  x_t (H, P), B_t, C_t (N) = xBC_t
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
    out = W_out (RMSNorm_{H P}(y_t * silu(z_t)) * w)    (gate, then norm)

with the state ``S`` (H, P, N) zero before row 0 (an entry stores it
with the state index down the rows and two heads' channels along the
lanes: ``pack_state``).  Computed two ways:
over a prompt **chunked** (``chunked_ssd``: inside a chunk of ``CHUNK``
rows matmuls against the decay mask, the state carried chunk to chunk;
no loop over rows, no triangular solve), and over a decode step's rows
one token on each slot's state: by ONE Pallas call a layer where
``pallas.use_ssd_step`` says so (``pallas/ssd_step.py``: the slots'
entries scalar-prefetched, each read once and written once where it
lies), else gathered, advanced by ``step_ssd`` and scattered in XLA,
the kernel's reference.  The conv before it runs over the step's rows
and the three rows of 4,352 channels each slot's entry keeps, by the
same entry index: ONE ``pallas/conv_step.py`` call a layer over the
tail pool where it lies (an entry ``(102, 128)``, its kept rows one
after another in rows of lanes: ``state_entry.tail_shape``), else
gathered, advanced and scattered in XLA
(``state_entry.conv_over_entries``); no loop over the slots.  The
plain recurrence, row by row, is the benchmark's
reference (``perf/reference/granite_hybrid_block.py``).

An **attention** layer: 32 query heads on 8 K/V heads of 64, no bias,
no q/k norm, no rotation (``position_embedding_type`` nope); causal
softmax of ``attention_multiplier * q.k`` (1/64, not ``64^-1/2``).  The
multiplier reaches the kernels folded into q: ``attention_multiplier *
64^1/2`` = 1/8, a power of two, so nothing is rounded; the kernels then
scale by ``64^-1/2`` (the flash prefill by its default, the paged step
told so).  **Pages hold two heads a row**: a page's row is stored as
``kv_heads / 2`` heads of 128 lanes, K/V heads ``2j`` and ``2j + 1``
side by side, so that a row is whole lanes with nothing padded
(``pack``).  The grouped paged kernel runs on it as it is: a query head
that reads K/V head ``2j`` carries its 64 numbers in lanes 0..63 and
zeros beyond (the other half's keys add exact zeros to its scores), one
that reads ``2j + 1`` the other way round, and of the 128 output lanes
each takes its own half.

The cache, the reservation and what is refused are
``decode/state_entry.py``'s, shared with ``models/olmo_hybrid.py``.

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
from paddle_tpu.decode.attention import paged_attention
from paddle_tpu.decode.model import _layer_pages, _write_rows
from paddle_tpu.decode.state_entry import (  # noqa: F401  (re-exported)
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    causal_conv,
    conv_over_entries,
    conv_tail,
    tail_shape,
)
from paddle_tpu.models.exaone_moe import swiglu
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.pallas.ssd_step import ssd_step

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"
LANES = 128
# Rows of a prompt the recurrence takes at once.  The published
# ``mamba_chunk_size`` 256 is a setting of the reference kernel, not a
# width: the result does not depend on it.  128 divides every bucket of
# the ladder up to 1,920 rows and keeps a layer's decay masks (rows x
# heads x chunk floats) at 63 MB there.
CHUNK = 128


def heads_a_row(heads: int, head_dim: int) -> int:
    """Heads stored side by side in one row of whole lanes (K/V heads
    in a page's row, mamba heads in a state entry's): ``128 /
    head_dim`` where that divides the heads (2 at heads of 64), else 1
    (heads of 128 and more are whole lanes already)."""
    pack = LANES // head_dim if LANES % head_dim == 0 else 1
    return pack if pack > 1 and heads % pack == 0 else 1


def pack_state(state, pack: int):
    """A layer's state (..., H, P, N) as an entry stores it: (..., H /
    pack, N, pack * P), ``pack`` heads' channels side by side along the
    lanes, the state index down the rows (``pallas/ssd_step.py`` says
    why)."""
    lead, (H, P, N) = state.shape[:-3], state.shape[-3:]
    rows = state.reshape(lead + (H // pack, pack, P, N))
    return jnp.moveaxis(rows, -1, -3).reshape(
        lead + (H // pack, N, pack * P))


def unpack_state(stored, pack: int):
    """``pack_state``'s inverse."""
    lead, (R, N, lanes) = stored.shape[:-3], stored.shape[-3:]
    rows = stored.reshape(lead + (R, N, pack, lanes // pack))
    return jnp.moveaxis(rows, -3, -1).reshape(
        lead + (R * pack, lanes // pack, N))


def chunked_ssd(x, dt, g, B, C, state, chunk=CHUNK):
    """The SSD recurrence over T rows, chunked.  ``x`` (T, H, P), ``dt``
    and ``g`` (the log of the decay) (T, H), ``B``, ``C`` (T, N),
    ``state`` (H, P, N) as it stood before row 0 -> (y (T, H, P) =
    ``S_t C_t`` without the skip, the state after row T - 1).  All
    float32.  ``B``, ``C`` (T, G, N): ``G`` one-group recurrences side
    by side, each over the ``H / G`` heads of its group.

    Inside a chunk, with ``G_t`` the running sum of ``g``: ``Y = e^G (C
    S_0^T) + M (dt x)`` with ``M[t, s] = e^(G_t - G_s) C_t.B_s`` on and
    below the diagonal, and ``S_c = e^(G_c) S_0 + (e^(G_c - G) dt x)^T
    B``.  Everything but the two terms in ``S_0`` is computed for all
    chunks at once; a scan over the chunks carries the state.  A row
    with ``dt = 0`` (a bucket's padding; then ``g = 0`` too) leaves the
    state as it was."""
    T, H, P = x.shape
    if B.ndim == 3:
        G = B.shape[1]

        def by_group(a):               # the heads axis as (G, H / G)
            return a.reshape(a.shape[:1] + (G, H // G) + a.shape[2:])

        y, state = jax.vmap(
            functools.partial(chunked_ssd, chunk=chunk),
            in_axes=(1, 1, 1, 1, 1, 0), out_axes=(1, 0))(
                by_group(x), by_group(dt), by_group(g), B, C,
                state.reshape((G, H // G) + state.shape[1:]))
        return y.reshape(x.shape), state.reshape((H,) + state.shape[2:])
    c = min(chunk, T)
    pad = -T % c
    if pad:
        x, dt, g, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                          for a in (x, dt, g, B, C))
    n = (T + pad) // c
    x, dt, g, B, C = (a.reshape((n, c) + a.shape[1:])
                      for a in (x, dt, g, B, C))
    G = jnp.moveaxis(jnp.cumsum(g, axis=1), 2, 1)             # (n, H, c)
    t = jnp.arange(c)
    decay = jnp.exp(jnp.where(t[:, None] >= t[None, :],
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    CB = jnp.einsum("ntk,nsk->nts", C, B, precision=_HIGHEST)
    xdt = x * dt[..., None]                                   # (n, c, H, P)
    Y = jnp.einsum("nhts,nshp->nthp", decay * CB[:, None], xdt,
                   precision=_HIGHEST)
    to_end = jnp.moveaxis(jnp.exp(G[..., -1:] - G), 1, 2)     # (n, c, H)
    dS = jnp.einsum("nshp,nsk->nhpk", to_end[..., None] * xdt, B,
                    precision=_HIGHEST)
    eG = jnp.moveaxis(jnp.exp(G), 1, 2)                       # (n, c, H)

    def one(S, chunk_c):
        Yc, dSc, eGc, Cc = chunk_c
        Yc = Yc + eGc[..., None] * jnp.einsum(
            "hpk,tk->thp", S, Cc, precision=_HIGHEST)
        return eGc[-1][:, None, None] * S + dSc, Yc

    state, Y = jax.lax.scan(one, state.astype(_F32), (Y, dS, eG, C))
    return Y.reshape(n * c, H, P)[:T], state


def step_ssd(x, dt, g, B, C, state):
    """One row on a state (any leading shape: a slot's, or the slots'):
    ``x`` (..., H, P), ``dt``, ``g`` (..., H), ``B``, ``C`` (..., N),
    or (..., G, N) a group of ``H / G`` heads,
    ``state`` (..., H, P, N) -> (y (..., H, P) = ``S_t C_t``, the new
    state).  Multiply-reduces, float32.  ``pallas/ssd_step.py`` is
    this, in this order, on blocks of the pool in VMEM."""
    if B.ndim == x.ndim:                # a group: each head its group's
        per_group = x.shape[-2] // B.shape[-2]
        B, C = (jnp.repeat(v, per_group, axis=-2)[..., None, :]
                for v in (B, C))
    else:
        B, C = B[..., None, None, :], C[..., None, None, :]
    new = (jnp.exp(g)[..., None, None] * state
           + (x * dt[..., None])[..., None] * B)
    return jnp.sum(new * C, axis=-1), new


class PackedHeadPages:
    """The page side of an attention layer whose pages hold ``pack`` K/V
    heads side by side a stored row of whole lanes (two 64-wide heads a
    128-lane row), over ``StateEntryCache``'s pools of the attention
    layers alone.  The block's fields ``pack``, ``kv_heads`` and
    ``full_pages`` say the layout; ``models/lfm2_moe.py`` stands on it
    too."""

    def _packed(self, rows, pool):
        """K or V rows (..., kv_heads, dh) as a page stores them:
        ``pack`` heads side by side a row of whole lanes (a reshape)."""
        return rows.reshape(rows.shape[:-2] + pool.shape[3:])

    def store_prompt(self, pool, rows, flat):
        return super().store_prompt(pool, self._packed(rows, pool), flat)

    def cached_attention(self, k_pool, v_pool, li, q, k, v, flat, tables,
                         lens):
        """A decode step's: the pools hold the attention layers alone,
        ``pack`` K/V heads a stored row, and the table's first columns
        are the page run.  The grouped kernel reads a stored row as one
        head of ``pack * dh`` lanes; each query head carries its
        numbers in the lanes of the K/V head it reads and zeros in the
        others', and takes that part of the output lanes."""
        S, Hq, dh = q.shape
        Hs, pack = k_pool.shape[3], self.pack
        G = Hq // self.kv_heads
        slab = self.index_in_kind       # of the attention layers' pools
        with jax.named_scope("attn_full"):
            k_pool = _write_rows(k_pool, slab, flat, self._packed(k, k_pool))
            v_pool = _write_rows(v_pool, slab, flat, self._packed(v, v_pool))
            pages = _layer_pages(k_pool, v_pool, slab,
                                 tables[:, :self.full_pages])
            # (S, stored heads, which of the row's heads, G, dh): in the
            # lanes of its own K/V head, zeros in the others'
            mine = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
            wide = (q.reshape(S, Hs, pack, G, 1, dh) * mine).reshape(
                S, Hq, pack * dh)
            a = paged_attention(wide, *pages, lens + 1,
                                scale=dh ** -0.5)
            a = a.reshape(S, Hs, pack, G, pack, dh)
            a = jnp.stack([a[:, :, p, :, p] for p in range(pack)], axis=2)
        return a.reshape(S, Hq, dh), k_pool, v_pool


@dataclasses.dataclass(frozen=True)
class GraniteHybridBlock(PackedHeadPages, StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side.  The
    state-space layers' sizes go by the published names:
    ``mamba_n_heads``, ``mamba_d_head`` (a head's channels) and
    ``mamba_d_state``; a layer's state is (mamba_n_heads, mamba_d_head,
    mamba_d_state), stored as ``pack_state`` lays it out.  ``pack``: the
    K/V heads a page's row holds side by side; ``state_pack``: the mamba
    heads a row of an entry does; ``mamba_n_groups``: the groups of
    heads that each read a ``B`` and a ``C`` of their own and are normed
    on their own (1: Granite's; 8: Nemotron-H's)."""

    recurrent_kind = MAMBA
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    kv_heads: int = 8
    head_dim: int = 64
    pack: int = 2
    state_pack: int = 2
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1      # groups of heads, a B and a C each
    eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    full_pages: int = 15         # table columns of the page run
    at: int = 0

    @property
    def _sizes(self):
        """(H, P, N): a mamba layer's state as published."""
        return self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32) * self.embedding_multiplier

    def qkv(self, lp, x, pos, heads):
        """An attention layer's: pre-norm, no q/k norm, no rotation;
        ``attention_multiplier * head_dim^1/2`` folded into q, so that
        a kernel that scales by ``head_dim^-1/2`` scales by the
        multiplier."""
        n = rms_norm(x, lp["w_in"], self.eps)
        lead, dh = x.shape[:-1], self.head_dim
        q = _mm(n, lp["wq"]).reshape(lead + (heads, dh)) * (
            self.attention_multiplier * dh ** 0.5)
        k = _mm(n, lp["wk"]).reshape(lead + (self.kv_heads, dh))
        v = _mm(n, lp["wv"]).reshape(lead + (self.kv_heads, dh))
        dtype = lp["wq"].dtype
        return q.astype(dtype), k.astype(dtype), v.astype(dtype)

    def attn_out(self, lp, x, a):
        return x + self.residual_multiplier * _mm(a, lp["wo"])

    def mlp(self, lp, x, live):
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["w_gate"].dtype)
        y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + self.residual_multiplier * y, None

    def head(self, params, x):
        """The tied head: the embedding contracted over its columns
        where it lies (no transposed copy of it)."""
        emb = params["emb"]
        n = rms_norm(x, params["w_f"], self.eps).astype(emb.dtype)
        return jax.lax.dot_general(
            n, emb, (((n.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_F32) / self.logits_scaling

    # -- the cache side of an attention layer: ``PackedHeadPages`` ----------

    # -- a mamba layer's pieces ---------------------------------------------

    def _projections(self, lp, x):
        """-> (the gate's rows z; the rows the conv sees, in the
        weights' dtype; dt and the log decay ``g``, (..., H))."""
        H, P, N = self._sizes
        BC = 2 * self.mamba_n_groups * N
        u = rms_norm(x, lp["w_in"], self.eps)
        with jax.named_scope("ssm_proj"):
            zxbcdt = _mm(u, lp["w_zxbcdt"])
        z = zxbcdt[..., :H * P]
        xBC = zxbcdt[..., H * P:2 * H * P + BC].astype(
            lp["w_zxbcdt"].dtype)
        dt = jax.nn.softplus(zxbcdt[..., 2 * H * P + BC:]
                             + lp["dt_bias"])
        return z, xBC, dt, -jnp.exp(lp["A_log"]) * dt

    def _split(self, xc):
        """The conv's output rows -> x (..., H, P), B, C (..., N), or
        (..., G, N) where the heads read them in G groups."""
        (H, P, N), G = self._sizes, self.mamba_n_groups
        x = xc[..., :H * P].reshape(xc.shape[:-1] + (H, P))
        B, C = xc[..., H * P:H * P + G * N], xc[..., H * P + G * N:]
        if G > 1:
            B, C = (v.reshape(v.shape[:-1] + (G, N)) for v in (B, C))
        return x, B, C

    def _gated_norm(self, lp, y, xs, z):
        """The skip, the gate, then the norm over all the layer's
        channels, or over each group's on their own."""
        y = (y + lp["D"][:, None] * xs).reshape(z.shape)
        y, w, G = y * jax.nn.silu(z), lp["w_norm"], self.mamba_n_groups
        if G > 1:
            by_group = y.shape[:-1] + (G, y.shape[-1] // G)
            return rms_norm(y.reshape(by_group), w.reshape(by_group[-2:]),
                            self.eps).reshape(y.shape)
        return rms_norm(y, w, self.eps)

    def _out(self, lp, x, y):
        """The output projection and the block's residual."""
        with jax.named_scope("ssm_proj"):
            return x + self.residual_multiplier * _mm(y, lp["w_out"])

    # -- the mixers ---------------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        if not self.recurrent:
            return super().prompt_mixer(lp, x, pos, heads, live)
        T = x.shape[0]
        z, xBC, dt, g = self._projections(lp, x)
        n = T if live is None else jnp.sum(live.astype(jnp.int32))
        if live is not None:
            # a recurrence sees padding that causal attention hides:
            # rows from n on neither decay the state nor write to it
            dt = jnp.where(live[:, None], dt, 0.0)
            g = jnp.where(live[:, None], g, 0.0)
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_conv"):
                xc = jax.nn.silu(causal_conv(xBC, lp["w_conv"])
                                 + lp["b_conv"].astype(_F32))
                tail = conv_tail(xBC, lp["w_conv"].shape[0], n)
            xs, B, C = self._split(xc)
            with jax.named_scope("ssm_scan"):
                y, state = chunked_ssd(
                    xs, dt, g, B, C, jnp.zeros(self._sizes, _F32))
            y = self._gated_norm(lp, y, xs, z)
        return self._out(lp, x, y), (pack_state(state, self.state_pack),
                                     tail)

    def recurrent_step(self, lp, x, cache, addr):
        k_pool, v_pool, state_pool, conv_pool = cache
        S = x.shape[0]
        z, xBC, dt, g = self._projections(lp, x)
        at = self.entries_of(state_pool, addr)
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_conv"):
                xc, tails = conv_over_entries(
                    conv_pool.reshape((-1,) + conv_pool.shape[2:]), at,
                    xBC, lp["w_conv"], lp["b_conv"])
            xs, B, C = self._split(xc)
            with jax.named_scope("ssm_state"):
                states = state_pool.reshape((-1,) + state_pool.shape[2:])
                if pk.use_ssd_step(state_pool.dtype, *state_pool.shape[2:],
                                   self.mamba_n_groups):
                    # along the lanes as an entry's rows of heads lie:
                    # each head's decay over its own channels
                    lanes = (S,) + state_pool.shape[2:3] + (-1,)
                    y, states = ssd_step(
                        states, at,
                        jnp.repeat(jnp.exp(g), self.mamba_d_head,
                                   -1).reshape(lanes),
                        (xs * dt[..., None]).reshape(lanes), B, C,
                        interpret=pk.interpret_mode())
                    y = y.reshape(xs.shape)
                else:
                    y, new = step_ssd(
                        xs, dt, g, B, C,
                        unpack_state(states[at], self.state_pack))
                    states = states.at[at].set(
                        pack_state(new, self.state_pack))
            y = self._gated_norm(lp, y, xs, z)
        return self._out(lp, x, y), (
            k_pool, v_pool, states.reshape(state_pool.shape),
            tails.reshape(conv_pool.shape))


# The standard deviation of a q or k row's numbers, whatever the width:
# an attention layer's q and k projections are drawn N(0, QK_ROW_STD *
# d^-1/2) where every other matrix is N(0, 0.02).  At 0.02 a score
# ``q.k / 64`` is ~0.1 and the softmax uniform whatever scales or
# rotates it, and its output the mean of a thousand random v rows:
# nothing in the logits.  At 4.5 (0.1 at d 2,048) scores lie ~2.5
# apart, a row attends to a few of the rows before it, as a trained
# model's sharper heads do, and a wrong scale, a rotation or a mis-read
# half of a page's row moves the logits.
QK_ROW_STD = 4.5


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


def attention_params(keys, *, d, heads, kv_heads, head_dim, qk_row_std,
                     dtype):
    """An attention layer's four projections from four keys: q and k
    at ``qk_row_std * d^-1/2``, v and o at 0.02."""
    qk = qk_row_std * d ** -0.5
    return dict(
        wq=_normal(keys[0], shape=(d, heads * head_dim), std=qk, dtype=dtype),
        wk=_normal(keys[1], shape=(d, kv_heads * head_dim), std=qk,
                   dtype=dtype),
        wv=_normal(keys[2], shape=(d, kv_heads * head_dim), std=0.02,
                   dtype=dtype),
        wo=_normal(keys[3], shape=(heads * head_dim, d), std=0.02,
                   dtype=dtype))


def mamba_params(keys, *, d, heads, head_dim, d_state, groups, conv, dtype):
    """A mamba layer's parameters from five keys (the in-projection, the
    conv's taps and bias, the out-projection, the step, the rate), as
    ``init_params`` says they are drawn."""
    inner = heads * head_dim
    channels = inner + 2 * groups * d_state
    bound = conv ** -0.5
    step = jnp.exp(jax.random.uniform(
        keys[3], (heads,), _F32, np.log(0.001), np.log(0.1)))
    taps = jax.random.uniform(keys[1], (conv + 1, channels), _F32,
                              -bound, bound).astype(dtype)
    return dict(
        w_zxbcdt=_normal(keys[0], shape=(d, inner + channels + heads),
                         std=0.02, dtype=dtype),
        w_conv=taps[:conv], b_conv=taps[conv],
        w_out=_normal(keys[2], shape=(inner, d), std=0.02, dtype=dtype),
        w_norm=jnp.ones((inner,), dtype),
        dt_bias=jnp.log(jnp.expm1(step)),
        A_log=jnp.log(jax.random.uniform(keys[4], (heads,), _F32, 1.0,
                                         16.0)),
        D=jnp.ones((heads,), _F32))


@functools.partial(jax.jit, static_argnames=(
    "kind", "d", "heads", "kv_heads", "head_dim", "width", "mamba_n_heads",
    "mamba_d_head", "mamba_d_state", "mamba_n_groups", "conv", "dtype"))
def _init_layer(key, *, kind, d, heads, kv_heads, head_dim, width,
                mamba_n_heads, mamba_d_head, mamba_d_state, conv, dtype,
                mamba_n_groups=1):
    """One layer's parameters: one program a kind of layer."""
    def normal(k, *shape, std=0.02):
        return _normal(k, shape=shape, std=std, dtype=dtype)

    ones = jnp.ones((d,), dtype)
    lk = jax.random.split(key, 9)
    lp = {"w_in": ones, "w_post": ones,
          "w_gate": normal(lk[0], d, width),
          "w_up": normal(lk[1], d, width),
          "w_down": normal(lk[2], width, d)}
    if kind == ATTENTION:
        lp.update(attention_params(
            lk[3:7], d=d, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            qk_row_std=QK_ROW_STD, dtype=dtype))
    else:
        lp.update(mamba_params(
            (lk[3], lk[4], lk[5], lk[7], lk[8]), d=d, heads=mamba_n_heads,
            head_dim=mamba_d_head, d_state=mamba_d_state,
            groups=mamba_n_groups, conv=conv, dtype=dtype))
    return lp


def init_params(key, *, vocab, d, heads, kv_heads, head_dim, layer_types,
                width, mamba_n_heads, mamba_d_head, mamba_d_state, conv,
                dtype, mamba_n_groups=1):
    """Every matrix N(0, 0.02) in ``dtype`` but an attention layer's q
    and k projections (``QK_ROW_STD``), every norm scale 1.  The
    recurrence's parameters as Mamba-2 initialises them, float32:
    ``dt_bias`` the inverse softplus of a step drawn log-uniform in
    [0.001, 0.1], ``A_log`` the log of a rate uniform in [1, 16], ``D``
    1; the conv's taps and bias uniform in +-conv^-1/2 (the published
    module's default for its depthwise conv), in ``dtype``: at N(0,
    0.02) x, B and C would be ~0.02 and the state's part of ``y`` a
    ten-thousandth of the skip's.  Made on the device, a layer at a
    time by one program a kind of layer (forty layers in one program
    took as long to compile as a bucket's prefill)."""
    ks = jax.random.split(key, 1 + len(layer_types))
    sizes = dict(d=d, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                 width=width, mamba_n_heads=mamba_n_heads,
                 mamba_d_head=mamba_d_head, mamba_d_state=mamba_d_state,
                 mamba_n_groups=mamba_n_groups, conv=conv, dtype=dtype)
    return {"emb": _normal(ks[0], shape=(vocab, d), std=0.02, dtype=dtype),
            "w_f": jnp.ones((d,), dtype),
            "layers": [_init_layer(k, kind=kind, **sizes)
                       for k, kind in zip(ks[1:], layer_types)]}


class GraniteHybridLM(StateEntryLM):
    """Granite-4.0-H over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/granite-4.0-h-micro.gen_config.py``).  The
    reservation, the table row and the refusals are
    ``decode/state_entry.py``'s."""

    def __init__(self, vocab: int = 100352, d_model: int = 2048,
                 num_heads: int = 32, num_kv_heads: int = 8,
                 head_dim: int = 64,
                 layer_types: Sequence[str] = GraniteHybridBlock.layer_types,
                 intermediate_size: int = 8192, mamba_n_heads: int = 64,
                 mamba_d_head: int = 64, mamba_d_state: int = 128,
                 mamba_d_conv: int = 4, mamba_n_groups: int = 1,
                 rms_norm_eps: float = 1e-5,
                 embedding_multiplier: float = 12.0,
                 residual_multiplier: float = 0.22,
                 attention_multiplier: float = 0.015625,
                 logits_scaling: float = 8.0, max_len: int = 1920,
                 num_pages: int = 64, page_size: int = 128,
                 pages_per_seq: int = 15, state_entries: int = 9,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        layer_types = tuple(layer_types)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if int(mamba_n_heads) % int(mamba_n_groups):
            raise ValueError("the groups have to divide the mamba heads")
        if num_heads % num_kv_heads:
            raise ValueError("the K/V heads have to divide the query heads")
        self.dh, self.kv_heads = int(head_dim), int(num_kv_heads)
        self._count_layers(layer_types, MAMBA)
        pack = heads_a_row(self.kv_heads, self.dh)
        state_pack = heads_a_row(int(mamba_n_heads), int(mamba_d_head))
        self.block = GraniteHybridBlock(
            layer_types=layer_types, kv_heads=self.kv_heads,
            head_dim=self.dh, pack=pack, state_pack=state_pack,
            mamba_n_heads=int(mamba_n_heads),
            mamba_d_head=int(mamba_d_head),
            mamba_d_state=int(mamba_d_state),
            mamba_n_groups=int(mamba_n_groups),
            eps=float(rms_norm_eps),
            embedding_multiplier=float(embedding_multiplier),
            residual_multiplier=float(residual_multiplier),
            attention_multiplier=float(attention_multiplier),
            logits_scaling=float(logits_scaling),
            full_pages=self.full_pages)
        (H, P, N), dtype = self.block._sizes, jnp.dtype(dtype)
        self.conv_taps = int(mamba_d_conv)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, kv_heads=self.kv_heads, head_dim=self.dh,
            layer_types=layer_types, width=int(intermediate_size),
            mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N,
            mamba_n_groups=self.block.mamba_n_groups, conv=self.conv_taps,
            dtype=dtype)
        # a page's row as the gauges count it: the published K/V heads
        # (stored ``pack`` a row of whole lanes, nothing padded)
        self.stored_heads = self.kv_heads
        self._make_pools(
            num_pages, dtype, int(state_entries),
            (self.kv_heads // pack, pack * self.dh),
            (H // state_pack, N, state_pack * P),
            tail_shape(self.conv_taps,
                       H * P + 2 * self.block.mamba_n_groups * N))
