"""Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning,
``model_type`` phi4flash: "SambaY with differential attention",
arXiv:2507.06607, a decoder-hybrid-decoder) behind ``/generate``, whole
on one chip.

The first model here in which a layer's cache is not its own.  With
``L`` layers and ``half = L / 2`` (32 and 16 as published):

    index             mixer                  owns          reads
    even, <= half     Mamba-1 (S6)           state + tail  its own
    odd, < half       differential, window   a ring        its own
    half + 1          differential, full     THE page run  its own
    even, > half      gated memory unit      nothing       layer half's y
    odd, > half + 1   differential cross     nothing       layer half + 1's
                                                           pages

(a GMU reads ``y`` of the same row; a window is 512 rows as published.)

The block (pre-norm, LayerNorm with scale and bias, eps 1e-5; no
positional encoding of any kind), ``x0 = E[token]``:

    h = x + mixer_l(LN(x));  x = h + W_down(silu(W_gate LN'(h)) * W_up LN'(h))
    logits = E^T LN_f(x_L)                                 (the head is tied)

**Mamba-1**, ``u = LN(x)``, C = 5,120 channels, state N = 16, rank R =
160:

    [xc; z] = W_in u;   xc_t <- silu(conv4(xc)_t + b_conv)   (depthwise,
        causal, zeros before row 0)
    [dt'; B_t; C_t] = W_x xc_t  (R, N, N);   dt_t = softplus(W_dt dt'_t + b_dt)
    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * xc_t) B_t^T,   A = -exp(A_log)
    y_t = S_t C_t + D * xc_t;    out = W_out (y_t * silu(z_t))

with ``S`` and ``A`` (C, N) float32, ``S`` zero before row 0.  An entry
stores a state with the state index down the rows and the channels
along the lanes, ``(N, C)`` (``pallas/s6_step.py`` says why), and every
function here takes it so.  Layer ``half`` alone also hands ``m_t =
y_t``, before the gate, to the GMU layers.  The recurrence two ways:
over a prompt **row by row** (``scan_s6``: a loop that carries the
state, its body ``UNROLL`` rows one after another; never a ``rows x C x
N`` tensor), and over a decode step's rows one token on each slot's entry in
place, by ONE ``s6_step`` call a layer where ``pallas.use_s6_step`` says
so, else gathered, advanced by ``step_s6`` and scattered in XLA, the
kernel's reference.  The conv is ``decode/state_entry.py``'s, as
Granite's.

**GMU**: ``out = W_out (silu(W_in u) * m_t)``: no state, no cache; row
``t`` reads row ``t``'s ``m``, an activation handed on inside the
program (behind the cache's buffers at a step, among what the layers
keep of a prompt at a prefill).

**Differential attention**: ``[q; k; v] = W_qkv u + b`` (40 / 20 / 20
heads of 64; a cross layer has ``q = W_q u + b`` alone).  Heads pair up:
query pair ``p`` is heads ``2p, 2p + 1``, K/V pair ``r`` heads ``2r, 2r
+ 1``, and **query pair p reads K/V pair p // 2**.  With causal softmax
at ``64^-1/2``, masked to the newest 512 rows in a window layer:

    a1 = softmax(q1 k1^T) [v1 | v2];  a2 = softmax(q2 k2^T) [v1 | v2]
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
    lam0(l) = 0.8 - 0.6 exp(-0.3 l)
    o_p = (1 - lam0(l)) RMSNorm_128(a1 - lam a2) * w_sub
    out = W_o concat_p(o_p) + b_o

**On the pages** this is Granite's packed layout with both halves of
the output kept: K/V pair ``r`` is ONE stored row of 128 lanes, ``[k_2r
| k_2r+1]``, ``[v_2r | v_2r+1]``; query head ``2p + i`` carries its 64
numbers in half ``i`` of 128 lanes and zeros in the other, so against
stored row ``p // 2`` it scores exactly ``q_i . k_i``, and its 128
output lanes are ``a_i`` whole.  The grouped paged kernel runs on it as
it is: 40 query heads on 10 stored heads of 128 (query head ``h`` on
stored head ``h // 4``).  The kernels scale by ``128^-1/2``, so q
carries ``2^1/2`` (folded in float32, before the one cast).

**The cache**: three resources a sequence from one manager
(``decode/paged_kv.py:CacheManager``).  The pools are ``(1, N, 10, pg,
128)``, every page one layer's and **a page's heads outside its rows**:
ten stored heads are no whole tile of a bfloat16 pool's (rows, heads)
(4, 8 or a multiple of 16 are), the chip's compiler then lays a
row-major pool out with the heads outermost anyway and copies all of it
to the kernel's layout and back around every layer's call
(``decode/attention.py:fits``; the probe in ``tests/test_chip_compile.py``
read 5.6 GB of temporaries), and rounding ten up to sixteen would store
and read 1.6 times the bytes.  Stored ``(heads, rows, 128)`` a page is
whole tiles whatever the head count, and is what the grouped kernel's
two batched dots take as it lies (``heads_major``).  The pages are layer
``half + 1``'s page run, the model's only one, which the cross layers
read through the same table columns and own no column of;
``ring_pages`` pages a window layer, a ring of the newest rows as
``models/exaone_moe.py`` lays one out; and beside them one state entry
(the Mamba layers' states and conv tails).  A table row is the run's
columns, each ring's, then the entry.

**The prefill stops half-way down.**  Nothing layers ``half + 2`` and
up compute on a row before the last reaches a cache or the first token,
and layer ``half + 1`` needs to attend for the last row only (its K/V
are stored for every row): told which row that is (``last``), the block
goes on from layer ``half + 1`` with that row alone.

**What a prompt leaves in the pools is written page by page.**  A
bucket is whole pages, a ring keeps whole pages (prompt page ``p`` in
column ``p % ring_pages``) and the run is whole pages, so no row of a
prompt needs an address of its own.  A window layer keeps, of the
bucket-long K/V it attended over, the pages its ring holds alone (the
prompt's last ``ring_pages``, cut at the first of them: ``_ring_of``);
the rows before them, which no later row sees, are dropped where they
were made.  ``store_prompts`` turns what the nine layers kept to the
pool's page form and writes it by ONE scatter a pool over the page
axis, a page (``heads x rows x 128`` contiguous numbers) an update;
the host names a pool page a kept page (``Phi4FlashLM._prompt_rows``),
the null page for a bucket's pages past the sequence's own and a
ring's past a short prompt's.  The decode step writes one row a slot a
layer (``write_rows``: a (row, head) a scatter update, 640 a layer).

What a state or a ring cannot do is refused by name: a prefill over
cached pages, a fork, the speculative verify
(``decode/state_entry.py:UnsupportedOverState``).

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, gates, decay and state.  Random
weights only: loading a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode.attention import (
    banded_prefill_attention,
    dense_prefill_attention,
    paged_attention,
    paged_ring_attention,
)
from paddle_tpu.decode.state_entry import (
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    causal_conv,
    conv_over_entries,
    conv_tail,
    tail_shape,
)
from paddle_tpu.decode.paged_kv import CacheManager
from paddle_tpu.models.exaone_moe import swiglu
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.pallas.s6_step import s6_step

_M_PREFILL_ROWS = _metrics.counter(
    "decode_prefill_rows_total",
    "rows a decoder-hybrid-decoder's bucketed prefills computed, by "
    "part: `self` = the layers that fill a cache (every bucket row), "
    "`cross` = the layers that read another layer's, the rows the "
    "bucket's program handed them (one when the prefill stops half-way "
    "down, every bucket row when it does not)")
_M_STORED_PAGES = _metrics.counter(
    "decode_prefill_stored_pages_total",
    "pages (a page's K and its V) a decoder-hybrid-decoder's bucketed "
    "prefills wrote to the pools, by where they went: `run` = a "
    "sequence's page run, `ring` = its window layers' rings, `null` = "
    "the null page (a bucket's pages past the sequence's own, a ring's "
    "past a short prompt's)")
_M_SHARED_READS = _metrics.counter(
    "decode_shared_run_reads_total",
    "reads of the one page run by a decode step's layers: its owner's "
    "and every cross layer's, a step")

_F32 = jnp.float32
MAMBA, WINDOW, FULL = "mamba", "window_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"
# Rows of a prompt the recurrence's loop body takes one after another.
UNROLL = 16
# Rows the layers below the page run's owner were handed in each
# bucket's prefill program, noted as the program was traced (a shape).
_CROSS_ROWS: dict = {}


def layer_kinds(num_layers: int, mb_per_layer: int = 2) -> tuple:
    """Which layer is which, from the published rule: a Mamba layer
    where ``idx % mb_per_layer == 0``, attention elsewhere; the first
    ``L / 2 + 2`` layers are the self-decoder (window attention on its
    odd layers but the last, which is full and owns the K/V), the rest
    the cross-decoder (its Mamba places hold GMUs, its attention reads
    that K/V)."""
    half = num_layers // 2
    if mb_per_layer != 2 or num_layers % 4 or num_layers < 8:
        raise ValueError("mb_per_layer 2 and whole periods of four layers, "
                         "eight layers at least: layer L/2 has to be a "
                         "Mamba layer with window layers before it and a "
                         "GMU after the full layer")
    kinds = []
    for idx in range(num_layers):
        if idx % mb_per_layer == 0:
            kinds.append(MAMBA if idx <= half else GMU)
        else:
            kinds.append(WINDOW if idx < half else
                         FULL if idx == half + 1 else CROSS)
    return tuple(kinds)


def lam0(idx: int) -> float:
    """Differential attention's ``lambda_init`` of layer ``idx``."""
    return 0.8 - 0.6 * math.exp(-0.3 * idx)


def layer_norm(x, scale, bias, eps):
    x = x.astype(_F32)
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + eps) * scale.astype(_F32)
            + bias.astype(_F32))


def step_s6(x, dt, A, B, C, state):
    """One row on a state (any leading shape: a slot's, or the slots'):
    ``x``, ``dt`` (..., C); ``A`` (N, C); ``B``, ``C`` (..., N);
    ``state`` (..., N, C) -> (y (..., C) = ``S_t C_t`` without the skip,
    the new state).  Multiply-reduces, float32.  ``pallas/s6_step.py``
    is this, in this order, on blocks of the pool in VMEM."""
    new = (jnp.exp(dt[..., None, :] * A) * state
           + B[..., :, None] * (x * dt)[..., None, :])
    return jnp.sum(new * C[..., :, None], axis=-2), new


def scan_s6(x, dt, A, B, C, state, unroll=UNROLL):
    """The S6 recurrence over T rows, one row after another.  ``x``,
    ``dt`` (T, C), ``A`` (N, C), ``B``, ``C`` (T, N), ``state`` (N, C)
    as it stood before row 0 -> (y (T, C) = ``S_t C_t`` without the
    skip, the state after row T - 1).  All float32.

    The decay differs for every (state index, channel) pair and every
    row, so a stretch of rows has no matmul form (``chunked_ssd``'s mask
    is one scalar a head a row): a ``lax.scan`` over the rows carries
    the state, each row ``step_s6``, its loop's body ``unroll`` rows
    long.  What is live at once is a state and a body's rows, never ``T
    x C x N``.  A row with ``dt = 0`` (a bucket's padding) leaves the
    state as it was; the result does not depend on ``unroll``, whether
    or not it divides T."""
    def row(S, r):
        y, S = step_s6(*r[:2], A, *r[2:], S)
        return S, y

    state, y = jax.lax.scan(row, state.astype(_F32), (x, dt, B, C),
                            unroll=max(1, min(unroll, x.shape[0])))
    return y, state


def write_rows(pool, flat, rows):
    """``pool`` (1, N, H, pg, D) with ``rows`` (R, H, D) written at the
    flat rows ``flat`` (R,) = page * pg + offset of a pool seen page by
    page: head ``h`` of row ``i`` lands in row ``flat[i] % pg`` of head
    ``h`` of page ``flat[i] // pg``.  One scatter into the pool's own
    (donated) buffer, seen (N * H * pg, D): an update a (row, head),
    for a decode step's row a slot (a prompt goes in by
    ``write_pages``)."""
    _, N, H, pg, D = pool.shape
    at = ((flat // pg)[:, None] * H + jnp.arange(H, dtype=flat.dtype)
          ) * pg + (flat % pg)[:, None]
    return (pool.reshape(N * H * pg, D).at[at.reshape(-1)]
            .set(rows.reshape(-1, D).astype(pool.dtype))
            .reshape(pool.shape))


def prompt_len(T, live, last):
    """How many of a bucket's ``T`` rows are the prompt's: told its last
    row (a prefill's ``n - 1``) or which rows are ``live``; all of them
    told neither."""
    if last is not None:
        return last + 1
    return T if live is None else jnp.sum(live.astype(jnp.int32))


def whole_pages(rows, pg):
    """``rows`` (T, H, D) with zero rows after them up to a whole page:
    every bucket of the ladder is whole pages; a top bucket that
    ``max_len`` cut inside a page ends with rows that ``lens`` hides and
    decode overwrites, as a bucket's own padding does."""
    pad = -rows.shape[0] % pg
    return rows if not pad else jnp.pad(rows, ((0, pad), (0, 0), (0, 0)))


def write_pages(pool, ids, rows):
    """``pool`` (1, N, H, pg, D) with ``rows`` (P * pg, H, D) written as
    P whole pages at the pages ``ids`` (P,): turned to the pool's page
    form ``(P, H, pg, D)`` and scattered over the pool's page axis, ONE
    update of ``H x pg x D`` contiguous numbers a page, into the pool's
    own (donated) buffer.  A page named twice (the null page) keeps
    either."""
    _, N, H, pg, D = pool.shape
    pages = jnp.swapaxes(rows.reshape(-1, pg, H, D), 1, 2)
    return (pool.reshape(N, H, pg, D).at[ids]
            .set(pages.astype(pool.dtype)).reshape(pool.shape))


def last_row_attention(q, k, v, pos):
    """One query row of a prompt against all its K/V rows: q (1, Hq, D)
    at position ``pos`` (1,), k, v (T, Hkv, D), query head ``h`` on K/V
    head ``h // (Hq / Hkv)`` -> (1, Hq, D): what a prefill that stops
    half-way down needs of the layer whose K/V it stores."""
    (_, Hq, D), (T, Hkv, _) = q.shape, k.shape
    s = jnp.einsum("qhgd,khd->hgqk", q.reshape(1, Hkv, Hq // Hkv, D), k,
                   preferred_element_type=_F32) * D ** -0.5
    seen = jnp.arange(T, dtype=pos.dtype)[None, :] <= pos[:, None]
    pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    out = jnp.einsum("hgqk,khd->qhgd", pr.astype(v.dtype), v,
                     preferred_element_type=_F32)
    return out.reshape(1, Hq, D).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class Phi4FlashBlock(StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side.
    ``kv_heads`` and ``head_dim`` as published (20 of 64); a page's row
    holds them two side by side (10 of 128).  ``full_pages``: the table
    columns of the page run; ``ring_pages``: of one window layer's
    ring; the entry's column comes after the rings'.  ``page_size``:
    a page's rows, which a window layer cuts a prompt's ring by."""

    recurrent_kind = MAMBA
    layer_types: tuple = layer_kinds(32)
    kv_heads: int = 20
    head_dim: int = 64
    window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    dt_rank: int = 160
    eps: float = 1e-5
    full_pages: int = 96
    ring_pages: int = 5
    page_size: int = 128
    at: int = 0

    @property
    def kind(self) -> str:
        return self.layer_types[self.at]

    @property
    def owner(self) -> int:
        """The layer whose page run the cross layers read."""
        return self.layer_types.index(FULL)

    @property
    def hands_memory(self) -> bool:
        """The Mamba layer whose ``y`` the GMU layers read: the last."""
        return self.at == self.owner - 1

    @property
    def ring_at(self) -> int:
        """First table column of this window layer's ring."""
        return self.full_pages + self.index_in_kind * self.ring_pages

    @property
    def entry_at(self) -> int:
        """The table column of the state entry: after the rings."""
        rings = sum(t == WINDOW for t in self.layer_types)
        return self.full_pages + rings * self.ring_pages

    def entries_of(self, state_pool, addr):
        E = state_pool.shape[1]
        return self.index_in_kind * E + addr.tables[:, self.entry_at]

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def _normed(self, lp, x, which):
        return layer_norm(x, lp["w_" + which], lp["b_" + which], self.eps)

    def mlp(self, lp, x, live):
        m = self._normed(lp, x, "post").astype(lp["w_gate"].dtype)
        return x + swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None

    def head(self, params, x):
        """The tied head: the embedding contracted over its columns
        where it lies (no transposed copy of it)."""
        emb = params["emb"]
        n = layer_norm(x, params["w_f"], params["b_f"],
                       self.eps).astype(emb.dtype)
        return jax.lax.dot_general(
            n, emb, (((n.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_F32)

    # -- differential attention's pieces ------------------------------------

    def qkv(self, lp, x, pos, heads):
        """-> q (..., heads, 2 dh) widened: head ``2p + i`` in half
        ``i`` of its lanes, zeros in the other, times ``2^1/2``; k, v
        (..., kv_heads / 2, 2 dh) as a page stores them, K/V heads ``2r``
        and ``2r + 1`` side by side (None for a cross layer, which has
        no K/V of its own)."""
        u = self._normed(lp, x, "in")
        lead, dh = x.shape[:-1], self.head_dim
        if self.kind == CROSS:
            q, k, v = _mm(u, lp["wq"]) + lp["b_q"].astype(_F32), None, None
        else:
            qkv = _mm(u, lp["wqkv"]) + lp["b_qkv"].astype(_F32)
            stored = lead + (self.kv_heads // 2, 2 * dh)
            q, k, v = jnp.split(
                qkv, [heads * dh, (heads + self.kv_heads) * dh], axis=-1)
            k, v = k.reshape(stored), v.reshape(stored)
        dtype = lp["wo"].dtype
        q = q.reshape(lead + (heads // 2, 2, 1, dh)) * 2.0 ** 0.5
        wide = (q.astype(dtype) * jnp.eye(2, dtype=dtype)[:, :, None]
                ).reshape(lead + (heads, 2 * dh))
        return wide, (None if k is None else k.astype(dtype)), (
            None if v is None else v.astype(dtype))

    def attn_out(self, lp, x, a):
        """``a`` (..., heads, 2 dh): each widened head's 128 output
        lanes, ``a_i`` whole.  The pairs' difference, the sub-norm, the
        output projection with its bias and the block's residual."""
        lead, (heads, wide) = a.shape[:-2], a.shape[-2:]
        a = a.astype(_F32).reshape(lead + (heads // 2, 2, wide))
        init = lam0(self.at)
        lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
               - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + init)
        o = rms_norm(a[..., 0, :] - lam * a[..., 1, :], lp["w_sub"],
                     self.eps) * (1.0 - init)
        return (x + _mm(o.reshape(lead + (-1,)), lp["wo"])
                + lp["b_o"].astype(_F32))

    def _window_prompt(self, q, k, v):
        """A window layer over a whole prompt: the banded form a stored
        head at a time (the scores of all ten at once are 2 GB at the
        top bucket)."""
        T, H, D = q.shape
        Hs = k.shape[1]

        def one(qkv):
            qh, kh, vh = qkv
            return banded_prefill_attention(qh, kh[:, None], vh[:, None],
                                            self.window)

        a = jax.lax.map(one, (
            jnp.moveaxis(q.reshape(T, Hs, H // Hs, D), 1, 0),
            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
        return jnp.moveaxis(a, 0, 1).reshape(T, H, D)

    def _ring_of(self, k, v, n):
        """What a window layer keeps of an ``n``-row prompt: the pages
        its ring holds, the prompt's last ``ring_pages`` (those of the
        bucket, where it has fewer), cut at the first of them; the
        bucket-long K/V die here.  Page ``j`` of the cut is the prompt's
        page ``first + j``, ``first = max((n - 1) // pg - (ring_pages -
        1), 0)``, as ``Phi4FlashLM._prompt_rows`` reckons it on the
        host: the cut's shape depends on the bucket alone."""
        pg, R = self.page_size, self.ring_pages
        keep = min(R, -(-k.shape[0] // pg)) * pg
        first = jnp.maximum((n - 1) // pg - (R - 1), 0) * pg
        return tuple(jax.lax.dynamic_slice_in_dim(whole_pages(rows, pg),
                                                  first, keep)
                     for rows in (k, v))

    # -- a Mamba layer's pieces ---------------------------------------------

    def _in_proj(self, lp, x):
        """-> (the rows the conv sees, in the weights' dtype; the gate's
        rows z, float32)."""
        xz = _mm(self._normed(lp, x, "in"), lp["w_inproj"])
        return (xz[..., :self.d_inner].astype(lp["w_inproj"].dtype),
                xz[..., self.d_inner:])

    def _selective(self, lp, xc):
        """The conv's output rows -> dt (..., C), B, C (..., N), and
        ``A`` (N, C): the input-dependent parameters of the scan."""
        R, N = self.dt_rank, self.d_state
        dbc = _mm(xc, lp["w_x"])
        dt = jax.nn.softplus(_mm(dbc[..., :R], lp["w_dt"]) + lp["b_dt"])
        return (dt, dbc[..., R:R + N], dbc[..., R + N:],
                -jnp.exp(lp["A_log"]).T)

    def _gate_out(self, lp, x, y, z):
        return x + _mm(y * jax.nn.silu(z), lp["w_out"])

    def _gmu(self, lp, x, m):
        g = jax.nn.silu(_mm(self._normed(lp, x, "in"), lp["w_inproj"]))
        with jax.named_scope("gmu"):
            h = g * m
        return x + _mm(h, lp["w_out"])

    # -- over a whole prompt ------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        kind = self.kind
        if kind == MAMBA:
            return self._mamba_prompt(lp, x, live, last)
        if kind in (GMU, CROSS) and last is not None:
            _CROSS_ROWS[kept[self.owner][0].shape[0]] = x.shape[0]
        if kind == GMU:
            return self._gmu(lp, x, kept[self.owner - 1][2]), None
        q, k, v = self.qkv(lp, x, pos, heads)
        if kind == WINDOW:
            with jax.named_scope("attn_window"):
                a = self._window_prompt(q, k, v)
            return self.attn_out(lp, x, a), self._ring_of(
                k, v, prompt_len(x.shape[0], live, last))
        if kind == CROSS:
            k, v = kept[self.owner]
        if kind == FULL and last is not None:
            # its K/V are kept for every row; from here on the last row
            # alone reaches a cache or the first token
            pos = last[None]
            q = jax.lax.dynamic_slice_in_dim(q, last, 1)
            x = jax.lax.dynamic_slice_in_dim(x, last, 1)
        with jax.named_scope("attn_shared"):
            a = (dense_prefill_attention(q, k, v, causal=True)
                 if q.shape[0] == k.shape[0]
                 else last_row_attention(q, k, v, pos))
        return self.attn_out(lp, x, a), ((k, v) if kind == FULL else None)

    def _mamba_prompt(self, lp, x, live, last):
        T = x.shape[0]
        xin, z = self._in_proj(lp, x)
        n = prompt_len(T, live, last)
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_conv"):
                xc = jax.nn.silu(causal_conv(xin, lp["w_conv"])
                                 + lp["b_conv"].astype(_F32))
                tail = conv_tail(xin, lp["w_conv"].shape[0], n)
            dt, B, C, A = self._selective(lp, xc)
            if live is not None:
                # a recurrence sees padding that causal attention hides:
                # rows from n on neither decay the state nor write to it
                dt = jnp.where(live[:, None], dt, 0.0)
            with jax.named_scope("ssm_scan"):
                y, state = scan_s6(
                    xc, dt, A, B, C,
                    jnp.zeros((self.d_state, self.d_inner), _F32))
            y = y + lp["D"] * xc
        keep = (state, tail)
        if self.hands_memory:
            keep += (y if last is None
                     else jax.lax.dynamic_slice_in_dim(y, last, 1),)
        return self._gate_out(lp, x, y, z), keep

    def store_prompts(self, cache, kept, where):
        """``where``: (a row a layer that owns K/V, the window layers
        and then the run's owner, ``bucket`` wide: in its first columns
        the pool page of each page the layer keeps of the prompt, a
        ring's pages as ``_ring_of`` cut them, the run's a page a page
        of the bucket, as the model's ``_prompt_rows`` reckons them; the
        state entry).  Written page by page, one update a page a pool;
        each Mamba layer's final state and conv tail written whole over
        the entry."""
        ids, entry = where
        k_pool, v_pool, state_pool, conv_pool = cache
        pg = k_pool.shape[3]
        ks, vs = zip(*[[whole_pages(rows, pg) for rows in kv]
                       for kv, t in zip(kept, self.layer_types)
                       if t in (WINDOW, FULL)])
        lin = [sc for sc, t in zip(kept, self.layer_types) if t == MAMBA]
        ids = jnp.concatenate([ids[i, :k.shape[0] // pg]
                               for i, k in enumerate(ks)])
        states = jnp.stack([s[0] for s in lin]).astype(state_pool.dtype)
        tails = jnp.stack([s[1] for s in lin]).astype(
            conv_pool.dtype).reshape((len(lin),) + conv_pool.shape[2:])
        return (write_pages(k_pool, ids, jnp.concatenate(ks)),
                write_pages(v_pool, ids, jnp.concatenate(vs)),
                state_pool.at[:, entry].set(states),
                conv_pool.at[:, entry].set(tails))

    # -- over a decode step's rows ------------------------------------------

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        if lone or x.ndim != 2:
            raise UnsupportedOverState(
                "a chunk of rows a sequence (a suffix prefill, the "
                "speculative verify) would need the state between them")
        kind = self.kind
        if kind == MAMBA:
            return self.recurrent_step(lp, x, cache, addr)
        if kind == GMU:
            # what the last Mamba layer handed on rides behind the buffers
            return self._gmu(lp, x, cache[4]), cache
        q, k, v = self.qkv(lp, x, pos, heads)
        step = self._ring_step if kind == WINDOW else self._shared_step
        a, k_pool, v_pool = step(*cache[:2], q, k, v, addr)
        return self.attn_out(lp, x, a), (k_pool, v_pool) + tuple(cache[2:])

    def _ring_step(self, k_pool, v_pool, q, k, v, addr):
        """A window layer writes at its ring's rows and reads its ring
        alone (``models/exaone_moe.py``'s, on packed rows)."""
        pg, R = k_pool.shape[3], self.ring_pages
        pos = addr.lens[:, None]
        ring = addr.tables[:, self.ring_at:self.ring_at + R]
        with jax.named_scope("attn_window"):
            rows = (jnp.take_along_axis(ring, (pos // pg) % R, axis=1) * pg
                    + pos % pg).reshape(-1)
            k_pool = write_rows(k_pool, rows, k)
            v_pool = write_rows(v_pool, rows, v)
            # the ring's pages are read where they lie in the pool
            a = paged_ring_attention(q[:, None], k_pool[0], v_pool[0], ring,
                                     pos, self.window, heads_major=True)
        return a[:, 0], k_pool, v_pool

    def _shared_step(self, k_pool, v_pool, q, k, v, addr):
        """The one page run: its owner writes the step's row and reads
        it; a cross layer reads the same rows, that one among them, and
        writes nothing."""
        with jax.named_scope("attn_shared"):
            if k is not None:
                k_pool = write_rows(k_pool, addr.flat, k)
                v_pool = write_rows(v_pool, addr.flat, v)
            a = paged_attention(q, k_pool[0], v_pool[0],
                                addr.tables[:, :self.full_pages],
                                addr.lens + 1, heads_major=True)
        return a, k_pool, v_pool

    def recurrent_step(self, lp, x, cache, addr):
        k_pool, v_pool, state_pool, conv_pool, *handed = cache
        xin, z = self._in_proj(lp, x)
        at = self.entries_of(state_pool, addr)
        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_conv"):
                xc, tails = conv_over_entries(
                    conv_pool.reshape((-1,) + conv_pool.shape[2:]), at,
                    xin, lp["w_conv"], lp["b_conv"])
            dt, B, C, A = self._selective(lp, xc)
            with jax.named_scope("ssm_state"):
                states = state_pool.reshape((-1,) + state_pool.shape[2:])
                if pk.use_s6_step(state_pool.dtype, *state_pool.shape[2:]):
                    y, states = s6_step(states, at, dt, xc * dt, A, B, C,
                                        interpret=pk.interpret_mode())
                else:
                    y, new = step_s6(xc, dt, A, B, C, states[at])
                    states = states.at[at].set(new)
            y = y + lp["D"] * xc
        if self.hands_memory:
            handed = handed + [y]
        return self._gate_out(lp, x, y, z), (
            k_pool, v_pool, states.reshape(state_pool.shape),
            tails.reshape(conv_pool.shape), *handed)


# The standard deviation of a q or k row's numbers: the q and k columns
# of an attention layer's projection are drawn N(0, QK_ROW_STD * d^-1/2)
# where every other matrix is N(0, 0.02) (Granite's first lesson,
# ``models/granite_hybrid.py:QK_ROW_STD``).  A score here is ``q.k /
# 8``, of standard deviation ``QK_ROW_STD^2``: at 0.02 x d^1/2 the
# softmax is flat, its output the mean of the v rows it sees, and no
# comparison of logits can see the pairing, the window or the shared
# pages; at 1.6 scores lie ~2.5 apart, as Granite's do at its scale.
QK_ROW_STD = 1.6


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "d", "heads", "kv_heads", "head_dim", "width", "d_inner",
    "d_state", "dt_rank", "conv", "dtype"))
def _init_layer(key, *, kind, d, heads, kv_heads, head_dim, width, d_inner,
                d_state, dt_rank, conv, dtype):
    """One layer's parameters: one program a kind of layer."""
    def normal(k, *shape, std=0.02, dt=dtype):
        return _normal(k, shape=shape, std=std, dtype=dt)

    ones, zeros = jnp.ones((d,), dtype), jnp.zeros((d,), dtype)
    lk = jax.random.split(key, 12)
    lp = {"w_in": ones, "b_in": zeros, "w_post": ones, "b_post": zeros,
          "w_gate": normal(lk[0], d, width),
          "w_up": normal(lk[1], d, width),
          "w_down": normal(lk[2], width, d)}
    if kind == GMU:
        lp.update(w_inproj=normal(lk[3], d, d_inner),
                  w_out=normal(lk[4], d_inner, d))
    elif kind == MAMBA:
        bound = conv ** -0.5
        step = jnp.exp(jax.random.uniform(
            lk[7], (d_inner,), _F32, np.log(0.001), np.log(0.1)))
        taps = jax.random.uniform(lk[5], (conv + 1, d_inner), _F32,
                                  -bound, bound).astype(dtype)
        lp.update(
            w_inproj=normal(lk[3], d, 2 * d_inner),
            w_conv=taps[:conv], b_conv=taps[conv],
            w_x=normal(lk[4], d_inner, dt_rank + 2 * d_state),
            w_dt=normal(lk[6], dt_rank, d_inner),
            b_dt=jnp.log(jnp.expm1(step)),
            A_log=jnp.broadcast_to(
                jnp.log(jnp.arange(1, d_state + 1, dtype=_F32)),
                (d_inner, d_state)),
            D=jnp.ones((d_inner,), _F32),
            w_out=normal(lk[8], d_inner, d))
    else:
        qk, q_w, kv_w = QK_ROW_STD * d ** -0.5, heads * head_dim, \
            kv_heads * head_dim
        lp.update(
            wo=normal(lk[4], q_w, d), b_o=normal(lk[5], d),
            w_sub=jnp.ones((2 * head_dim,), _F32),
            **{name: normal(k, head_dim, std=0.1, dt=_F32)
               for name, k in zip(("lq1", "lk1", "lq2", "lk2"), lk[8:])})
        if kind == CROSS:
            lp.update(wq=normal(lk[3], d, q_w, std=qk),
                      b_q=normal(lk[6], q_w))
        else:
            lp.update(
                wqkv=jnp.concatenate(
                    [normal(lk[3], d, q_w + kv_w, std=qk),
                     normal(lk[7], d, kv_w)], axis=1),
                b_qkv=normal(lk[6], q_w + 2 * kv_w))
    return lp


def init_params(key, *, vocab, d, heads, kv_heads, head_dim, layer_types,
                width, d_inner, d_state, dt_rank, conv, dtype):
    """Every matrix and bias N(0, 0.02) in ``dtype`` but the q and k
    columns of an attention layer's projection (``QK_ROW_STD``), every
    LayerNorm scale 1 and bias 0; differential attention's four
    ``lambda`` vectors N(0, 0.1) and its sub-norm's scale 1, float32, as
    published.  The recurrence's parameters as Mamba's published
    initialisation has them, float32 (Granite's second lesson): ``b_dt``
    the inverse softplus of a step drawn log-uniform in [0.001, 0.1],
    ``A_cn = -(n + 1)``, ``D`` 1; the conv's taps and bias uniform in
    +-conv^-1/2, in ``dtype``.  Made on the device, a layer at a time by
    one program a kind of layer."""
    ks = jax.random.split(key, 1 + len(layer_types))
    sizes = dict(d=d, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                 width=width, d_inner=d_inner, d_state=d_state,
                 dt_rank=dt_rank, conv=conv, dtype=dtype)
    return {"emb": _normal(ks[0], shape=(vocab, d), std=0.02, dtype=dtype),
            "w_f": jnp.ones((d,), dtype), "b_f": jnp.zeros((d,), dtype),
            "layers": [_init_layer(k, kind=kind, **sizes)
                       for k, kind in zip(ks[1:], layer_types)]}


class Phi4FlashLM(StateEntryLM):
    """Phi-4-mini-flash-reasoning over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/phi-4-mini-flash-reasoning.gen_config.py``).

    The constructor's ``pages_per_seq`` is the page run's pages (kept as
    ``full_pages``); the attribute, which the session sizes its table
    rows and its admission by, counts the rings' pages and the state
    entry's column too.  A reservation is the run, ``rings x
    ring_pages`` pages and ONE entry, all or nothing
    (``CacheManager``)."""

    def __init__(self, vocab: int = 200064, d_model: int = 2560,
                 num_heads: int = 40, num_kv_heads: int = 20,
                 num_layers: int = 32, mb_per_layer: int = 2,
                 intermediate_size: int = 10240, sliding_window: int = 512,
                 mamba_d_state: int = 16, mamba_d_conv: int = 4,
                 mamba_expand: int = 2, mamba_dt_rank: int = 160,
                 layer_norm_eps: float = 1e-5, max_len: int = 12288,
                 num_pages: int = 64, page_size: int = 128,
                 pages_per_seq: int = 96, state_entries: int = 9,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        layer_types = layer_kinds(int(num_layers), int(mb_per_layer))
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if num_heads % 4 or num_kv_heads * 2 != num_heads:
            raise ValueError("query pair p reads K/V pair p // 2: twice "
                             "as many query heads as K/V heads, in fours")
        if sliding_window % page_size and page_size % sliding_window:
            raise ValueError("sliding_window and page_size: one must "
                             "divide the other")
        self.kv_heads = int(num_kv_heads)
        self._count_layers(layer_types, MAMBA)
        # the newest `window` rows are whole in window/pg + 1 pages
        self.ring_pages = -(-int(sliding_window) // self.page_size) + 1
        self.rings = sum(t == WINDOW for t in layer_types)
        self.pages_per_seq = (self.full_pages
                              + self.rings * self.ring_pages + 1)
        # the layers that OWN the page run, and all that read it a step
        self.full_layers = 1
        self.shared_readers = 1 + sum(t == CROSS for t in layer_types)
        d_inner = int(mamba_expand) * self.d
        self.block = Phi4FlashBlock(
            layer_types=layer_types, kv_heads=self.kv_heads,
            head_dim=self.dh, window=int(sliding_window), d_inner=d_inner,
            d_state=int(mamba_d_state), dt_rank=int(mamba_dt_rank),
            eps=float(layer_norm_eps), full_pages=self.full_pages,
            ring_pages=self.ring_pages, page_size=self.page_size)
        dtype = jnp.dtype(dtype)
        self.conv_taps = int(mamba_d_conv)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, kv_heads=self.kv_heads, head_dim=self.dh,
            layer_types=layer_types, width=int(intermediate_size),
            d_inner=d_inner, d_state=int(mamba_d_state),
            dt_rank=int(mamba_dt_rank), conv=self.conv_taps, dtype=dtype)
        # a page's row as the gauges count it: the published K/V heads
        # (stored two a row of whole lanes, nothing padded)
        self.stored_heads = self.kv_heads
        self._make_pools(
            num_pages, dtype, int(state_entries),
            (self.kv_heads // 2, 2 * self.dh),
            (int(mamba_d_state), d_inner),
            tail_shape(self.conv_taps, d_inner))

    def _make_pools(self, num_pages, dtype, state_entries, page_heads,
                    state_shape, tail_shape):
        """``StateEntryLM``'s, the pages with their heads outside their
        rows: ``(1, N, heads, pg, width)``."""
        self.allocator = CacheManager(num_pages, state_entries)
        heads, width = page_heads
        shape = (1, num_pages, heads, self.page_size, width)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype)
        self.extra_pools = (
            jnp.zeros((self.linear_layers, state_entries, *state_shape),
                      _F32),
            jnp.zeros((self.linear_layers, state_entries, *tail_shape),
                      dtype))

    def _observe(self, phase, report, rows):
        if phase == "prefill":
            _M_PREFILL_ROWS.inc(rows, part="self")
            _M_PREFILL_ROWS.inc(_CROSS_ROWS.get(rows, rows), part="cross")
        else:
            _M_SHARED_READS.inc(self.shared_readers)

    # -- the reservation: the run, the rings, then the entry -----------------

    def context_pages(self, prompt, max_new_tokens: int) -> int:
        return (super().context_pages(prompt, max_new_tokens)
                + self.rings * self.ring_pages)

    def _split(self, pages):
        """(the page run, the rings' pages) of a sequence's ids."""
        pages = self.allocator.pages_of(pages)
        n = len(pages) - self.rings * self.ring_pages
        if n < 1:
            raise ValueError(
                f"{len(pages)} pages hold no page run beside "
                f"{self.rings} rings of {self.ring_pages}")
        return pages[:n], pages[n:]

    def pool_table(self, pages) -> np.ndarray:
        run, rings = self._split(pages)
        t = np.zeros((self.pages_per_seq,), np.int32)
        t[:len(run)] = run
        t[self.full_pages:self.full_pages + len(rings)] = rings
        t[self.block.entry_at] = self.allocator.entry_of(pages)
        return t

    def _prompt_rows(self, pages, bucket: int, n: int):
        """Where an ``n``-row prompt's bucket goes, page by page: ((layers
        that own K/V, bucket): in the first columns of a layer's row the
        pool page of each page the bucket's program stores of it, the
        window layers' and then the run's, as
        ``Phi4FlashBlock.store_prompts`` takes them; the sequence's
        state entry).  A ring keeps the prompt's last ``ring_pages``
        pages, page ``p`` in its column ``p % ring_pages`` (as
        ``models/exaone_moe.py`` lays a ring out): the program cuts them
        at the first, ``max(last - ring_pages + 1, 0)``, and of a prompt
        with fewer the cut's pages past the last go to the null page, as
        the run's pages past the table's do.  The rows before the ring,
        which no later row sees, go nowhere.  The array keeps a column a
        bucket row, most of them unread: it is the shape the benchmark's
        driver lowers a bucket's program with
        (``perf/drivers/generate_dhd.py:compiled_texts``)."""
        table = self.pool_table(pages)
        pg, R = self.page_size, self.ring_pages
        P = -(-bucket // pg)
        last = (n - 1) // pg
        held = max(last - R + 1, 0) + np.arange(min(R, P))
        cols = (self.full_pages + R * np.arange(self.rings)[:, None]
                + held % R)
        ring = np.where(held <= last, table[cols], 0)
        run = table[:P]         # a bucket is no longer than the run
        ids = np.zeros((self.rings + 1, bucket), np.int32)
        ids[:self.rings, :held.size], ids[self.rings, :P] = ring, run
        rings, runs = np.count_nonzero(ring), np.count_nonzero(run)
        _M_STORED_PAGES.inc(rings, kind="ring")
        _M_STORED_PAGES.inc(runs, kind="run")
        _M_STORED_PAGES.inc(ring.size + P - rings - runs, kind="null")
        return ids, np.int32(table[self.block.entry_at])

    def cache_rows(self, lens) -> dict:
        """What is resident for sequences of ``lens`` rows, by kind of
        cache: the one run holds every row once, whatever reads it; a
        ring its newest ``ring_pages * page_size`` at most; a Mamba
        layer one state a sequence."""
        lens = np.asarray(lens, np.int64)
        ring = self.ring_pages * self.page_size
        return {"full": int(lens.sum()),
                "window": int(np.minimum(lens, ring).sum()) * self.rings,
                "state": len(lens) * self.linear_layers}

    def cache_bytes(self, lens) -> dict:
        row = 2 * self.stored_heads * self.dh * self.k_pool.dtype.itemsize
        rows = self.cache_rows(lens)
        return {"full": rows["full"] * row, "window": rows["window"] * row,
                "state": len(lens) * self.entry_bytes()}
