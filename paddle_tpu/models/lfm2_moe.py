"""LFM2-MoE (LiquidAI/LFM2-8B-A1B, ``model_type`` lfm2_moe) behind
``/generate``: gated short-conv layers three of four, a grouped-query
attention layer the fourth, two leading dense feed-forwards and then
sigmoid-routed experts, every one held.

The block (pre-norm, RMSNorm ``w x / sqrt(mean x^2 + eps)`` everywhere):

    h = x + op_l(RMSNorm_op(x));   x' = h + ffn_l(RMSNorm_ffn(h))
    logits = E^T RMSNorm(x_L)                      (the head is tied)

A **conv** layer, ``u`` the normed input (T, d):

    [B; C; z] = W_in u  (three chunks of d, in that order);  g = B * z
    c_t = sum_{j<L} w[j] g_{t-(L-1)+j}   (depthwise, causal, zeros before
        row 0; NO bias, NO activation; L = conv_L_cache, 3)
    op(x) = W_out (C * c)

A sequence's whole recurrent state is the layer's conv tail: rows
``g_{n-2}, g_{n-1}`` (2 x 2,048 bfloat16 = 8 KB a layer), kept in the
sequence's state entry (``decode/state_entry.py``); there is no state
pool (a placeholder every program threads and none reads).  A decode
step's conv is ONE ``pallas/conv_step.py`` call a layer over the tail
pool where it lies, told to apply no activation; both gates are the
block's, in XLA, outside the kernel.

An **attention** layer: 32 query heads on 8 K/V heads of 64, no bias; a
learned RMSNorm over each head's 64 numbers of q and of k (one vector
each, shared by the heads), THEN rotate-half RoPE over the whole head
(theta 1e6); causal softmax of ``q.k / 8``.  Pages hold two heads a
128-lane row and the decode step reads them through the grouped walk
kernel: ``models/granite_hybrid.py:PackedHeadPages``, as it is.

The **feed-forward**: layers ``< num_dense_layers`` a SwiGLU; the others
the routed sum over the top-k of ``num_experts`` SwiGLU experts by the
sigmoid rule with a selection bias (``moe.sigmoid_scores``: ranked by
``s + bias``, weighed ``s / (sum of the chosen + 1e-6)``), no shared
expert.

**A prompt longer than the top bucket** runs as that bucket and then
chunks, each from what the one before left (``StateEntryLM.prefill``):
in a chunk a conv layer starts from the entry's tail and writes the
tail at the chunk's last real row back (``recurrent_chunk``), an
attention layer writes its rows to the pages, gathers the ``done``
cached rows by the table and reads them and the chunk itself through
the flash forward, merged by log-sum-exp
(``decode/attention.py:prompt_chunk_attention``; ``page_chunk``).  What
would need the tail at an EARLIER row (a prefix hit, a fork, the
speculative verify) stays refused by ``UnsupportedOverState``.

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, rotation and router; the gated
rows ``g`` the conv sees are kept in the weights' dtype on every path,
so that a tail written by the prefill is what the step would have kept.
Random weights only: loading a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.decode.attention import prompt_chunk_attention
from paddle_tpu.decode.model import _write_rows
from paddle_tpu.decode.state_entry import (  # noqa: F401  (re-exported)
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    causal_conv,
    conv_over_entries,
    conv_tail,
    tail_shape,
)
from paddle_tpu.models import moe
from paddle_tpu.models.exaone_moe import swiglu
from paddle_tpu.models.granite_hybrid import PackedHeadPages, heads_a_row
from paddle_tpu.models.olmoe import _mm, rms_norm, rope, rope_angles

_F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
# the published layer_types' period: three conv layers, then attention
# (attention at layers 2, 6, 10, ...: the period's third place)
PERIOD = (CONV, CONV, ATTENTION, CONV)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeBlock(PackedHeadPages, StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side.
    ``pack``: the K/V heads a page's row holds side by side;
    ``route_eps``: the epsilon in the routing weights' denominator."""

    recurrent_kind = CONV
    layer_types: tuple = PERIOD * 3
    kv_heads: int = 8
    head_dim: int = 64
    pack: int = 2
    eps: float = 1e-5
    theta: float = 1e6
    top_k: int = 4
    scale: float = 1.0
    route_eps: float = 1e-6
    experts: int = 32
    full_pages: int = 192        # table columns of the page run
    at: int = 0

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def qkv(self, lp, x, pos, heads):
        """An attention layer's: pre-norm, the per-head RMSNorm of q and
        k, then the rotation."""
        n = rms_norm(x, lp["w_in"], self.eps)
        lead, dh = x.shape[:-1], self.head_dim
        q = _mm(n, lp["wq"]).reshape(lead + (heads, dh))
        k = _mm(n, lp["wk"]).reshape(lead + (self.kv_heads, dh))
        v = _mm(n, lp["wv"]).reshape(lead + (self.kv_heads, dh))
        cos, sin = rope_angles(pos, dh, self.theta)
        dtype = lp["wq"].dtype

        def placed(a, w):
            return rope(rms_norm(a, w, self.eps), cos, sin).astype(dtype)

        return placed(q, lp["w_qn"]), placed(k, lp["w_kn"]), v.astype(dtype)

    def attn_out(self, lp, x, a):
        return x + _mm(a, lp["wo"])

    def router_rows(self, lp, x):
        """What the feed-forward (and a routed layer's router) is fed:
        (R, d) in the weights' dtype."""
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["w_gate"].dtype)
        return m.reshape(-1, m.shape[-1])

    def scores(self, lp):
        return moe.sigmoid_scores(lp["b"], self.scale, self.route_eps)

    def mlp(self, lp, x, live):
        """A leading layer's dense SwiGLU, or the routed experts, all
        held.  Reports (experts,) int32: the live rows' assignments an
        expert (a dense layer: zeros)."""
        m = self.router_rows(lp, x)
        if "wr" not in lp:
            y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
            load = jnp.zeros((self.experts,), jnp.int32)
        else:
            y, load, _ = moe.routed_experts(
                m, lp["wr"], lp["w_gate"], lp["w_up"], lp["w_down"],
                top_k=self.top_k,
                live=None if live is None else live.reshape(-1),
                scores=self.scores(lp))
        return x + y.reshape(x.shape), load

    def head(self, params, x):
        """The tied head: the embedding contracted over its columns
        where it lies (no transposed copy of it)."""
        emb = params["emb"]
        n = rms_norm(x, params["w_f"], self.eps).astype(emb.dtype)
        return jax.lax.dot_general(
            n, emb, (((n.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_F32)

    # -- a conv layer's pieces ----------------------------------------------

    def _gates(self, lp, x):
        """-> (the rows the conv sees ``g = B * z``, in the weights'
        dtype; the output gate's rows ``C``, float32)."""
        d = x.shape[-1]
        bcz = _mm(rms_norm(x, lp["w_in"], self.eps), lp["w_bcz"])
        with jax.named_scope("short_conv"):
            g = (bcz[..., :d] * bcz[..., 2 * d:]).astype(lp["w_bcz"].dtype)
        return g, bcz[..., d:2 * d]

    def _out(self, lp, x, c, gate):
        """The output gate, the output projection and the residual."""
        with jax.named_scope("short_conv"):
            y = gate * c
        return x + _mm(y, lp["w_out"])

    # -- the mixers ---------------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        if not self.recurrent:
            return super().prompt_mixer(lp, x, pos, heads, live)
        g, gate = self._gates(lp, x)
        n = x.shape[0] if live is None else jnp.sum(live.astype(jnp.int32))
        with jax.named_scope("short_conv"), \
                jax.named_scope("short_conv_scan"):
            c = causal_conv(g, lp["w_conv"])
            tail = conv_tail(g, lp["w_conv"].shape[0], n)
        return self._out(lp, x, c, gate), (None, tail)

    def recurrent_step(self, lp, x, cache, addr):
        k_pool, v_pool, state_pool, conv_pool = cache
        g, gate = self._gates(lp, x)
        with jax.named_scope("short_conv"), \
                jax.named_scope("short_conv_step"):
            c, tails = conv_over_entries(
                conv_pool.reshape((-1,) + conv_pool.shape[2:]),
                self.entries_of(conv_pool, addr), g, lp["w_conv"],
                activation=None)
        return self._out(lp, x, c, gate), (
            k_pool, v_pool, state_pool, tails.reshape(conv_pool.shape))

    def recurrent_chunk(self, lp, x, cache, chunk):
        """A conv layer over a chunk's rows from the entry's tail, the
        tail at the chunk's last real row written back."""
        k_pool, v_pool, state_pool, conv_pool = cache
        taps, C = lp["w_conv"].shape
        slab, entry = self.index_in_kind, chunk.table[self.full_pages]
        g, gate = self._gates(lp, x)
        with jax.named_scope("short_conv"), \
                jax.named_scope("short_conv_scan"):
            before = conv_pool[slab, entry].reshape(taps - 1, C)
            c = causal_conv(g, lp["w_conv"], before)
            tail = conv_tail(g, taps, chunk.n, before)
            conv_pool = conv_pool.at[slab, entry].set(
                tail.reshape(conv_pool.shape[2:]))
        return self._out(lp, x, c, gate), (k_pool, v_pool, state_pool,
                                           conv_pool)

    def page_chunk(self, lp, x, pos, pages, li, chunk, heads):
        """An attention layer over a chunk's rows: written to the page
        run, then attending over the ``done`` cached rows, gathered by
        the table, and the chunk's own causal part."""
        k_pool, v_pool = pages
        q, k, v = self.qkv(lp, x, pos, heads)
        slab, cols = self.index_in_kind, chunk.done // k_pool.shape[2]
        with jax.named_scope("attn_full"):
            k_pool = _write_rows(k_pool, slab, chunk.flat,
                                 self._packed(k, k_pool))
            v_pool = _write_rows(v_pool, slab, chunk.flat,
                                 self._packed(v, v_pool))
            with jax.named_scope("attn_chunk"):
                N, pg = k_pool.shape[1:3]
                # each cached row's flat row of the pool seen as rows,
                # as ``_write_rows`` addresses them
                at = ((slab * N + chunk.table[:cols])[:, None] * pg
                      + jnp.arange(pg, dtype=jnp.int32)).reshape(-1)

                def run(pool):          # the cached rows, (Hkv, done, dh)
                    rows = pool.reshape((-1,) + pool.shape[3:])[at]
                    rows = rows.reshape(chunk.done, self.kv_heads,
                                        self.head_dim)
                    return jnp.moveaxis(rows, 1, 0)

                a = prompt_chunk_attention(q, k, v, run(k_pool), run(v_pool))
        return (self.attn_out(lp, x, a.reshape(x.shape[0], -1)),
                (k_pool, v_pool))


# The q/k norms' scales are drawn uniform in this range a channel.  At 1
# the norm commutes with the rotation (nothing could tell on which side
# of it the norm stands) and a score ``q.k / 8`` of unit-RMS rows is ~1:
# the softmax over thousands of rows is flat, its output the mean of the
# v rows, and nothing in the logits sees a rotation, a page or a scale.
# At 0.75..2.25 the scores lie ~2.8 apart: a row attends to a few of the
# rows before it, as a trained model's sharper heads do (the reasoning
# of ``granite_hybrid.QK_ROW_STD``).
QK_NORM = (0.75, 2.25)


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "kind", "routed", "d", "heads", "kv_heads", "head_dim", "width",
    "experts", "conv", "dtype"))
def _init_layer(key, *, kind, routed, d, heads, kv_heads, head_dim, width,
                experts, conv, dtype):
    """One layer's parameters: one program a (kind of mixer, kind of
    feed-forward)."""
    def normal(k, *shape, std=0.02, dt=dtype):
        return _normal(k, shape=shape, std=std, dtype=dt)

    ones = jnp.ones((d,), dtype)
    lk = jax.random.split(key, 9)
    lead = (experts,) if routed else ()
    lp = {"w_in": ones, "w_post": ones,
          "w_gate": normal(lk[0], *lead, d, width),
          "w_up": normal(lk[1], *lead, d, width),
          "w_down": normal(lk[2], *lead, width, d)}
    if routed:
        lp.update(wr=normal(lk[7], d, experts),
                  b=normal(lk[8], experts, dt=_F32))
    if kind == ATTENTION:
        lp.update(wq=normal(lk[3], d, heads * head_dim),
                  wk=normal(lk[4], d, kv_heads * head_dim),
                  wv=normal(lk[5], d, kv_heads * head_dim),
                  wo=normal(lk[6], heads * head_dim, d),
                  w_qn=jax.random.uniform(lk[7], (head_dim,), _F32, *QK_NORM
                                          ).astype(dtype),
                  w_kn=jax.random.uniform(lk[8], (head_dim,), _F32, *QK_NORM
                                          ).astype(dtype))
        return lp
    bound = conv ** -0.5
    lp.update(w_bcz=normal(lk[3], d, 3 * d),
              w_conv=jax.random.uniform(lk[4], (conv, d), _F32, -bound,
                                        bound).astype(dtype),
              w_out=normal(lk[5], d, d))
    return lp


def init_params(key, *, vocab, d, heads, kv_heads, head_dim, layer_types,
                dense_layers, dense_width, expert_width, experts, conv,
                dtype):
    """Every matrix N(0, 0.02) in ``dtype``, every norm scale 1, the
    router's selection bias N(0, 0.02) float32; the conv's taps uniform
    in +-conv^-1/2 (the published module's default for its depthwise
    conv; at N(0, 0.02) the conv's part of a layer would be a fiftieth
    of what it is); the q/k norms' scales uniform in ``QK_NORM`` a
    channel, which says why.  Made on the device, a layer at a time."""
    ks = jax.random.split(key, 1 + len(layer_types))
    sizes = dict(d=d, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                 experts=experts, conv=conv, dtype=dtype)
    return {"emb": _normal(ks[0], shape=(vocab, d), std=0.02, dtype=dtype),
            "w_f": jnp.ones((d,), dtype),
            "layers": [
                _init_layer(k, kind=kind, routed=i >= dense_layers,
                            width=(expert_width if i >= dense_layers
                                   else dense_width), **sizes)
                for i, (k, kind) in enumerate(zip(ks[1:], layer_types))]}


class Lfm2MoeLM(StateEntryLM):
    """LFM2-MoE over the paged skeleton: what ``make_decode_model()``
    returns (``perf/configs/lfm2-8b-a1b.gen_config.py``).  The
    reservation, the table row, the chunk loop and the refusals are
    ``decode/state_entry.py``'s."""

    def __init__(self, vocab: int = 65536, d_model: int = 2048,
                 num_heads: int = 32, num_kv_heads: int = 8,
                 head_dim: int = 64,
                 layer_types: Sequence[str] = Lfm2MoeBlock.layer_types,
                 num_dense_layers: int = 2, intermediate_size: int = 7168,
                 moe_intermediate_size: int = 1792, num_experts: int = 32,
                 experts_per_tok: int = 4, routed_scaling_factor: float = 1.0,
                 route_eps: float = 1e-6, conv_L_cache: int = 3,
                 norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 max_len: int = 24576, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 192,
                 state_entries: int = 9, prefill_rows: int = 8192,
                 chunk_rows: int = 4096, dtype="bfloat16", bos_id: int = 1,
                 eos_id: int = -1, seed: int = 0):
        layer_types = tuple(layer_types)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if num_heads % num_kv_heads:
            raise ValueError("the K/V heads have to divide the query heads")
        if not set(layer_types) <= {CONV, ATTENTION}:
            raise ValueError(f"layer_types are {CONV!r} or {ATTENTION!r}")
        self.dh, self.kv_heads = int(head_dim), int(num_kv_heads)
        self._count_layers(layer_types, CONV)
        self._chunked(prefill_rows, chunk_rows)
        pack = heads_a_row(self.kv_heads, self.dh)
        self.block = Lfm2MoeBlock(
            layer_types=layer_types, kv_heads=self.kv_heads,
            head_dim=self.dh, pack=pack, eps=float(norm_eps),
            theta=float(rope_theta), top_k=int(experts_per_tok),
            scale=float(routed_scaling_factor), route_eps=float(route_eps),
            experts=int(num_experts), full_pages=self.full_pages)
        dtype = jnp.dtype(dtype)
        self.conv_taps = int(conv_L_cache)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, kv_heads=self.kv_heads, head_dim=self.dh,
            layer_types=layer_types, dense_layers=int(num_dense_layers),
            dense_width=int(intermediate_size),
            expert_width=int(moe_intermediate_size),
            experts=self.block.experts, conv=self.conv_taps, dtype=dtype)
        self._routed = list(range(int(num_dense_layers), self.layers))
        # a page's row as the gauges count it: the published K/V heads
        # (stored ``pack`` a row of whole lanes, nothing padded)
        self.stored_heads = self.kv_heads
        self._make_pools(
            num_pages, dtype, int(state_entries),
            (self.kv_heads // pack, pack * self.dh), None,
            tail_shape(self.conv_taps, self.d))

    def _observe(self, phase, report, rows):
        load = np.asarray(report)[self._routed]       # (routed, experts)
        if load.size:
            moe.count_load(phase, load, rows, self.block.top_k,
                           self.block.experts)
