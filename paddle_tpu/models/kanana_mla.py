"""Kanana-2-30B-A3B (kakaocorp/kanana-2-30b-a3b-instruct-2601,
``model_type`` deepseek_v3) behind ``/generate``, as ONE chip of an
expert-parallel deployment serves it: multi-head LATENT attention over
the paged skeleton, and the DeepSeek-V3 routed feed-forward.

The layer, as published.  Pre-norm residuals; ``h = RMSNorm(x)``:

    q_t = h_t W_q -> heads x [q^n (nope) ; q^r (rope)]
    [c_t ; k^r_t] = h_t W_kva           (rank + rope wide)
    c_t <- RMSNorm_rank(c_t);  q^r, k^r rotated at position t, pairs
    (2i, 2i + 1) (interleaved); k^r ONE row shared by all heads
    [k^n_{t,h} ; v_{t,h}] = c_t W_kvb   (rank -> heads x (nope + v))
    p = softmax_{s<=t}((q^n.k^n + q^r.k^r) / sqrt(nope + rope))
    x <- x + concat_h(sum_s p v_{s,h}) W_o

``W_kvb`` is kept as its two per-head halves, ``w_uk`` (heads, nope,
rank) and ``w_uv`` (heads, rank, v): the same numbers, laid out for
both of the ways the layer is computed.

Computed two ways, the same numbers reassociated:

- **expanded** (a prefill bucket; a suffix over cached rows longer
  than that):
  ``k^n`` and ``v`` made from ``c`` by ``w_uk`` / ``w_uv``
  (``attn_latent_expand``), heads of ``nope + rope`` for q and k and of
  ``v`` for the values through the dense prefill's flash kernel, the
  values padded with zero lanes to the keys' head size (the kernel has
  one) and the padding sliced off its output;
- **absorbed** (a decode step; a verify chunk; a suffix over cached
  rows up to ``ABSORBED_MAX_ROWS``): ``q~ = w_uk q^n``
  (rank wide), the score ``(q~.c + q^r.k^r) / sqrt(nope + rope)``,
  ``o~ = sum p c`` and ``o = o~ w_uv`` (``attn_latent_absorb``): every
  head attends on the ONE stored row, whose first ``rank`` lanes are
  also the value: ``pallas/latent_attention.py``, one call a layer.

``chunk_form`` picks between them from the call's static shape.

What a page holds (the second row format on the pages): ONE row a token
a layer, ``[c (rank) ; k^r (rope) ; zeros]`` at ``row_width`` lanes,
whole 128-lane tiles: 576 numbers stored at 640.  At 576 the chip's
compiler lays the pool out at 640 lanes anyway and refuses the kernel's
page copy (``tests/test_chip_compile.py``'s layout probe); ``c`` and
``k^r`` in two pools are the same bytes in two copies a page.  The pool
is ``(layers, pages, page_size, row_width)``, the skeleton's ``k_pool``;
its ``v_pool`` is a placeholder of one element a layer that every
program threads through and none reads.

The feed-forward is K-EXAONE's to the letter (``models/exaone_moe.py``
over ``models/moe.py``): layer 0 a dense SwiGLU, then the sigmoid router
over the published experts of which this chip holds a range, beside an
always-on shared SwiGLU; an untied head over the held rows of the
vocabulary.

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, scores and rotation; the latent
rows in the pool's dtype.  Random weights only: loading a checkpoint is
not supported.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.decode.attention import dense_prefill_attention
from paddle_tpu.decode.model import (PagedDecoderLM, PageRunCache,
                                    _dense_blocks)
from paddle_tpu.decode.paged_kv import PageAllocator
from paddle_tpu.models.exaone_moe import ExaoneMoeBlock, ExaoneMoeLM
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.pallas import latent_attention as la

_F32 = jnp.float32
_NEG_INF = -1e30

# Rows of a chunk (a sequence's, in one call) up to which the chunk runs
# absorbed.  By the FLOPs the two are even at ~170 rows (absorbed costs
# heads x (width + rank) x 2 a (query row, cached row) pair and nothing
# a cached row; expanded heads x (nope + rope + v) x 2 a pair and 2 x
# rank x heads x (nope + v) a cached row to expand it).  Measured on the
# chip, one layer, one sequence over its 8,192 table rows (PERF.md
# section 6, PR 45): absorbed 0.5 ms up to 16 rows (the kernel), 0.9 /
# 2.5 / 7.9 ms at 64 / 256 / 1,024 rows (its jnp reference: one gather,
# two matmuls of rows x heads query rows); expanded, as written below in
# XLA, 25.4 ms a block of 256 query rows whatever the rows: absorbed
# wins as far as its scores fit beside the pool (rows x heads x table
# rows float32: 1 GB at 1,024), so the edge is memory's, not the FLOPs'.
ABSORBED_MAX_ROWS = 1024

# q blocks of an expanded chunk: the scores are (heads, this, cached
# rows) float32 at a time, never (heads, T, cached rows)
EXPANDED_Q_BLOCK = 256

# Std of an entry of q, of k^n and of k^r under unit-RMS inputs, so a
# score's too.  At the N(0, 0.02) of the other weights they come out at
# 0.9 / 0.45 / 0.9, scores ~0.5 apart, the softmax flat over a thousand
# rows, and nothing the attention does wrong shows in the logits (PR
# 41's lesson).  At 1.0 every attention ablation reads 25 times the
# bf16 noise or more.  Not wider: sixteen random layers of sharp heads
# amplify rounding (the bf16 program against the float32 reference at
# the median row: 0.013 at 1.0, 0.020 at 1.2, 0.107 at 1.4, 0.17 at 1.7,
# where no limit separates the program from a dropped expert; PERF.md
# section 6, PR 45).
QK_ROW_STD = 1.0

_M_PREFILL_PAIRS = _metrics.counter(
    "attn_latent_prefill_pairs_total",
    "causal (query row, key row) pairs of the bucketed prefills' real "
    "rows, n (n + 1) / 2 a prompt of n rows: one layer's; the latent "
    "layers' attention FLOPs are this x layers x heads x (qk + v head "
    "sizes) x 2")


class UnsupportedOverLatentRows(RuntimeError):
    """Asked of the latent model what its programs do not lay out: a
    chunk for SEVERAL sequences of more rows each than the absorbed
    kernel keeps resident (off the kernel a chunk gathers, or expands,
    a sequence's whole page run: for 64 slots that is the pool)."""


def kernel_rows(heads: int) -> int:
    """Rows a sequence may bring to a chunk the kernel runs."""
    return la.MAX_ROWS // heads


def row_width(rank: int, rope_dim: int) -> int:
    """Lanes a stored row takes: ``rank + rope_dim`` in whole tiles."""
    return -(-(rank + rope_dim) // la.LANES) * la.LANES


def chunk_form(rows: int) -> str:
    """How a chunk of ``rows`` rows a sequence over cached rows is
    computed: ``"absorbed"`` or ``"expanded"``.  A function of the
    call's static shape alone (a decode step is a chunk of one)."""
    return "absorbed" if rows <= ABSORBED_MAX_ROWS else "expanded"


def rope_interleaved(x, pos, theta):
    """Rotate ``x`` (..., n, dr) at the rows' absolute positions ``pos``
    (...): channel 2i pairs with 2i + 1, at ``pos * theta^(-2i / dr)``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(_F32).reshape(x.shape[:-1] + (half, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _pages(pool, li, tables):
    """The pool as the kernel takes it for layer ``li``: all layers'
    pages flat (a bitcast) and the tables moved by ``li * N``."""
    L, N, pg, W = pool.shape
    return pool.reshape(L * N, pg, W), tables + li * N


def _write(pool, li, flat, rows):
    """``pool`` with layer ``li``'s ``rows`` (R, W) at its flat rows
    ``flat`` (R,): one scatter into the donated buffer."""
    L, N, pg, W = pool.shape
    return (pool.reshape(L * N * pg, W).at[li * N * pg + flat]
            .set(rows.astype(pool.dtype)).reshape(pool.shape))


@dataclasses.dataclass(frozen=True)
class KananaMlaBlock(PageRunCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``PageRunCache`` for the cache side, which this block defines whole:
    the two mixers and ``store_prompts`` over the one latent pool."""

    nope: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    rank: int = 512
    eps: float = 1e-6
    theta: float = 1e6
    top_k: int = 6
    scale: float = 2.448
    held: tuple = (0, 16)

    # the embedding, the output projection, the feed-forward (dense in
    # layer 0, then routed beside the shared expert) and the head are
    # K-EXAONE's
    embed = ExaoneMoeBlock.embed
    attn_out = ExaoneMoeBlock.attn_out
    mlp = ExaoneMoeBlock.mlp
    head = ExaoneMoeBlock.head

    @property
    def width(self) -> int:
        return row_width(self.rank, self.rope_dim)

    @property
    def softmax_scale(self) -> float:
        return float(self.nope + self.rope_dim) ** -0.5

    # -- the pieces ---------------------------------------------------------

    def queries(self, lp, n, pos, heads):
        """-> (q^n (..., heads, nope) float32, q^r rotated)."""
        q = _mm(n, lp["wq"]).reshape(
            n.shape[:-1] + (heads, self.nope + self.rope_dim))
        return (q[..., :self.nope],
                rope_interleaved(q[..., self.nope:], pos, self.theta))

    def down(self, lp, n, pos):
        """What a page keeps of the rows ``n`` (the layer's normalised
        input): (..., width) in the weights' dtype, ``[RMSNorm(c) ;
        k^r rotated ; zeros]``."""
        with jax.named_scope("attn_latent_down"):
            kva = _mm(n, lp["w_kva"])
            c = rms_norm(kva[..., :self.rank], lp["w_cn"], self.eps)
            kr = rope_interleaved(kva[..., None, self.rank:], pos,
                                  self.theta)[..., 0, :]
            return self._at_row_width([c, kr], lp["w_kva"].dtype)

    def _at_row_width(self, parts, dtype):
        """``parts`` side by side along the lanes, zeros behind them up
        to the stored width, in ``dtype``."""
        used = sum(p.shape[-1] for p in parts)
        pad = jnp.zeros(parts[0].shape[:-1] + (self.width - used,), _F32)
        return jnp.concatenate([*parts, pad], axis=-1).astype(dtype)

    def expand(self, lp, rows, heads):
        """Stored rows (T, width) -> (k (T, heads, nope + rope), v (T,
        heads, v)) in the rows' dtype: the keys' own part and the values
        from ``c``, the rotated key shared by every head."""
        with jax.named_scope("attn_latent_expand"):
            c = rows[:, :self.rank]
            kn = jnp.einsum("tc,hnc->thn", c, lp["w_uk"],
                            preferred_element_type=_F32)
            v = jnp.einsum("tc,hcv->thv", c, lp["w_uv"],
                           preferred_element_type=_F32)
            kr = jnp.broadcast_to(
                rows[:, None, self.rank:self.rank + self.rope_dim],
                (rows.shape[0], heads, self.rope_dim))
            k = jnp.concatenate([kn.astype(rows.dtype), kr], axis=-1)
            return k, v.astype(rows.dtype)

    # -- the cache side -----------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        """One whole prompt, expanded -> (rows after the residual, the
        stored rows (T, width))."""
        with jax.named_scope("attn_latent"):
            n = rms_norm(x, lp["w_in"], self.eps)
            qn, qr = self.queries(lp, n, pos, heads)
            rows = self.down(lp, n, pos)
            k, v = self.expand(lp, rows, heads)
            q = jnp.concatenate([qn, qr], axis=-1).astype(rows.dtype)
            # the flash kernel has one head size: the values ride in the
            # keys' with zero lanes behind them
            v = jnp.pad(v, ((0, 0), (0, 0), (0, k.shape[-1] - self.v_dim)))
            a = dense_prefill_attention(q, k, v, causal=True)
            a = a[..., :self.v_dim].reshape(x.shape[0], -1)
            return self.attn_out(lp, x, a), rows

    def store_prompts(self, cache, kept, where):
        """One scatter of every layer's rows into the pool seen flat
        (indexed a layer, ``.at[:, where]``, the compiler lays the pool
        out layers-innermost and copies all of it)."""
        pool, placeholder = cache
        L, N, pg, W = pool.shape
        rows = jnp.stack(kept).astype(pool.dtype)           # (L, T, W)
        flat = (jnp.arange(L, dtype=jnp.int32)[:, None] * (N * pg)
                + where[None, :]).reshape(-1)
        return (pool.reshape(L * N * pg, W).at[flat]
                .set(rows.reshape(-1, W)).reshape(pool.shape), placeholder)

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        """A step's rows, a chunk's or one sequence's suffix over the
        cached rows: the new rows written, then attention in the form
        ``chunk_form`` names for the rows a sequence brings."""
        pool, placeholder = cache
        step = x.ndim == 2 and not lone
        xs = x[:, None] if step else x[None] if lone else x   # (S, T, d)
        ps = pos[:, None] if step else pos[None] if lone else pos
        tables = addr.tables[None] if lone else addr.tables
        S, T = xs.shape[:2]
        with jax.named_scope("attn_latent"):
            n = rms_norm(xs, lp["w_in"], self.eps)
            qn, qr = self.queries(lp, n, ps, heads)
            pool = _write(pool, li, addr.flat,
                          self.down(lp, n, ps).reshape(S * T, -1))
            if S != 1 and T > kernel_rows(heads):
                raise UnsupportedOverLatentRows(
                    f"a chunk of {T} rows for each of {S} sequences: over "
                    f"{kernel_rows(heads)} rows a chunk runs off the "
                    "kernel, one sequence a call")
            if chunk_form(T) == "absorbed":
                a = self._absorbed(lp, qn, qr, pool, li, tables, addr.lens,
                                   heads)
            else:
                a = self._expanded(lp, qn[0], qr[0], pool, li, tables[0],
                                   addr.lens[0], heads)[None]
            out = self.attn_out(lp, xs, a.reshape(S, T, -1))
        return out.reshape(x.shape), (pool, placeholder)

    def _absorbed(self, lp, qn, qr, pool, li, tables, lens, heads,
                  bias=None):
        """q (S, T, heads, .) over the slots' pages -> (S, T, heads, v);
        ``bias`` (S, table rows), where a block has one (a sparse
        layer's selected set), is added to a slot's scores."""
        from paddle_tpu import pallas as pk

        S, T = qn.shape[:2]
        dtype, W = pool.dtype, self.width
        with jax.named_scope("attn_latent_absorb"):
            q_abs = jnp.einsum("sthn,hnc->sthc", qn.astype(lp["w_uk"].dtype),
                               lp["w_uk"], preferred_element_type=_F32)
            q = self._at_row_width([q_abs, qr], dtype).reshape(
                S, T * heads, W)
        pages, moved = _pages(pool, li, tables)
        kw = dict(heads=heads, v_width=self.rank, scale=self.softmax_scale)
        if pk.use_latent_paged_attention(dtype, pages.shape[1], T * heads,
                                         W, self.rank):
            o = la.latent_paged_attention(q, pages, moved, lens, bias,
                                          interpret=pk.interpret_mode(),
                                          **kw)
        else:
            o = la.latent_paged_attention_reference(q, pages, moved, lens,
                                                    bias=bias, **kw)
        with jax.named_scope("attn_latent_absorb"):
            o = o.reshape(S, T, heads, self.rank).astype(lp["w_uv"].dtype)
            return jnp.einsum("sthc,hcv->sthv", o, lp["w_uv"],
                              preferred_element_type=_F32)

    def _expanded(self, lp, qn, qr, pool, li, table, cached, heads):
        """One sequence's suffix q (T, heads, .) at positions ``cached +
        0..T-1`` over ALL the rows its table names (the suffix's own are
        written), expanded, a block of query rows at a time -> (T,
        heads, v)."""
        T, B = qn.shape[0], EXPANDED_Q_BLOCK
        pages, moved = _pages(pool, li, table)
        rows = pages[moved].reshape(-1, pool.shape[-1])     # (P * pg, W)
        k, v = self.expand(lp, rows, heads)
        q = jnp.concatenate([qn, qr], axis=-1).astype(rows.dtype)
        blocks = -(-T // B)
        q = jnp.pad(q, ((0, blocks * B - T), (0, 0), (0, 0)))
        t = jnp.arange(rows.shape[0], dtype=jnp.int32)

        def block(args):
            qb, first = args                                # (B, heads, .)
            s = jnp.einsum("qhd,khd->hqk", qb, k,
                           preferred_element_type=_F32) * self.softmax_scale
            limit = cached + first + jnp.arange(B, dtype=jnp.int32) + 1
            s = jnp.where(t[None, None, :] < limit[None, :, None], s,
                          _NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            return jnp.einsum("hqk,khv->qhv", p, v,
                              preferred_element_type=_F32)

        out = jax.lax.map(block, (q.reshape(blocks, B, heads, -1),
                                  jnp.arange(blocks, dtype=jnp.int32) * B))
        return out.reshape(blocks * B, heads, self.v_dim)[:T]


# -- parameters --------------------------------------------------------------


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, _F32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "nope", "rope_dim", "v_dim", "rank", "dense_width",
    "expert_width", "shared_width", "router_width", "held", "routed",
    "dtype"))
def _init_layer(key, *, d, heads, nope, rope_dim, v_dim, rank, dense_width,
                expert_width, shared_width, router_width, held, routed,
                dtype):
    """One layer's weights, made on the device: one program a kind of
    layer (dense, routed), not one of all the layers."""
    lk = jax.random.split(key, 14)
    wide = QK_ROW_STD * d ** -0.5
    ones = jnp.ones((d,), dtype)
    lp = {"w_in": ones, "w_post": ones, "w_cn": jnp.ones((rank,), dtype),
          "wq": _normal(lk[0], (d, heads * (nope + rope_dim)), wide, dtype),
          "w_kva": jnp.concatenate(
              [_normal(lk[1], (d, rank), 0.02, dtype),
               _normal(lk[2], (d, rope_dim), wide, dtype)], axis=1),
          "w_uk": _normal(lk[3], (heads, nope, rank),
                          QK_ROW_STD * rank ** -0.5, dtype),
          "w_uv": _normal(lk[4], (heads, rank, v_dim), 0.02, dtype),
          "wo": _normal(lk[5], (heads * v_dim, d), 0.02, dtype)}
    if routed:
        f, s = expert_width, shared_width
        lp.update(
            wr=_normal(lk[6], (d, router_width), 0.02, dtype),
            b=_normal(lk[7], (router_width,), 0.02, _F32),
            ws_gate=_normal(lk[8], (d, s), 0.02, dtype),
            ws_up=_normal(lk[9], (d, s), 0.02, dtype),
            ws_down=_normal(lk[10], (s, d), 0.02, dtype),
            w_gate=_normal(lk[11], (held, d, f), 0.02, dtype),
            w_up=_normal(lk[12], (held, d, f), 0.02, dtype),
            w_down=_normal(lk[13], (held, f, d), 0.02, dtype))
    else:
        lp.update(w_gate=_normal(lk[6], (d, dense_width), 0.02, dtype),
                  w_up=_normal(lk[7], (d, dense_width), 0.02, dtype),
                  w_down=_normal(lk[8], (dense_width, d), 0.02, dtype))
    return lp


@functools.partial(jax.jit, static_argnames=("vocab", "d", "dtype"))
def _init_ends(key, *, vocab, d, dtype):
    k0, k1 = jax.random.split(key)
    return {"emb": _normal(k0, (vocab, d), 0.02, dtype),
            "w_f": jnp.ones((d,), dtype),
            "lm_head": _normal(k1, (d, vocab), 0.02, dtype)}


def init_params(key, *, vocab, layers, first_dense, dtype, **sizes):
    """Every weight N(0, 0.02) in ``dtype`` but the three that make q,
    k^n and k^r (``QK_ROW_STD``), every norm scale 1, the router's
    selection bias N(0, 0.02) in float32 (K-EXAONE's reasoning:
    ``models/exaone_moe.py:init_params``)."""
    ks = jax.random.split(key, 1 + layers)
    params = _init_ends(ks[0], vocab=vocab, d=sizes["d"], dtype=dtype)
    params["layers"] = [
        _init_layer(ks[1 + i], routed=i >= first_dense, dtype=dtype, **sizes)
        for i in range(layers)]
    return params


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    return pool.at[:, dst].set(pool[:, src])


class KananaMlaLM(PagedDecoderLM):
    """One chip's share of the model over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/kanana-2-30b-a3b.gen_config.py``).  A page-run model:
    every layer keeps every row in the one run, so it shares prefixes,
    forks and verifies like the plain-heads models."""

    def __init__(self, vocab: int = 16032, d_model: int = 2048,
                 num_heads: int = 32, num_layers: int = 16,
                 first_k_dense_replace: int = 1, qk_nope_head_dim: int = 128,
                 qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                 kv_lora_rank: int = 512, dense_width: int = 6144,
                 expert_width: int = 768, num_shared_experts: int = 2,
                 num_experts_published: int = 128, held_experts=(0, 16),
                 experts_per_tok: int = 6,
                 routed_scaling_factor: float = 2.448,
                 rms_norm_eps: float = 1e-6, rope_theta: float = 1e6,
                 max_len: int = 8192, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 64,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        super().__init__(vocab, d_model, num_heads, num_layers, max_len,
                         page_size, pages_per_seq, bos_id, eos_id)
        self.dh = int(qk_nope_head_dim) + int(qk_rope_head_dim)
        self.block = KananaMlaBlock(
            nope=int(qk_nope_head_dim), rope_dim=int(qk_rope_head_dim),
            v_dim=int(v_head_dim), rank=int(kv_lora_rank),
            eps=float(rms_norm_eps), theta=float(rope_theta),
            top_k=int(experts_per_tok), scale=float(routed_scaling_factor),
            held=tuple(int(x) for x in held_experts))
        dtype = jnp.dtype(dtype)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, layers=self.layers,
            first_dense=int(first_k_dense_replace), dtype=dtype, d=self.d,
            heads=self.heads, nope=self.block.nope,
            rope_dim=self.block.rope_dim, v_dim=self.block.v_dim,
            rank=self.block.rank, dense_width=int(dense_width),
            expert_width=int(expert_width),
            shared_width=int(num_shared_experts) * int(expert_width),
            router_width=int(num_experts_published),
            held=self.block.held[1])
        self._routed = list(range(int(first_k_dense_replace), self.layers))
        self._router_width = int(num_experts_published)
        self._make_pools(num_pages, dtype)

    _observe = ExaoneMoeLM._observe

    def _make_pools(self, num_pages, dtype):
        self.allocator = PageAllocator(num_pages)
        self.k_pool = jnp.zeros((self.layers, num_pages, self.page_size,
                                 self.block.width), dtype)
        self.v_pool = jnp.zeros((self.layers, 1), dtype)     # never read

    @property
    def row_bytes(self) -> int:
        """Bytes one token's row takes in one layer, as stored."""
        return self.block.width * self.k_pool.dtype.itemsize

    def _forward(self, tokens):
        """-> (logits (T, V), the stored rows (L, T, width), None)."""
        x, kept, _ = _dense_blocks(self.block, self.params, tokens,
                                   self.heads, None)
        return self.block.head(self.params, x), jnp.stack(kept), None

    def prefill(self, prompt, pages, cached_len: int = 0):
        out = super().prefill(prompt, pages, cached_len)
        if not cached_len:
            n = len(prompt)
            _M_PREFILL_PAIRS.inc(n * (n + 1) // 2)
        return out

    def cache_rows(self, lens) -> dict:
        return {"latent": int(np.sum(lens)) * self.layers}

    def cache_bytes(self, lens) -> dict:
        return {"latent": int(np.sum(lens)) * self.layers * self.row_bytes}

    def copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page, every layer's (the CoW split)."""
        with self._donating():
            self.k_pool = _copy_page(self.k_pool, np.int32(src),
                                     np.int32(dst))

    def verify_chunk(self, tokens, states, tables, lens):
        if tokens.shape[1] > kernel_rows(self.heads):
            raise UnsupportedOverLatentRows(
                f"a verify chunk of {tokens.shape[1]} rows a slot: the "
                f"absorbed kernel keeps {kernel_rows(self.heads)} rows x "
                f"{self.heads} heads resident, and off the kernel a chunk "
                "gathers every slot's whole page run")
        return super().verify_chunk(tokens, states, tables, lens)
