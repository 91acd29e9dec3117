"""GLM-5 (zai-org/GLM-5, ``model_type`` glm_moe_dsa) behind
``/generate``, as ONE chip of an expert-parallel deployment serves it:
latent attention whose every query row reads only the cached rows a
learned INDEXER chooses (the DeepSeek-V3.2 sparse-attention family),
over the paged skeleton, and the DeepSeek-V3 routed feed-forward.

The layer, as it is computed.  Pre-norm residuals, ``h = RMSNorm(x)``,
eps 1e-5:

    c^q_t = RMSNorm_2048(h_t W_qa)
    q_t = c^q_t W_qb -> 64 heads x [q^n (192) ; q^r (64)]
    [c_t ; k^r_t] = h_t W_kva  (512 + 64)    c_t <- RMSNorm_512(c_t)
    q^r, k^r rotated at t, pairs (2i, 2i+1), theta 1e6
    k^r ONE row for all heads
    [k^n_{t,h} ; v_{t,h}] = c_t W_kvb        (512 -> 64 x (192 + 256))

    indexer (every layer its own):
      q^I_{t,j} = c^q_t W^I_q -> 32 heads x 128
      k^I_s     = LayerNorm_128(h_s W^I_k)   ONE row a token: what the
                                             cache keeps
      the first 64 channels of q^I_{t,j} rotated at t, of k^I_s at s
      w_{t,j}   = (h_t W^I_w)_j x 32^-1/2 x 128^-1/2
      I_{t,s}   = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)          s <= t
      S_t       = the min(2048, t+1) rows s <= t of largest I_{t,s}
                  (a tie goes to the lower s)

    p = softmax over s in S_t of (q^n.k^n + q^r.k^r) / sqrt(256)
    x <- x + concat_h(sum_{s in S_t} p v_{s,h}) W_o

Feed-forward: the leading layers a dense SwiGLU, then Kanana's routed
layer to the letter (``ExaoneMoeBlock.mlp``): sigmoid scores over the
published experts, the selection bias for CHOOSING the 8 and not for
weighing them, ``w_e = 2.5 x s_e / sum of the 8 chosen``, one always-on
shared SwiGLU added unweighted; an untied head over the held ids.

What a page holds (the THIRD row format on the pages): beside the latent
row ``[c ; k^r ; zeros]`` of ``models/kanana_mla.py`` (the skeleton's
first pool), the indexer's key row ``k^I``, 128 lanes, one tile, in the
skeleton's SECOND pool ``(layers, pages, page_size, 128)`` under the
same page ids: one allocation seats both, ``copy_page`` copies both, a
prefix hit shares both.  (The latent model's second pool is a
placeholder; a block that declares its pools, ROADMAP D1, is not needed
for two.)

Computed three ways, the same numbers:

- **a decode step** (``mixer``): the step's rows written, then ONE
  walk of every slot's live latent pages, absorbed
  (``latent_paged_attention``): plain, as Kanana runs it, while no slot
  holds more than ``index_topk`` rows; else under the selected sets.
  For those, ``paged_index_scores`` over each slot's live index pages
  (``attn_index``), then ``selection_bias`` over the slots' scores, a
  row of the kernel a slot (``attn_index_select``: the set is exact, a
  tie to the lower row; ``selection_mask`` where the slots are no row
  block), and the walk with that bias added to each page's scores
  (``attn_sparse``): nothing is sorted and no row is fetched by id.  A
  slot with fewer rows than ``index_topk`` selects all of them: the
  dense numbers.
- **a prefill bucket** (``prompt_mixer``): up to ``index_topk`` rows
  plain causal through the flash kernel, expanded, as Kanana's; above,
  ``I`` for every (query row, key row) pair (``index_scores``), then
  ``select`` hands back ``S_t`` as the BIAS the attention runs under (0
  on a member, a large negative number elsewhere): each row's
  ``index_topk``-th largest score found by bisection over the scores'
  bits and its ties cut by row inside ``selection_bias``, a block of
  query rows over the whole key width in VMEM, the score matrix read
  once (``selection_mask`` below, the same set by 34 passes over the
  matrix in XLA, is its oracle and the path off a TPU and for shapes
  that do not tile); and expanded attention under that bias
  (``selected_flash_attention``: a second entry point, the causal flash
  kernel is not touched).
- **a chunk over cached rows** (``mixer(lone=True)``; a prompt longer
  than the top bucket is its first bucket and then consecutive chunks,
  inside one admission; a prefix hit's suffix is chunks too): the
  chunk's rows written, the sequence's latent and index rows up to the
  chunk's ``extent`` gathered through its table, then as a bucket.

Refused by name: a verify chunk (``UnsupportedOverSelectedRows``: rows
of several slots each selecting among cached rows is not laid out).

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, scores and rotation; latent and
index rows in the pools' dtype.  Random weights only: loading a
checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.bucket import bucket_dim
from paddle_tpu.decode.attention import dense_prefill_attention
from paddle_tpu.decode.model import (Addressing, PagedDecoderLM,
                                    _copy_pools_page, _dense_blocks,
                                    _M_PREFILL_PADDED, _M_PREFILL_TOKENS,
                                    _stack_reports)
from paddle_tpu.decode.paged_kv import PageAllocator
from paddle_tpu.models import kanana_mla as km
from paddle_tpu.models.kanana_mla import (KananaMlaBlock, KananaMlaLM,
                                         _normal, _pages, _write,
                                         rope_interleaved)
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import phase
from paddle_tpu.pallas import sparse_latent as sl

_F32 = jnp.float32
_NEG_INF = -1e30
_INT_MIN = -2 ** 31

# Std of an entry of q, k^n, k^r and of q^I under unit-RMS inputs
# (``kanana_mla.QK_ROW_STD``'s reasoning: attention scores with std ~1,
# so that what the selection leaves out shows in the logits), and of a
# head's index weight before its two constants (so that ``I`` has std
# ~1: 32 heads of ReLU(q^I . k^I), each ~N(0, 128) before the ReLU)
QK_ROW_STD = 1.0
INDEX_WEIGHT_STD = 1.3

_M_SCORED = _metrics.counter(
    "attn_index_rows_scored_total",
    "cached rows the indexer scored in the decode steps that selected "
    "(a slot's rows, its own included, summed over slots and steps): one "
    "layer's; every layer scores as many")
_M_SELECTED = _metrics.counter(
    "attn_index_rows_selected_total",
    "cached rows the decode steps that selected read of the latent pool "
    "(min(rows, index_topk) a slot a step): one layer's")
_M_PREFILL_PAIRS = _metrics.counter(
    "attn_index_prefill_pairs_total",
    "(query row, key row at or before it) pairs the indexer scored in "
    "the prefill programs that select (a bucket over index_topk rows, a "
    "chunk over cached rows), real rows alone: one layer's")


class UnsupportedOverSelectedRows(RuntimeError):
    """Asked of the sparse latent model what its programs do not lay
    out: a chunk of rows for SEVERAL sequences at once (a speculative
    verify), each row selecting among its sequence's cached rows."""


def sortable(x):
    """float32 -> int32 with the floats' order (-0.0 under 0.0)."""
    b = jax.lax.bitcast_convert_type(x.astype(_F32), jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7fffffff), b)


def kth_largest(keys, k: int):
    """The largest ``v`` with ``count(keys >= v) >= k`` along the last
    axis, or the least int32 where a row has fewer than ``k`` entries:
    the ``k``-th largest key, by bisection over the 32 bits (32 passes
    of a compare and a count, no sort)."""
    lo = jnp.full(keys.shape[:-1], _INT_MIN, jnp.int32)
    hi = jnp.full(keys.shape[:-1], 2 ** 31 - 1, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)        # the ceiling
        ok = jnp.sum(keys >= mid[..., None], axis=-1) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    return jax.lax.fori_loop(0, 32, halve, (lo, hi))[0]


def selection_mask(scores, seen, k: int):
    """(T, n) bool: of each row's ``seen`` entries the ``min(k, seen)``
    of largest score, a tie at the edge to the lower index: ``S_t``,
    exactly."""
    keys = jnp.where(seen, sortable(scores), _INT_MIN)
    edge = kth_largest(keys, k)[:, None]
    above, tied = keys > edge, keys == edge
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(tied.astype(jnp.int32), axis=-1) <= room
    return (above | (tied & first)) & seen


def _bias(sel, dtype):
    """A selected set as the bias attention runs under: 0 on a member."""
    return jnp.where(sel, 0.0, _NEG_INF).astype(dtype)


def _store(pool, rows, where):
    """``pool`` (L, N, pg, W) with a prompt's ``rows`` (L, T, W) at the
    page run's flat rows ``where`` (T,): one scatter into the pool seen
    flat (``KananaMlaBlock.store_prompts``' form)."""
    L, N, pg, W = pool.shape
    flat = (jnp.arange(L, dtype=jnp.int32)[:, None] * (N * pg)
            + where[None, :]).reshape(-1)
    return (pool.reshape(L * N * pg, W).at[flat]
            .set(rows.astype(pool.dtype).reshape(-1, W)).reshape(pool.shape))


@dataclasses.dataclass(frozen=True)
class GlmDsaBlock(KananaMlaBlock):
    """``KananaMlaBlock`` with the compressed query, the indexer and the
    selection: both mixers and ``store_prompts`` over the latent pool
    and the index pool."""

    nope: int = 192
    v_dim: int = 256
    eps: float = 1e-5
    top_k: int = 8
    scale: float = 2.5
    index_heads: int = 32
    index_dim: int = 128
    index_rope: int = 64
    index_topk: int = 2048

    # -- the pieces ---------------------------------------------------------

    def queries(self, lp, n, pos, heads):
        """-> (q^n (..., heads, nope), q^r rotated, c^q (..., q_rank)),
        float32."""
        cq = rms_norm(_mm(n, lp["w_qa"]), lp["w_qn"], self.eps)
        q = _mm(cq, lp["w_qb"]).reshape(
            n.shape[:-1] + (heads, self.nope + self.rope_dim))
        return (q[..., :self.nope],
                rope_interleaved(q[..., self.nope:], pos, self.theta), cq)

    def _index_rotated(self, x, pos):
        """``x`` (..., heads, index_dim) with its first ``index_rope``
        channels rotated at ``pos``."""
        r = self.index_rope
        return jnp.concatenate(
            [rope_interleaved(x[..., :r], pos, self.theta),
             x[..., r:].astype(_F32)], axis=-1)

    def index_query(self, lp, cq, n, pos):
        """-> (q^I (..., index_heads, index_dim) rotated, w (...,
        index_heads)), float32."""
        J, D = self.index_heads, self.index_dim
        with jax.named_scope("attn_index"):
            q = _mm(cq, lp["wi_q"]).reshape(cq.shape[:-1] + (J, D))
            w = _mm(n, lp["wi_w"]) * (float(J) ** -0.5 * float(D) ** -0.5)
            return self._index_rotated(q, pos), w

    def index_key(self, lp, n, pos):
        """What a page keeps for the indexer of the rows ``n``: (...,
        index_dim) in the weights' dtype, LayerNorm (with bias) then the
        rotation."""
        with jax.named_scope("attn_index"):
            k = _mm(n, lp["wi_k"])
            k = k - jnp.mean(k, axis=-1, keepdims=True)
            k = k * jax.lax.rsqrt(
                jnp.mean(jnp.square(k), axis=-1, keepdims=True) + self.eps)
            k = k * lp["wi_kn"].astype(_F32) + lp["wi_kb"].astype(_F32)
            return self._index_rotated(k[..., None, :], pos)[
                ..., 0, :].astype(lp["wi_k"].dtype)

    def select(self, q_i, w_i, keys, first):
        """(T, n) in the keys' dtype, ``S_t`` of every query row as the
        bias its attention runs under (0 where key ``s`` is in ``S_t``,
        a large negative number elsewhere): the rows at positions
        ``first + 0..T-1`` over the key rows ``keys`` (n, index_dim) at
        positions ``0..n-1``."""
        from paddle_tpu import pallas as pk

        T, n = q_i.shape[0], keys.shape[0]
        seen = (jnp.arange(n, dtype=jnp.int32)[None, :]
                <= first + jnp.arange(T, dtype=jnp.int32)[:, None])
        if n <= self.index_topk:
            return _bias(seen, keys.dtype)
        limit = jnp.reshape(first, (1,))
        with jax.named_scope("attn_index"):
            q = jnp.moveaxis(q_i, 1, 0)                     # (J, T, D)
            if pk.use_index_scores(T, n, self.index_heads, self.index_dim):
                scores = sl.index_scores(q, w_i, keys, limit,
                                         interpret=pk.interpret_mode())
            else:
                scores = sl.index_scores_reference(q, w_i, keys)
        with jax.named_scope("attn_index_select"):
            if pk.use_selection_bias(T, n, keys.dtype):
                return sl.selection_bias(
                    scores, limit, k=self.index_topk, dtype=keys.dtype,
                    interpret=pk.interpret_mode())
            return _bias(selection_mask(scores, seen, self.index_topk),
                         keys.dtype)

    def attend(self, lp, qn, qr, rows, bias, first, heads):
        """Expanded attention of the query rows (T, heads, .) on the
        latent rows ``rows`` (n, width) under ``select``'s ``bias`` (T,
        n) -> (T, heads, v)."""
        from paddle_tpu import pallas as pk

        T, n = bias.shape
        dtype, qk = rows.dtype, self.nope + self.rope_dim
        with jax.named_scope("attn_sparse"):
            with jax.named_scope("attn_latent_expand"):
                c = rows[:, :self.rank]
                kn = jnp.einsum("tc,hnc->htn", c, lp["w_uk"],
                                preferred_element_type=_F32)
                v = jnp.einsum("tc,hcv->htv", c, lp["w_uv"],
                               preferred_element_type=_F32).astype(dtype)
                kr = jnp.broadcast_to(
                    rows[None, :, self.rank:self.rank + self.rope_dim],
                    (heads, n, self.rope_dim))
                k = jnp.concatenate([kn.astype(dtype), kr], axis=-1)
            q = jnp.moveaxis(jnp.concatenate([qn, qr], axis=-1), 1,
                             0).astype(dtype)               # (H, T, qk)
            if pk.use_selected_flash_attention(heads, T, n, qk):
                # the kernel has one head size: the values ride in the
                # keys' with zero lanes behind them
                v = jnp.pad(v, ((0, 0), (0, 0), (0, qk - self.v_dim)))
                a = sl.selected_flash_attention(
                    q, k, v, bias, jnp.reshape(first, (1,)),
                    scale=self.softmax_scale,
                    interpret=pk.interpret_mode())[..., :self.v_dim]
            else:
                a = sl.selected_attention_reference(q, k, v, bias,
                                                    self.softmax_scale)
            return jnp.moveaxis(a, 0, 1)

    def _causal(self, lp, qn, qr, rows, heads):
        """A bucket of up to ``index_topk`` rows: every row reads all
        before it, expanded through the flash kernel (Kanana's
        ``prompt_mixer``)."""
        k, v = self.expand(lp, rows, heads)
        q = jnp.concatenate([qn, qr], axis=-1).astype(rows.dtype)
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k.shape[-1] - self.v_dim)))
        return dense_prefill_attention(q, k, v, causal=True)[
            ..., :self.v_dim]

    # -- the cache side -----------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        """One whole prompt from its first row -> (rows after the
        residual, (the latent rows (T, width), the index rows (T,
        index_dim)))."""
        T = x.shape[0]
        with jax.named_scope("attn_latent"):
            n = rms_norm(x, lp["w_in"], self.eps)
            qn, qr, cq = self.queries(lp, n, pos, heads)
            rows, keys = self.down(lp, n, pos), self.index_key(lp, n, pos)
            if T <= self.index_topk:
                a = self._causal(lp, qn, qr, rows, heads)
            else:
                first = jnp.int32(0)
                bias = self.select(*self.index_query(lp, cq, n, pos), keys,
                                   first)
                a = self.attend(lp, qn, qr, rows, bias, first, heads)
            return self.attn_out(lp, x, a.reshape(T, -1)), (rows, keys)

    def store_prompts(self, cache, kept, where):
        pool, index_pool = cache
        return (_store(pool, jnp.stack([r for r, _ in kept]), where),
                _store(index_pool, jnp.stack([k for _, k in kept]), where))

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        """A step's rows (S, d), or (``lone``) one sequence's chunk (T,
        d) over the rows its table names: the new latent and index rows
        written, then attention on the selected rows."""
        if x.ndim == 3:
            raise UnsupportedOverSelectedRows(
                f"a chunk of {x.shape[1]} rows for each of {x.shape[0]} "
                "sequences: rows of several sequences each selecting "
                "among its cached rows are not laid out")
        pool, index_pool = cache
        with jax.named_scope("attn_latent"):
            n = rms_norm(x, lp["w_in"], self.eps)
            qn, qr, cq = self.queries(lp, n, pos, heads)
            pool = _write(pool, li, addr.flat, self.down(lp, n, pos))
            index_pool = _write(index_pool, li, addr.flat,
                                self.index_key(lp, n, pos))
            q_i, w_i = self.index_query(lp, cq, n, pos)
            if lone:
                a = self._chunk(lp, qn, qr, q_i, w_i, pool, index_pool, li,
                                addr, heads)
            else:
                a = self._step(lp, qn, qr, q_i, w_i, pool, index_pool, li,
                               addr, heads)
            out = self.attn_out(lp, x, a.reshape(x.shape[0], -1))
        return out, (pool, index_pool)

    def _chunk(self, lp, qn, qr, q_i, w_i, pool, index_pool, li, addr,
               heads):
        """One sequence's chunk at positions ``addr.lens[0] + 0..T-1``
        over the rows of the table columns it was handed (the chunk's
        extent; its own rows are written)."""
        first = addr.lens[0]
        pages, moved = _pages(pool, li, addr.tables)
        rows = pages[moved].reshape(-1, pool.shape[-1])
        pages, moved = _pages(index_pool, li, addr.tables)
        keys = pages[moved].reshape(-1, index_pool.shape[-1])
        bias = self.select(q_i, w_i, keys, first)
        return self.attend(lp, qn, qr, rows, bias, first, heads)

    def _step(self, lp, qn, qr, q_i, w_i, pool, index_pool, li, addr,
              heads):
        """(S, heads, v): every slot's one row over its cached rows, ONE
        walk of the slot's live latent pages either way: plain while no
        slot holds ``index_topk`` rows, else under the selected sets as
        a bias a (slot, cached row) pair.  The walk reads every live row
        to attend on ``index_topk`` of them: at the pages' stream rate
        that beats fetching the chosen rows one by one (16 ns a row)
        while a slot holds under ~35,000 rows; past that a selection by
        blocks of rows, whose pages the walk could skip, takes over
        (ROADMAP R12 b)."""
        S, k = qn.shape[0], self.index_topk
        pg = pool.shape[2]
        n = addr.tables.shape[1] * pg
        qn, qr = qn[:, None], qr[:, None]                   # (S, 1, ..)

        def walk(bias):
            return self._absorbed(lp, qn, qr, pool, li, addr.tables,
                                  addr.lens, heads, bias=bias)

        if n <= k:                             # no sequence can hold more
            return walk(None)

        def sparse(_):
            from paddle_tpu import pallas as pk

            with jax.named_scope("attn_index"):
                pages, moved = _pages(index_pool, li, addr.tables)
                args = (q_i, w_i, pages, moved, addr.lens + 1)
                if pk.use_paged_index_scores(pages.dtype, pg,
                                             self.index_heads,
                                             self.index_dim):
                    scores = sl.paged_index_scores(
                        *args, interpret=pk.interpret_mode())
                else:
                    scores = sl.paged_index_scores_reference(*args)
            with jax.named_scope("attn_index_select"):
                if pk.use_selection_bias(S, n, _F32):
                    # a row of the kernel is a slot, and each sees the
                    # columns up to the longest slot's last: the scores
                    # past a slot's own rows are -inf, under every real
                    # one, and what of them a slot short of k rows keeps
                    # the walk masks by ``lens``
                    bias = sl.selection_bias(
                        scores, jnp.max(addr.lens).reshape(1), k=k,
                        dtype=_F32, interpret=pk.interpret_mode())
                else:
                    seen = (jnp.arange(n, dtype=jnp.int32)[None, :]
                            <= addr.lens[:, None])
                    bias = _bias(selection_mask(scores, seen, k), _F32)
            with jax.named_scope("attn_sparse"):
                return walk(bias)

        return jax.lax.cond(jnp.max(addr.lens) < k, lambda _: walk(None),
                            sparse, None)


# -- parameters --------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "d", "heads", "nope", "rope_dim", "v_dim", "rank", "q_rank",
    "index_heads", "index_dim", "dense_width", "expert_width",
    "shared_width", "router_width", "held", "routed", "dtype"))
def _init_layer(key, *, d, heads, nope, rope_dim, v_dim, rank, q_rank,
                index_heads, index_dim, dense_width, expert_width,
                shared_width, router_width, held, routed, dtype):
    """One layer's weights, made on the device: one program a kind of
    layer (dense, routed)."""
    lk = jax.random.split(key, 20)
    ones = jnp.ones((d,), dtype)
    lp = {"w_in": ones, "w_post": ones, "w_cn": jnp.ones((rank,), dtype),
          "w_qn": jnp.ones((q_rank,), dtype),
          "w_qa": _normal(lk[0], (d, q_rank), 0.02, dtype),
          "w_qb": _normal(lk[1], (q_rank, heads * (nope + rope_dim)),
                          QK_ROW_STD * q_rank ** -0.5, dtype),
          "w_kva": jnp.concatenate(
              [_normal(lk[2], (d, rank), 0.02, dtype),
               _normal(lk[3], (d, rope_dim), QK_ROW_STD * d ** -0.5,
                       dtype)], axis=1),
          "w_uk": _normal(lk[4], (heads, nope, rank),
                          QK_ROW_STD * rank ** -0.5, dtype),
          "w_uv": _normal(lk[5], (heads, rank, v_dim), 0.02, dtype),
          "wo": _normal(lk[6], (heads * v_dim, d), 0.02, dtype),
          "wi_q": _normal(lk[7], (q_rank, index_heads * index_dim),
                          QK_ROW_STD * q_rank ** -0.5, dtype),
          "wi_k": _normal(lk[8], (d, index_dim), 0.02, dtype),
          "wi_kn": (1.0 + 0.5 * jax.random.normal(
              lk[9], (index_dim,), _F32)).astype(dtype),
          "wi_kb": _normal(lk[10], (index_dim,), 0.5, dtype),
          "wi_w": _normal(lk[11], (d, index_heads),
                          INDEX_WEIGHT_STD * d ** -0.5, dtype)}
    if routed:
        f, s = expert_width, shared_width
        lp.update(
            wr=_normal(lk[12], (d, router_width), 0.02, dtype),
            b=_normal(lk[13], (router_width,), 0.02, _F32),
            ws_gate=_normal(lk[14], (d, s), 0.02, dtype),
            ws_up=_normal(lk[15], (d, s), 0.02, dtype),
            ws_down=_normal(lk[16], (s, d), 0.02, dtype),
            w_gate=_normal(lk[17], (held, d, f), 0.02, dtype),
            w_up=_normal(lk[18], (held, d, f), 0.02, dtype),
            w_down=_normal(lk[19], (held, f, d), 0.02, dtype))
    else:
        lp.update(w_gate=_normal(lk[12], (d, dense_width), 0.02, dtype),
                  w_up=_normal(lk[13], (d, dense_width), 0.02, dtype),
                  w_down=_normal(lk[14], (dense_width, d), 0.02, dtype))
    return lp


def init_params(key, *, vocab, layers, first_dense, dtype, **sizes):
    """Every weight N(0, 0.02) in ``dtype`` but those that make q, k^n,
    k^r and q^I (``QK_ROW_STD``) and the index weights
    (``INDEX_WEIGHT_STD``); every RMSNorm scale 1; the index key's
    LayerNorm scale N(1, 0.5) and bias N(0, 0.5) (at scale 1 and bias 0
    dropping the norm would scale every score alike and change no
    selected set); the router's selection bias N(0, 0.02) in float32."""
    ks = jax.random.split(key, 1 + layers)
    params = km._init_ends(ks[0], vocab=vocab, d=sizes["d"], dtype=dtype)
    params["layers"] = [
        _init_layer(ks[1 + i], routed=i >= first_dense, dtype=dtype, **sizes)
        for i in range(layers)]
    return params


@functools.partial(jax.jit, static_argnames=("heads", "page_size", "block",
                                             "extent"),
                   donate_argnums=(1, 2))
def _prefill_bucket_chunk(params, k_pool, v_pool, table, cached_len, tokens,
                          n, *, heads, page_size, block, extent):
    """A chunk of a prompt over the rows cached so far: ``tokens`` (C,)
    at positions ``cached_len + 0..C-1``, the first ``n`` of them real,
    attending on the rows of the table's first ``extent`` columns (the
    cached rows and the chunk's own, which it writes) -> the logits of
    row ``n - 1``, both pools, the layers' reports.  Its shape depends
    on (C, extent) alone, not on where the chunk lies.  Padding rows
    are not ``live``; those past the sequence's table go to the null
    page."""
    C, P = tokens.shape[0], table.shape[0]
    pos = cached_len + jnp.arange(C, dtype=jnp.int32)
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, pos)
    flat = jnp.where(
        pos < P * page_size,
        table[jnp.minimum(pos // page_size, P - 1)] * page_size, 0) \
        + pos % page_size
    live = jnp.arange(C, dtype=jnp.int32) < n
    cache = (k_pool, v_pool)
    addr = Addressing(flat, table[:extent], jnp.reshape(cached_len, (1,)))
    reports = []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, cache = lb.mixer(lp, x, pos, cache, li, addr, heads,
                                lone=True)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, live)
        reports.append(report)
    with jax.named_scope("blk_head"):
        logits = block.head(
            params, jax.lax.dynamic_slice_in_dim(x, n - 1, 1))[0]
    return (logits, *cache, _stack_reports(reports))


class GlmDsaLM(KananaMlaLM):
    """One chip's share of the model over the paged skeleton: what
    ``make_decode_model()`` returns (``perf/configs/glm-5.gen_config.py``).
    A page-run model: every layer keeps every token's latent row and
    index row in the one run, so it shares prefixes and forks as the
    plain-heads models do; a prompt longer than ``prefill_rows`` is
    prefilled as that bucket and then chunks of ``chunk_rows``."""

    def __init__(self, vocab: int = 19360, d_model: int = 6144,
                 num_heads: int = 64, num_layers: int = 5,
                 first_k_dense_replace: int = 1, q_lora_rank: int = 2048,
                 kv_lora_rank: int = 512, qk_nope_head_dim: int = 192,
                 qk_rope_head_dim: int = 64, v_head_dim: int = 256,
                 index_n_heads: int = 32, index_head_dim: int = 128,
                 index_rope_dim: int = 64, index_topk: int = 2048,
                 dense_width: int = 12288, expert_width: int = 2048,
                 num_shared_experts: int = 1,
                 num_experts_published: int = 256, held_experts=(0, 16),
                 experts_per_tok: int = 8,
                 routed_scaling_factor: float = 2.5,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 max_len: int = 25600, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 200,
                 prefill_rows: int = 8192, chunk_rows: int = 4096,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        PagedDecoderLM.__init__(self, vocab, d_model, num_heads, num_layers,
                                max_len, page_size, pages_per_seq, bos_id,
                                eos_id)
        if prefill_rows % page_size or chunk_rows % page_size:
            raise ValueError(
                "prefill_rows and chunk_rows are whole pages of "
                f"{page_size} rows: a chunk starts on a page")
        self.dh = int(qk_nope_head_dim) + int(qk_rope_head_dim)
        self.prefill_rows, self.chunk_rows = int(prefill_rows), int(chunk_rows)
        self.block = GlmDsaBlock(
            nope=int(qk_nope_head_dim), rope_dim=int(qk_rope_head_dim),
            v_dim=int(v_head_dim), rank=int(kv_lora_rank),
            eps=float(rms_norm_eps), theta=float(rope_theta),
            top_k=int(experts_per_tok), scale=float(routed_scaling_factor),
            held=tuple(int(x) for x in held_experts),
            index_heads=int(index_n_heads), index_dim=int(index_head_dim),
            index_rope=int(index_rope_dim), index_topk=int(index_topk))
        dtype = jnp.dtype(dtype)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, layers=self.layers,
            first_dense=int(first_k_dense_replace), dtype=dtype, d=self.d,
            heads=self.heads, nope=self.block.nope,
            rope_dim=self.block.rope_dim, v_dim=self.block.v_dim,
            rank=self.block.rank, q_rank=int(q_lora_rank),
            index_heads=self.block.index_heads,
            index_dim=self.block.index_dim, dense_width=int(dense_width),
            expert_width=int(expert_width),
            shared_width=int(num_shared_experts) * int(expert_width),
            router_width=int(num_experts_published),
            held=self.block.held[1])
        self._routed = list(range(int(first_k_dense_replace), self.layers))
        self._router_width = int(num_experts_published)
        self._make_pools(num_pages, dtype)

    def _make_pools(self, num_pages, dtype):
        self.allocator = PageAllocator(num_pages)
        shape = (self.layers, num_pages, self.page_size)
        self.k_pool = jnp.zeros(shape + (self.block.width,), dtype)
        self.v_pool = jnp.zeros(shape + (self.block.index_dim,), dtype)

    @property
    def index_row_bytes(self) -> int:
        """Bytes one token's index row takes in one layer."""
        return self.block.index_dim * self.v_pool.dtype.itemsize

    def _forward(self, tokens):
        """-> (logits (T, V), the latent rows (L, T, width), the index
        rows (L, T, index_dim))."""
        x, kept, _ = _dense_blocks(self.block, self.params, tokens,
                                   self.heads, None)
        return (self.block.head(self.params, x),
                jnp.stack([r for r, _ in kept]),
                jnp.stack([k for _, k in kept]))

    # -- prefill: a bucket, then chunks over what is cached ------------------

    @property
    def prefill_cap(self) -> int:
        return min(super().prefill_cap, self.prefill_rows)

    def _chunk_of(self, rest: int) -> int:
        """Rows the program of a chunk with ``rest`` rows to go computes."""
        return min(self.chunk_rows,
                   max(bucket_dim(rest), 64, self.page_size))

    def prefill_bucket(self, n: int) -> int:
        """Rows the prefill of an ``n``-token prompt computes: its
        bucket, or the top bucket and the chunks that follow it."""
        cap = self.prefill_cap
        if n <= cap:
            return super().prefill_bucket(n)
        if n > min(self.max_len, self.seq_rows):
            raise ValueError(
                f"a prompt of {n} tokens is outside 1.."
                f"{min(self.max_len, self.seq_rows)}, the rows one "
                "sequence of this model can hold")
        whole, rest = divmod(n - cap, self.chunk_rows)
        return cap + whole * self.chunk_rows + (
            self._chunk_of(rest) if rest else 0)

    def _prefill_whole(self, prompt, pages):
        """A prompt from its first row through its bucket's program."""
        out = PagedDecoderLM.prefill(self, prompt, pages)
        n = len(prompt)
        if self.prefill_bucket(n) > self.block.index_topk:   # it selected
            _M_PREFILL_PAIRS.inc(n * (n + 1) // 2)
        return out

    def prefill(self, prompt, pages, cached_len: int = 0):
        """As ``PagedDecoderLM.prefill``.  A prompt of more rows than the
        top bucket runs as that bucket and then consecutive chunks over
        the rows cached so far, one after another inside this call; a
        prefix hit's suffix (``cached_len``) is chunks from the start."""
        T, cap, k = len(prompt), self.prefill_cap, self.block.index_topk
        if not cached_len and T <= cap:
            return self._prefill_whole(prompt, pages)
        if cached_len and not (0 < cached_len < T
                               and cached_len % self.page_size == 0):
            raise ValueError(
                f"cached_len {cached_len} must be a positive multiple of "
                f"page_size strictly inside the {T}-token prompt")
        self.prefill_bucket(T)                    # refuses what is too long
        done = cached_len
        if not done:
            self._prefill_whole(prompt[:cap], pages)
            done = cap
        table = jnp.asarray(self.pool_table(pages))
        while done < T:
            C = self._chunk_of(T - done)
            real = min(C, T - done)
            extent = min(self.seq_rows, -(-(done + C) // cap) * cap)
            toks = np.zeros((C,), np.int32)
            toks[:real] = prompt[done:done + real]
            with self._donating():
                logits, k_pool, v_pool, report = _prefill_bucket_chunk(
                    self.params, self.k_pool, self.v_pool, table,
                    np.int32(done), toks, np.int32(real), heads=self.heads,
                    page_size=self.page_size, block=self.block,
                    extent=extent // self.page_size)
                self._set_cache(k_pool, v_pool)
                if done + real == T:
                    with phase("decode.prefill_wait"):
                        logits = np.asarray(logits)
                self._observe("prefill", report, C)
            if extent > k:
                _M_PREFILL_PAIRS.inc(real * done + real * (real + 1) // 2)
            if not cached_len:
                _M_PREFILL_TOKENS.inc(real)
                _M_PREFILL_PADDED.inc(C)
            done += real
        return T, [], logits

    # -- a step, and what it counted ----------------------------------------

    def step_dispatch(self, tokens, states, tables, lens):
        step = super().step_dispatch(tokens, states, tables, lens)
        if step.next is not None:
            step.next["lens"].copy_to_host_async()   # for the counters
        return step

    def step_collect(self, step):
        out = super().step_collect(step)
        k = self.block.index_topk
        if step.next is not None and self.seq_rows > k:
            rows = np.asarray(step.next["lens"])     # a live slot's, own in
            if rows.max(initial=0) > k:              # the step selected
                _M_SCORED.inc(int(rows.sum()))
                _M_SELECTED.inc(int(np.minimum(rows, k).sum()))
        return out

    def cache_rows(self, lens) -> dict:
        rows = int(np.sum(lens)) * self.layers
        return {"latent": rows, "index": rows}

    def cache_bytes(self, lens) -> dict:
        rows = int(np.sum(lens)) * self.layers
        return {"latent": rows * self.row_bytes,
                "index": rows * self.index_row_bytes}

    def copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page, every layer's latent AND index rows
        (the CoW split)."""
        with self._donating():
            self.k_pool, self.v_pool = _copy_pools_page(
                self.k_pool, self.v_pool, np.int32(src), np.int32(dst))

    def verify_chunk(self, tokens, states, tables, lens):
        raise UnsupportedOverSelectedRows(
            f"a verify chunk of {tokens.shape[1]} rows a slot: rows of "
            "several sequences each selecting among its cached rows are "
            "not laid out")


def chosen_sets(model, tokens):
    """((layers, T, T) bool, (routed layers, T, experts) bool): ``S_t``
    and the router's chosen experts as the SYSTEM's own block functions
    choose them for each row of one sequence over the dense forward, in
    the weights' precision (a probe for tests and the benchmark; the
    programs hand out counts, not sets)."""
    from paddle_tpu.models import moe

    block = model.block

    @jax.jit
    def run(params, toks):
        T = toks.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)
        x = block.embed(params, toks, pos)
        sets, routed = [], []
        for lp in params["layers"]:
            n = rms_norm(x, lp["w_in"], block.eps)
            _, _, cq = block.queries(lp, n, pos, model.heads)
            sets.append(block.select(
                *block.index_query(lp, cq, n, pos),
                block.index_key(lp, n, pos), jnp.int32(0)) == 0)
            x, _ = block.prompt_mixer(lp, x, pos, model.heads, None)
            if "wr" in lp:
                m = rms_norm(x, lp["w_post"], block.eps).astype(
                    lp["wr"].dtype)
                _, idx = moe.route(m, lp["wr"], block.top_k,
                                   moe.sigmoid_scores(lp["b"], block.scale))
                routed.append(jnp.any(
                    idx[..., None] == jnp.arange(lp["wr"].shape[1]), axis=1))
            x, _ = block.mlp(lp, x, None)
        return jnp.stack(sets), jnp.stack(routed)

    sets, routed = run(model.params, jnp.asarray(tokens, jnp.int32))
    return np.asarray(sets), np.asarray(routed)
