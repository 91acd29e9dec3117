"""K-EXAONE (LGAI-EXAONE/K-EXAONE-236B-A23B, ``model_type`` exaone_moe)
behind ``/generate``, as ONE chip of an expert-parallel deployment
serves it.

The block: pre-norm residuals (RMSNorm), 64 query heads on 8 K/V heads
of 128, an RMSNorm per head over the 128 channels of q and of k, rotary
positions (rotate-half pairing) on the SLIDING layers only — a full
layer has no positional rotation at all — a window of 128 rows (a row
sees itself and the 127 before it) on the sliding layers; a dense
SwiGLU in layer 0, and from layer 1 on a routed feed-forward: the
DeepSeek-V3 router (sigmoid scores over the published 128 experts, the
8 largest of ``s + b`` chosen, weighed ``2.5 * s / sum of the 8
chosen s``) beside an always-on shared SwiGLU expert; an untied head;
no bias anywhere.

What this chip holds of it (``held``, ``vocab``): a contiguous range of
the routed experts and the first rows of the vocabulary; attention, the
router at its full width and the shared expert whole, as a chip of a
wide expert-parallel group does.  The routed part of a row's sum is the
held experts' part; nothing stands in for the other chips.

Two kinds of cache in the one page pool ``(1, N, pg, Hkv, dh)`` of the
paged skeleton (``decode/model.py``), from the one allocator.  A
sequence's pages are its full run, one page per ``pg`` rows as every
paged model has, followed by ``ring_pages`` pages for EACH sliding
layer: a ring that holds the layer's newest ``ring_pages * pg`` rows
(page ``pi`` of the sequence in ring slot ``pi % ring_pages``), never
more, however long the sequence grows.  Its table row is ``full_pages``
columns of the full run, then each sliding layer's ring.  One full
layer a model HERE: a second would need a slab of its own in a
layer-axis pool, which this model's one pool ``(1, N, ..)`` is not;
``models/mimo_v2.py`` lays that out (its full layers' run over
``(full layers, N, ..)`` pools, its rings an entry a sequence beside
it).

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, scores and rotation; K (rotated
where the layer rotates) and V rows in the pools' dtype.  Random
weights only: loading a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.decode.attention import (
    banded_prefill_attention,
    paged_ring_attention,
)
from paddle_tpu.decode.model import (
    PagedDecoderLM,
    PageRunCache,
    _layer_pages,
    _write_rows,
)
from paddle_tpu.models import moe
from paddle_tpu.models.olmoe import _mm, rms_norm, rope, rope_angles

_F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"


class UnsupportedOverRings(RuntimeError):
    """Asked of a model with window layers on rings what it cannot do
    yet: share a prefix or fork a sequence (both need the rings'
    contents at the fork point copied; ROADMAP R4)."""


def swiglu(m, w_gate, w_up, w_down):
    h = (jax.nn.silu(_mm(m, w_gate)) * _mm(m, w_up)).astype(w_down.dtype)
    return _mm(h, w_down)


@dataclasses.dataclass(frozen=True)
class ExaoneMoeBlock(PageRunCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``PageRunCache`` for the cache side: the full layer's is that one's
    over the table's first columns, a sliding layer's is a ring.
    ``at``: the layer this view of the block is (``layer``)."""

    layer_types: tuple = (SLIDING, FULL)
    kv_heads: int = 8
    head_dim: int = 128
    window: int = 128
    eps: float = 1e-5
    theta: float = 1e6
    top_k: int = 8
    scale: float = 2.5
    held: tuple = (0, 16)
    full_pages: int = 8          # table columns of the full run
    ring_pages: int = 2          # pages of one sliding layer's ring
    at: int = 0

    def layer(self, li):
        return dataclasses.replace(self, at=li)

    @property
    def sliding(self) -> bool:
        return self.layer_types[self.at] == SLIDING

    @property
    def ring_at(self) -> int:
        """First table column of this sliding layer's ring."""
        before = sum(t == SLIDING for t in self.layer_types[:self.at])
        return self.full_pages + before * self.ring_pages

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def qkv(self, lp, x, pos, heads):
        """Pre-norm, the three projections split to heads, RMSNorm per
        head over q and k, and the rotation where this layer rotates."""
        n = rms_norm(x, lp["w_in"], self.eps)
        lead, dh = x.shape[:-1], self.head_dim
        q = rms_norm(_mm(n, lp["wq"]).reshape(lead + (heads, dh)),
                     lp["w_qn"], self.eps)
        k = rms_norm(_mm(n, lp["wk"]).reshape(lead + (self.kv_heads, dh)),
                     lp["w_kn"], self.eps)
        v = _mm(n, lp["wv"]).reshape(lead + (self.kv_heads, dh))
        if self.sliding:
            cos, sin = rope_angles(pos, dh, self.theta)
            q, k = rope(q, cos, sin), rope(k, cos, sin)
        dtype = lp["wq"].dtype
        return q.astype(dtype), k.astype(dtype), v.astype(dtype)

    def attn_out(self, lp, x, a):
        return x + _mm(a, lp["wo"])

    def mlp(self, lp, x, live):
        """The feed-forward after the second pre-norm: layer 0's dense
        SwiGLU, or the shared expert plus the held routed experts.
        Reports (held experts + 1,) int32: the live rows' assignments
        per held expert, then those that went elsewhere (a dense layer:
        zeros)."""
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["w_gate"].dtype)
        m = m.reshape(-1, m.shape[-1])
        if "wr" not in lp:
            y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
            report = jnp.zeros((self.held[1] + 1,), jnp.int32)
        else:
            with jax.named_scope("moe_shared"):
                y = swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
            routed, load, elsewhere = moe.routed_experts(
                m, lp["wr"], lp["w_gate"], lp["w_up"], lp["w_down"],
                top_k=self.top_k,
                live=None if live is None else live.reshape(-1),
                scores=moe.sigmoid_scores(lp["b"], self.scale),
                held=self.held)
            y = y + routed
            report = jnp.concatenate(
                [load, elsewhere.astype(jnp.int32)[None]])
        return x + y.reshape(x.shape), report

    def head(self, params, x):
        return _mm(rms_norm(x, params["w_f"], self.eps), params["lm_head"])

    # -- the cache side -----------------------------------------------------

    def prompt_attention(self, q, k, v):
        if self.sliding:
            with jax.named_scope("attn_window"):
                return banded_prefill_attention(q, k, v, self.window)
        return super().prompt_attention(q, k, v)       # under ``attn_full``

    def store_prompt(self, pool, rows, flat):
        """``rows`` (L, T, Hkv, dh) at the flat pool rows ``flat``
        (L, T): each layer's own (a sliding layer keeps its last ring
        of the prompt; the rest goes to the null page)."""
        _, N, pg, H, dh = pool.shape
        return (pool.reshape(N * pg, H, dh).at[flat.reshape(-1)]
                .set(rows.reshape(-1, H, dh).astype(pool.dtype))
                .reshape(pool.shape))

    def cached_attention(self, k_pool, v_pool, li, q, k, v, flat, tables,
                         lens):
        """``flat`` (the page run's rows, which the skeleton reckons
        for every model) is the full layer's.  A sliding layer writes
        at its ring's rows and reads its ring alone."""
        if not self.sliding:                            # under ``attn_full``
            return super().cached_attention(
                k_pool, v_pool, 0, q, k, v, flat,
                tables[:, :self.full_pages], lens)
        pg, H, dh = k_pool.shape[2:]
        step = q.ndim == 3
        qc = q[:, None] if step else q                       # (S, T, ..)
        T = qc.shape[1]
        pos = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        ring = tables[:, self.ring_at:self.ring_at + self.ring_pages]
        with jax.named_scope("attn_window"):
            rows = (jnp.take_along_axis(
                ring, (pos // pg) % self.ring_pages, axis=1) * pg
                + pos % pg).reshape(-1)
            k_pool = _write_rows(k_pool, 0, rows, k.reshape(-1, H, dh))
            v_pool = _write_rows(v_pool, 0, rows, v.reshape(-1, H, dh))
            # the pool seen as pages is a bitcast (``k_pool[0]`` would
            # be a slab the size of the pool); of these row-major pages
            # the gather is the read
            k_pages, v_pages, _ = _layer_pages(k_pool, v_pool, 0, ring)
            a = paged_ring_attention(qc, k_pages, v_pages, ring, pos,
                                     self.window)
        return (a[:, 0] if step else a), k_pool, v_pool


@functools.partial(jax.jit, static_argnames=(
    "vocab", "d", "heads", "kv_heads", "head_dim", "dense_width",
    "expert_width", "router_width", "held", "moe_layers", "dtype"))
def init_params(key, *, vocab, d, heads, kv_heads, head_dim, dense_width,
                expert_width, router_width, held, moe_layers, dtype):
    """Every weight N(0, 0.02) in ``dtype``, every norm scale 1, the
    router's selection bias N(0, 0.02) in float32: wide enough that
    choosing by ``s + b`` and by ``s`` differ on four rows of five (the
    8th and 9th scores of 128 lie ~0.008 apart), narrow enough that the
    routing stays as even as a deployment's, whose bias exists to even
    it (at N(0, 0.1) the bias outweighs the saturated scores, a third
    of the held experts get no row in a step and which third is the
    seed's); made on the device by this one program.  ``moe_layers``: a
    bool a layer."""
    def normal(k, *shape, std=0.02, dt=dtype):
        return (jax.random.normal(k, shape, _F32) * std).astype(dt)

    ones = jnp.ones((d,), dtype)
    head_ones = jnp.ones((head_dim,), dtype)
    ks = jax.random.split(key, 2 + len(moe_layers))
    params = {"emb": normal(ks[0], vocab, d), "w_f": ones,
              "lm_head": normal(ks[1], d, vocab), "layers": []}
    f, C = expert_width, held
    for i, routed in enumerate(moe_layers):
        lk = jax.random.split(ks[2 + i], 12)
        lp = {"w_in": ones, "w_post": ones,
              "w_qn": head_ones, "w_kn": head_ones,
              "wq": normal(lk[0], d, heads * head_dim),
              "wk": normal(lk[1], d, kv_heads * head_dim),
              "wv": normal(lk[2], d, kv_heads * head_dim),
              "wo": normal(lk[3], heads * head_dim, d)}
        if routed:
            lp.update(
                wr=normal(lk[4], d, router_width),
                b=normal(lk[5], router_width, std=0.02, dt=_F32),
                ws_gate=normal(lk[6], d, f), ws_up=normal(lk[7], d, f),
                ws_down=normal(lk[8], f, d),
                w_gate=normal(lk[9], C, d, f), w_up=normal(lk[10], C, d, f),
                w_down=normal(lk[11], C, f, d))
        else:
            lp.update(w_gate=normal(lk[4], d, dense_width),
                      w_up=normal(lk[5], d, dense_width),
                      w_down=normal(lk[6], dense_width, d))
        params["layers"].append(lp)
    return params


class ExaoneMoeLM(PagedDecoderLM):
    """K-EXAONE's share of one chip over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/k-exaone-236b-a23b.gen_config.py``).

    The constructor's ``pages_per_seq`` is the full run's pages (the
    rows a sequence may hold over ``page_size``, kept as
    ``full_pages``); the attribute, which the session sizes its table
    rows and its admission by, counts the rings too."""

    supports_prefix_cache = False     # a prefix's rings are not kept
    supports_fork = False             # nor copied for a beam's siblings

    def __init__(self, vocab: int = 19200, d_model: int = 6144,
                 num_heads: int = 64, num_kv_heads: int = 8,
                 head_dim: int = 128,
                 layer_types: Sequence[str] = (SLIDING, FULL),
                 mlp_layer_types: Sequence[str] = ("dense", "sparse"),
                 sliding_window: int = 128, dense_width: int = 18432,
                 expert_width: int = 2048, num_experts_published: int = 128,
                 held_experts=(0, 16), experts_per_tok: int = 8,
                 routed_scaling_factor: float = 2.5,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 1e6,
                 max_len: int = 4608, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 36,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        layer_types = tuple(layer_types)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if len(mlp_layer_types) != len(layer_types):
            raise ValueError("one mlp_layer_types entry a layer")
        if sum(t == FULL for t in layer_types) > 1:
            raise ValueError(
                "one full-attention layer a model: the full layers would "
                "need a page run each, which this model does not lay out")
        if sliding_window % page_size and page_size % sliding_window:
            raise ValueError("sliding_window and page_size: one must "
                             "divide the other")
        self.dh, self.kv_heads = int(head_dim), int(num_kv_heads)
        self.full_pages = self.pages_per_seq
        self.seq_rows = self.full_pages * self.page_size
        # the newest `window` rows are whole in window/pg + 1 pages
        self.ring_pages = -(-int(sliding_window) // self.page_size) + 1
        self.rings = sum(t == SLIDING for t in layer_types)
        self.pages_per_seq = self.full_pages + self.rings * self.ring_pages
        self.block = ExaoneMoeBlock(
            layer_types=layer_types, kv_heads=self.kv_heads,
            head_dim=self.dh, window=int(sliding_window),
            eps=float(rms_norm_eps), theta=float(rope_theta),
            top_k=int(experts_per_tok), scale=float(routed_scaling_factor),
            held=tuple(int(x) for x in held_experts),
            full_pages=self.full_pages, ring_pages=self.ring_pages)
        dtype = jnp.dtype(dtype)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, kv_heads=self.kv_heads, head_dim=self.dh,
            dense_width=int(dense_width), expert_width=int(expert_width),
            router_width=int(num_experts_published),
            held=self.block.held[1],
            moe_layers=tuple(t == "sparse" for t in mlp_layer_types),
            dtype=dtype)
        self._routed = [i for i, t in enumerate(mlp_layer_types)
                        if t == "sparse"]
        self._router_width = int(num_experts_published)
        self._make_pools(num_pages, dtype)

    def _make_pools(self, num_pages, dtype):
        from paddle_tpu.decode.paged_kv import PageAllocator

        self.allocator = PageAllocator(num_pages)
        shape = (1, num_pages, self.page_size, self.kv_heads, self.dh)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype)

    def _observe(self, phase, report, rows):
        report = np.asarray(report)[self._routed]      # (routed, held + 1)
        moe.count_load(phase, report[:, :-1], rows, self.block.top_k,
                       self._router_width, int(report[:, -1].sum()))

    # -- pages: the full run, then a ring a sliding layer --------------------

    def context_pages(self, prompt, max_new_tokens: int) -> int:
        return (super().context_pages(prompt, max_new_tokens)
                + self.rings * self.ring_pages)

    def _split(self, pages):
        n = len(pages) - self.rings * self.ring_pages
        if n < 1:
            raise ValueError(
                f"{len(pages)} pages hold no full run beside "
                f"{self.rings} rings of {self.ring_pages}")
        return list(pages[:n]), list(pages[n:])

    def pool_table(self, pages) -> np.ndarray:
        full, rings = self._split(pages)
        t = np.zeros((self.pages_per_seq,), np.int32)
        t[:len(full)] = full
        t[self.full_pages:] = rings
        return t

    def cache_rows(self, lens) -> dict:
        """Rows resident per kind of cache for sequences of ``lens``
        rows, summed over the layers of the kind: a full layer holds
        every row, a ring its newest ``ring_pages * page_size`` at
        most."""
        lens = np.asarray(lens, np.int64)
        ring = self.ring_pages * self.page_size
        return {"full": int(lens.sum()) * (self.layers - self.rings),
                "window": int(np.minimum(lens, ring).sum()) * self.rings}

    def prefill(self, prompt, pages, cached_len: int = 0):
        if cached_len:
            raise UnsupportedOverRings(
                "a prefill over cached pages needs the rings as they "
                "stood at the cached length; they are not kept")
        return super().prefill(prompt, pages)

    def _prompt_rows(self, pages, bucket: int, n: int) -> np.ndarray:
        """(layers, bucket): the full layer's rows as every paged model
        has them; a sliding layer keeps the last ``ring_pages`` pages of
        the prompt in its ring, and the rows before them (which no
        later row sees) go to the null page with the padding."""
        table = self.pool_table(pages)
        pg, R = self.page_size, self.ring_pages
        rows = np.arange(bucket)
        page_of = rows // pg
        in_run = np.where(page_of < self.full_pages,
                          table[np.minimum(page_of, self.full_pages - 1)], 0)
        last = (n - 1) // pg
        kept = (page_of > last - R) & (page_of <= last)
        flat = np.zeros((self.layers, bucket), np.int32)
        for li, kind in enumerate(self.block.layer_types):
            page = in_run
            if kind == SLIDING:
                at = self.block.layer(li).ring_at
                page = np.where(kept, table[at:at + R][page_of % R], 0)
            flat[li] = page * pg + rows % pg
        return flat

    def copy_page(self, src: int, dst: int) -> None:
        raise UnsupportedOverRings(
            "a copy-on-write split follows a fork, which this model "
            "refuses")

    def verify_chunk(self, tokens, states, tables, lens):
        if tokens.shape[1] > self.page_size:
            raise ValueError(
                f"a chunk of {tokens.shape[1]} rows is more than a page "
                f"({self.page_size}): a ring would lose rows that the "
                "chunk's first row still sees")
        return super().verify_chunk(tokens, states, tables, lens)
