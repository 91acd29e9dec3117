"""MiMo-V2.5 (XiaomiMiMo/MiMo-V2.5, ``model_type`` mimo_v2: the
MiMo-V2-Flash language model) behind ``/generate``, as ONE chip of a
16-way expert-parallel group serves it.

The block (pre-norm residuals, RMSNorm, no bias anywhere, an untied
head).  Attention, both kinds of layer: 64 query heads, **q and k heads
of 192, v heads of 128**; rotate-half RoPE on the FIRST 64 channels of
each q and k head, the other 128 not rotated; scores ``q.k * 192^-1/2``;
the values multiplied by 0.707 (``attention_value_scale``) where they
are made.

- a **full** layer: 4 K/V heads (16 query heads a K/V head), theta 1e7,
  causal, no sink;
- a **window** layer: 8 K/V heads (8 query heads a K/V head), theta 1e4,
  a row sees itself and the 127 before it, and a learned scalar a query
  head joins the softmax's denominator and carries no value
  (``decode/attention.py:_softmax``).

Feed-forward: layer 0 a dense SwiGLU; from layer 1 on 256 experts under
the DeepSeek-V3 router (sigmoid scores, the 8 largest of ``s + b``
chosen, weighed ``s / sum of the 8 chosen s``), NO shared expert.  What
this chip holds of it (``held``, ``vocab``): a contiguous range of the
experts and the first rows of the vocabulary; attention and the router
at full width.  The routed part of a row's sum is the held experts'
part; nothing stands in for the other chips.

**Two kinds of cached row of different size, two resources a sequence**
from the one cache manager (``decode/paged_kv.py:CacheManager``), as a
hybrid's pages and state entry are (``decode/state_entry.py``, on which
this model stands: a window layer is its *recurrent* kind, whose whole
per-sequence state is a ring):

- the full layers' K/V as a PAGE RUN over layer-axis pools, every full
  layer under the same page ids: ``k_pool (full layers, N, pg, 4, 256)``
  and ``v_pool (.., 4, 128)``.  A key of 192 is stored at 256 lanes
  (zeros behind it): the walk kernel copies a page out of HBM and Mosaic
  takes that slice only of rows of whole 128-lane tiles
  (``attention.walk_fits``; the probes of the forms refused are
  ``tests/test_chip_compile_mimo.py``).  The decode step reads the run
  through the grouped walk (``ragged_paged_attention_gqa``) with q
  padded to 256 lanes, the values' pool at its own width;
- the window layers' RINGS as one ENTRY a sequence: ``ring_k (window
  layers, entries, 2 pg, 8, 256)``, ``ring_v (.., 8, 128)``; row ``p %
  (2 pg)`` of a layer's ring holds position ``p`` until position ``p + 2
  pg`` overwrites it, so the newest ``window`` rows are always whole in
  it (``window == page_size``).  The step writes its row first and reads
  the slot's entry gathered, in plain XLA
  (``attention.ring_window_attention``, K-EXAONE's form).

Admission counts both (a reservation is the run's pages and ONE entry);
``cache_rows`` / ``cache_bytes`` report both kinds.

**A prompt longer than the top bucket** runs as that bucket and then
chunks inside one admission (``StateEntryLM.prefill``).  In a chunk a
window layer is banded over the ring's newest page and the chunk, with
the sink, and leaves the ring holding the prompt's last two pages; a
full layer writes its rows to the run, gathers the ``done`` cached rows
by the table and reads them and the chunk itself through the flash
forward, merged by log-sum-exp (``attention.prompt_chunk_attention``,
LFM2's form; the flash kernel has one head size, so the values ride at
the keys' 192 lanes and the padding is sliced off).

What would need the rings as they stood at an EARLIER row (a prefix
hit, a fork, the speculative verify) is refused by name
(``UnsupportedOverState``).

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, scores and rotation; K (rotated)
and V (scaled) rows in the pools' dtype.  Random weights only: loading a
checkpoint is not supported.
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
    dense_prefill_attention,
    paged_attention,
    prompt_chunk_attention,
    ring_window_attention,
)
from paddle_tpu.decode.model import _write_rows
from paddle_tpu.decode.paged_kv import CacheManager
from paddle_tpu.decode.state_entry import (  # noqa: F401  (re-exported)
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    _pad_last,
)
from paddle_tpu.models import moe
from paddle_tpu.models.exaone_moe import swiglu
from paddle_tpu.models.olmoe import _mm, rms_norm, rope, rope_angles
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.pallas.conv_step import LANES

_F32 = jnp.float32
WINDOW, FULL = "sliding_attention", "full_attention"
# the published ``hybrid_layer_pattern``'s first layers (0 = full): a
# leading full layer, then a period of five window layers and a full one
PATTERN = (FULL, WINDOW, WINDOW, WINDOW, WINDOW, FULL, WINDOW)

_M_CACHE_RESOURCE = _metrics.gauge(
    "decode_cache_resource",
    "the two resources of a model that keeps a page run and a ring entry "
    "a sequence (models/mimo_v2.py), by resource (run_pages, ring_entries) "
    "and state (in_use, free)")
_M_FULL_ROWS_READ = _metrics.counter(
    "decode_full_rows_read_total",
    "cached rows the full layers of a decode step read, one layer's: the "
    "sum over the seated slots of their lengths, the step's own row "
    "counted; over decode_steps_total it is the rows a step reads")
_M_RUN_PAGE_STEPS = _metrics.counter(
    "decode_run_pages_in_use_steps_total",
    "run pages in use, summed over the decode steps collected; over "
    "decode_steps_total x the pool's usable pages it is the pool's mean "
    "fill")


class RingRunManager(CacheManager):
    """``CacheManager`` whose entries are ring entries: the same
    reservation, and a gauge that names both resources."""

    def gauge_entries(self) -> None:
        super().gauge_entries()
        _M_CACHE_RESOURCE.set(self.pages_in_use, resource="run_pages",
                              state="in_use")
        _M_CACHE_RESOURCE.set(self.free_pages, resource="run_pages",
                              state="free")
        _M_CACHE_RESOURCE.set(self.entries_in_use, resource="ring_entries",
                              state="in_use")
        _M_CACHE_RESOURCE.set(self.free_entries, resource="ring_entries",
                              state="free")


def partial_rope(x, cos, sin, rotary: int):
    """Rotate-half RoPE on the first ``rotary`` channels of each head of
    ``x`` (..., heads, dh); the others pass."""
    return jnp.concatenate(
        [rope(x[..., :rotary], cos, sin), x[..., rotary:]], axis=-1)


def ring_rows_of(first, n, rows: int, page: int):
    """Which row of a run of rows that starts at position ``first``
    (whole pages) and holds ``n`` real rows each row of a two-page ring
    keeps once they are written: ring row ``r`` holds the newest
    position ``p <= first + n - 1`` with ``p // page % 2 == r // page``
    and ``p % page == r % page`` -> (2 page,) int32 indices into the
    run, clipped to it, and whether the position lies in the run at all
    (else the ring keeps what it held).  A position past the last real
    row is a padding row's: never seen, whatever it holds."""
    r = jnp.arange(2 * page, dtype=jnp.int32)
    slot, off = r // page, r % page
    last = (first + n - 1) // page
    held = last - (last - slot) % 2                  # the page slot holds
    at = held * page + off - first
    return jnp.clip(at, 0, rows - 1), at >= 0


@dataclasses.dataclass(frozen=True)
class MimoV2Block(StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side: a
    window layer is the recurrent kind, its state the sequence's ring.
    ``key_lanes``: the lanes a full layer's key is stored at in a page;
    ``ring_lanes``: a window layer's in its ring."""

    recurrent_kind = WINDOW
    layer_types: tuple = PATTERN
    kv_heads: int = 4            # a full layer's
    window_kv_heads: int = 8
    head_dim: int = 192          # q and k
    value_dim: int = 128
    rotary: int = 64
    key_lanes: int = 256
    ring_lanes: int = 256
    window: int = 128
    eps: float = 1e-5
    theta: float = 1e7
    window_theta: float = 1e4
    value_scale: float = 0.707
    top_k: int = 8
    scale: float = 1.0
    held: tuple = (0, 16)
    experts: int = 256           # the router's width: the published experts
    full_pages: int = 256        # table columns of the page run
    at: int = 0

    # -- the block ----------------------------------------------------------

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    @property
    def score_scale(self) -> float:
        return self.head_dim ** -0.5

    def qkv(self, lp, x, pos, heads):
        """Pre-norm, the three projections split to heads (this kind of
        layer's K/V heads), the rotation of the first ``rotary``
        channels at this kind's theta, the values scaled."""
        n = rms_norm(x, lp["w_in"], self.eps)
        lead = x.shape[:-1]
        hkv = self.window_kv_heads if self.recurrent else self.kv_heads
        q = _mm(n, lp["wq"]).reshape(lead + (heads, self.head_dim))
        k = _mm(n, lp["wk"]).reshape(lead + (hkv, self.head_dim))
        v = _mm(n, lp["wv"]).reshape(lead + (hkv, self.value_dim))
        cos, sin = rope_angles(
            pos, self.rotary,
            self.window_theta if self.recurrent else self.theta)
        dtype = lp["wq"].dtype
        return (partial_rope(q, cos, sin, self.rotary).astype(dtype),
                partial_rope(k, cos, sin, self.rotary).astype(dtype),
                (v * self.value_scale).astype(dtype))

    def attn_out(self, lp, x, a):
        return x + _mm(a, lp["wo"])

    def router_rows(self, lp, x):
        """What the feed-forward (and a routed layer's router) is fed:
        (R, d) in the weights' dtype."""
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["w_gate"].dtype)
        return m.reshape(-1, m.shape[-1])

    def scores(self, lp):
        return moe.sigmoid_scores(lp["b"], self.scale)

    def mlp(self, lp, x, live):
        """Layer 0's dense SwiGLU, or the held routed experts (no shared
        expert).  Reports (held experts + 1,) int32: the live rows'
        assignments per held expert, then those that went elsewhere (a
        dense layer: zeros)."""
        m = self.router_rows(lp, x)
        if "wr" not in lp:
            y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
            report = jnp.zeros((self.held[1] + 1,), jnp.int32)
        else:
            y, load, elsewhere = moe.routed_experts(
                m, lp["wr"], lp["w_gate"], lp["w_up"], lp["w_down"],
                top_k=self.top_k,
                live=None if live is None else live.reshape(-1),
                scores=self.scores(lp), held=self.held)
            report = jnp.concatenate(
                [load, elsewhere.astype(jnp.int32)[None]])
        return x + y.reshape(x.shape), report

    def head(self, params, x):
        return _mm(rms_norm(x, params["w_f"], self.eps), params["lm_head"])

    # -- what the two caches hold -------------------------------------------

    def _stored_key(self, k):
        """This layer's key rows as its cache holds them: zeros behind
        the published numbers, up to whole lanes."""
        return _pad_last(
            k, self.ring_lanes if self.recurrent else self.key_lanes)

    def _run_pages(self, k_pool, v_pool, tables):
        """What the paged kernels take for this full layer: each pool
        whole as pages (a bitcast) and the run's table columns moved to
        the layer's slab."""
        N = k_pool.shape[1]
        return (k_pool.reshape((-1,) + k_pool.shape[2:]),
                v_pool.reshape((-1,) + v_pool.shape[2:]),
                tables[..., :self.full_pages] + self.index_in_kind * N)


    # -- a whole prompt (a bucket) ------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        q, k, v = self.qkv(lp, x, pos, heads)
        T = x.shape[0]
        if not self.recurrent:
            with jax.named_scope("attn_full"):
                # the flash kernel has one head size: the values ride in
                # the keys' lanes and the padding is sliced off
                a = dense_prefill_attention(
                    q, k, _pad_last(v, self.head_dim),
                    causal=True)[..., :self.value_dim]
            keep = (self._stored_key(k), v)
        else:
            n = T if live is None else jnp.sum(live.astype(jnp.int32))
            with jax.named_scope("attn_window"):
                a = banded_prefill_attention(
                    q, k, v, self.window, sink=lp["sink"],
                    scale=self.score_scale)
                at, _ = ring_rows_of(0, n, T, self.window)
                keep = (self._stored_key(k)[at], v[at])
        return self.attn_out(lp, x, a.reshape(T, -1)), keep

    def store_prompts(self, cache, kept, where):
        """``where``: (the page run's flat rows (T,), the ring entry).
        The full layers' K/V rows as every paged model's; each window
        layer's ring written whole over the entry, so that a reused
        entry needs no reset."""
        flat, entry = where
        k_pool, v_pool, ring_k, ring_v = cache
        win = [t == WINDOW for t in self.layer_types]
        full = [kv for kv, w in zip(kept, win) if not w]
        ring = [kv for kv, w in zip(kept, win) if w]
        k_pool = self.store_prompt(k_pool, jnp.stack([k for k, _ in full]),
                                   flat)
        v_pool = self.store_prompt(v_pool, jnp.stack([v for _, v in full]),
                                   flat)
        ring_k = ring_k.at[:, entry].set(
            jnp.stack([k for k, _ in ring]).astype(ring_k.dtype))
        ring_v = ring_v.at[:, entry].set(
            jnp.stack([v for _, v in ring]).astype(ring_v.dtype))
        return k_pool, v_pool, ring_k, ring_v

    # -- a decode step -------------------------------------------------------

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        if lone or x.ndim != 2:
            raise UnsupportedOverState(
                "a chunk of rows a sequence over cached rows (a suffix "
                "prefill, the speculative verify) would need the rings as "
                "they stood before rows that may be rejected")
        k_pool, v_pool, ring_k, ring_v = cache
        q, k, v = self.qkv(lp, x, pos, heads)
        if not self.recurrent:
            slab = self.index_in_kind
            with jax.named_scope("attn_full"):
                k_pool = _write_rows(k_pool, slab, addr.flat,
                                     self._stored_key(k))
                v_pool = _write_rows(v_pool, slab, addr.flat, v)
                a = paged_attention(
                    _pad_last(q, self.key_lanes),
                    *self._run_pages(k_pool, v_pool, addr.tables),
                    addr.lens + 1, scale=self.score_scale)
        else:
            with jax.named_scope("attn_window"):
                a, ring_k, ring_v = self._ring_step(
                    lp, q, k, v, ring_k, ring_v, addr)
        return (self.attn_out(lp, x, a.reshape(x.shape[0], -1)),
                (k_pool, v_pool, ring_k, ring_v))

    def _ring_step(self, lp, q, k, v, ring_k, ring_v, addr):
        """A window layer of a step: the new row written at its slot's
        ring row first, then the slot's ring gathered and read under
        the window's mask with the sink."""
        W, E, R = ring_k.shape[:3]
        pg = self.window
        entry = self.index_in_kind * E + addr.tables[:, self.full_pages]
        row = entry * R + addr.lens % R

        def written(ring, new):
            flat = ring.reshape((W * E * R,) + ring.shape[3:])
            return flat.at[row].set(new.astype(ring.dtype)).reshape(
                ring.shape)

        ring_k = written(ring_k, self._stored_key(k))
        ring_v = written(ring_v, v)

        # the slots' rings, (S, 2, pg, Hkv, D), gathered a PAGE of the
        # ring at a time: behind the write a gather of whole entries read
        # 0.96 ms a layer on the chip, by pages 0.17 (PERF.md section 6,
        # PR 62), K-EXAONE's form, which XLA fuses into the scores
        halves = (entry[:, None] * (R // pg)
                  + jnp.arange(R // pg, dtype=jnp.int32))

        def mine(ring):
            return ring.reshape((W * E * (R // pg), pg)
                                + ring.shape[3:])[halves]

        a = ring_window_attention(
            _pad_last(q, self.ring_lanes)[:, None], mine(ring_k),
            mine(ring_v), addr.lens[:, None],
            self.window, pg, sink=lp["sink"], scale=self.score_scale)
        return a[:, 0], ring_k, ring_v

    # -- a chunk of ONE prompt, after the rows it has run --------------------

    def chunk_mixer(self, lp, x, pos, cache, li, chunk, heads):
        k_pool, v_pool, ring_k, ring_v = cache
        q, k, v = self.qkv(lp, x, pos, heads)
        if not self.recurrent:
            with jax.named_scope("attn_full"):
                a, k_pool, v_pool = self._run_chunk(q, k, v, k_pool, v_pool,
                                                    chunk)
        else:
            with jax.named_scope("attn_window"):
                a, ring_k, ring_v = self._ring_chunk(lp, q, k, v, ring_k,
                                                     ring_v, chunk)
        return (self.attn_out(lp, x, a.reshape(x.shape[0], -1)),
                (k_pool, v_pool, ring_k, ring_v))

    def _run_chunk(self, q, k, v, k_pool, v_pool, chunk):
        """A full layer over a chunk's rows: written to the page run,
        then attending over the ``done`` cached rows, gathered by the
        table, and the chunk's own causal part."""
        slab = self.index_in_kind
        k_pool = _write_rows(k_pool, slab, chunk.flat, self._stored_key(k))
        v_pool = _write_rows(v_pool, slab, chunk.flat, v)
        with jax.named_scope("attn_chunk"):
            N, pg = k_pool.shape[1:3]
            cols = chunk.done // pg
            at = ((slab * N + chunk.table[:cols])[:, None] * pg
                  + jnp.arange(pg, dtype=jnp.int32)).reshape(-1)

            def run(pool, width):       # the cached rows, (Hkv, done, width)
                rows = pool.reshape((-1,) + pool.shape[3:])[at]
                return jnp.moveaxis(_pad_last(rows[..., :width],
                                              self.head_dim), 1, 0)

            a = prompt_chunk_attention(
                q, k, _pad_last(v, self.head_dim),
                run(k_pool, self.head_dim), run(v_pool, self.value_dim))
        return a[..., :self.value_dim], k_pool, v_pool

    def _ring_chunk(self, lp, q, k, v, ring_k, ring_v, chunk):
        """A window layer over a chunk's rows: banded over the ring's
        newest page (the ``window`` rows before the chunk) and the
        chunk, with the sink; the ring left holding the last two pages
        of what has run."""
        pg = self.window
        slab, entry = self.index_in_kind, chunk.table[self.full_pages]
        newest = (chunk.done // pg - 1) % 2 * pg       # static

        def before(ring, width):
            return jax.lax.dynamic_slice_in_dim(
                ring[slab, entry], newest, pg)[..., :width]

        a = banded_prefill_attention(
            q, k, v, self.window, sink=lp["sink"], scale=self.score_scale,
            before=(before(ring_k, self.head_dim),
                    before(ring_v, self.value_dim)))

        at, reached = ring_rows_of(chunk.done, chunk.n, q.shape[0], pg)

        def kept(ring, rows):
            return ring.at[slab, entry].set(jnp.where(
                reached[:, None, None], rows[at].astype(ring.dtype),
                ring[slab, entry]))

        return a, kept(ring_k, self._stored_key(k)), kept(ring_v, v)


# The standard deviation of a q or k row's numbers: the q and k
# projections are drawn N(0, QK_ROW_STD * d^-1/2) where every other
# matrix is N(0, 0.02) (``granite_hybrid.QK_ROW_STD`` says why: at a
# flat softmax nothing in the logits sees a rotation, a window, a page
# or a scale).  At 1.0 a score ``q.k * 192^-1/2`` has a spread of 1,
# Kanana's choice and for its reason: at 1.5 (my first chip run, PR 62)
# the median logits row stood 0.017-0.019 from the float32 reference,
# twice K-EXAONE's 0.010, and a sink given to the full layers read 0.030:
# sharper heads amplify the bf16 rounding toward the ablations.
QK_ROW_STD = 1.0

# A window layer's sinks are drawn N(SINK_MEAN, 1) a query head.  The
# exponentials of a window's 128 scores at the spread above sum to ~210,
# so a sink of 4 (e^4 = 55) holds a fifth of a row's mass, one of 5 two
# fifths and one of 3 a twelfth: dropping them, or giving them to the
# full layers too, moves the logits by far more than the bf16 rounding
# does.  At N(0, 1) a sink would hold half a percent and no limit would
# see it go.
SINK_MEAN = 4.0


@functools.partial(jax.jit, static_argnames=("shape", "std", "mean",
                                             "dtype"))
def _normal(key, *, shape, std, dtype, mean=0.0):
    return (mean + jax.random.normal(key, shape, _F32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "routed", "d", "heads", "kv_heads", "head_dim", "value_dim",
    "width", "router_width", "held", "dtype"))
def _init_layer(key, *, window, routed, d, heads, kv_heads, head_dim,
                value_dim, width, router_width, held, dtype):
    """One layer's parameters: one program a (kind of attention, kind
    of feed-forward)."""
    def normal(k, *shape, std=0.02, dt=dtype, mean=0.0):
        return _normal(k, shape=shape, std=std, dtype=dt, mean=mean)

    ones = jnp.ones((d,), dtype)
    lk = jax.random.split(key, 10)
    qk = QK_ROW_STD * d ** -0.5
    lead = (held,) if routed else ()
    lp = {"w_in": ones, "w_post": ones,
          "wq": normal(lk[0], d, heads * head_dim, std=qk),
          "wk": normal(lk[1], d, kv_heads * head_dim, std=qk),
          "wv": normal(lk[2], d, kv_heads * value_dim),
          "wo": normal(lk[3], heads * value_dim, d),
          "w_gate": normal(lk[4], *lead, d, width),
          "w_up": normal(lk[5], *lead, d, width),
          "w_down": normal(lk[6], *lead, width, d)}
    if routed:
        lp.update(wr=normal(lk[7], d, router_width),
                  b=normal(lk[8], router_width, dt=_F32))
    if window:
        lp["sink"] = normal(lk[9], heads, std=1.0, dt=_F32, mean=SINK_MEAN)
    return lp


def init_params(key, *, vocab, d, heads, kv_heads, window_kv_heads,
                head_dim, value_dim, layer_types, moe_layers, dense_width,
                expert_width, router_width, held, dtype):
    """Every matrix N(0, 0.02) in ``dtype`` but the q and k projections
    (``QK_ROW_STD``), every norm scale 1, the router's selection bias
    N(0, 0.02) float32 (``exaone_moe.init_params`` says why that wide),
    a window layer's sinks N(``SINK_MEAN``, 1) float32.  Made on the
    device, a layer at a time."""
    ks = jax.random.split(key, 2 + len(layer_types))
    sizes = dict(d=d, heads=heads, head_dim=head_dim, value_dim=value_dim,
                 router_width=router_width, held=held, dtype=dtype)
    return {"emb": _normal(ks[0], shape=(vocab, d), std=0.02, dtype=dtype),
            "w_f": jnp.ones((d,), dtype),
            "lm_head": _normal(ks[1], shape=(d, vocab), std=0.02,
                               dtype=dtype),
            "layers": [
                _init_layer(
                    k, window=kind == WINDOW, routed=routed,
                    kv_heads=window_kv_heads if kind == WINDOW else kv_heads,
                    width=expert_width if routed else dense_width, **sizes)
                for k, kind, routed in zip(ks[2:], layer_types, moe_layers)]}


class MimoV2LM(StateEntryLM):
    """MiMo-V2.5's share of one chip over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/mimo-v2.5.gen_config.py``).  The reservation (the
    run's pages, then ONE ring entry), the table row, the chunk loop and
    the refusals are ``decode/state_entry.py``'s.

    ``ring_entries``: the entries of the ring pools, entry 0 the null
    one (a slot a seated sequence at most: slots + 1 is enough)."""

    chunk_over = "ring"          # decode_prefill_chunk_rows_total{over}

    def __init__(self, vocab: int = 19072, d_model: int = 4096,
                 num_heads: int = 64, num_kv_heads: int = 4,
                 swa_num_kv_heads: int = 8, head_dim: int = 192,
                 v_head_dim: int = 128, rotary_dim: int = 64,
                 layer_types: Sequence[str] = PATTERN,
                 mlp_layer_types: Sequence[str] = ("dense",) + ("sparse",) * 6,
                 sliding_window: int = 128, dense_width: int = 16384,
                 expert_width: int = 2048, num_experts_published: int = 256,
                 held_experts=(0, 16), experts_per_tok: int = 8,
                 routed_scaling_factor: float = 1.0,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 1e7,
                 swa_rope_theta: float = 1e4,
                 attention_value_scale: float = 0.707,
                 max_len: int = 32768, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 256,
                 ring_entries: int = 49, prefill_rows: int = 8192,
                 chunk_rows: int = 4096, dtype="bfloat16", bos_id: int = 1,
                 eos_id: int = -1, seed: int = 0):
        layer_types = tuple(layer_types)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if len(mlp_layer_types) != len(layer_types):
            raise ValueError("one mlp_layer_types entry a layer")
        if not set(layer_types) <= {WINDOW, FULL}:
            raise ValueError(f"layer_types are {WINDOW!r} or {FULL!r}")
        if int(sliding_window) != self.page_size:
            raise ValueError(
                "sliding_window has to be the page size: a ring is the two "
                "pages that hold the newest window whole, and a chunk's "
                "window layers start from the ring's newest page")
        if num_heads % num_kv_heads or num_heads % swa_num_kv_heads:
            raise ValueError("the K/V heads of both kinds have to divide "
                             "the query heads")
        if not 0 < rotary_dim <= head_dim or rotary_dim % 2:
            raise ValueError("rotary_dim: an even part of the head")
        self.dh, self.dv = int(head_dim), int(v_head_dim)
        self.kv_heads = int(num_kv_heads)
        self.window_kv_heads = int(swa_num_kv_heads)
        self._count_layers(layer_types, WINDOW)
        self.window_layers = self.linear_layers
        self._chunked(prefill_rows, chunk_rows)
        self.ring_rows = 2 * self.page_size
        # a key of a full layer as a page stores it: whole 128-lane tiles
        key_lanes = -(-self.dh // LANES) * LANES
        self.block = MimoV2Block(
            layer_types=layer_types, kv_heads=self.kv_heads,
            window_kv_heads=self.window_kv_heads, head_dim=self.dh,
            value_dim=self.dv, rotary=int(rotary_dim), key_lanes=key_lanes,
            ring_lanes=key_lanes,
            window=int(sliding_window), eps=float(rms_norm_eps),
            theta=float(rope_theta), window_theta=float(swa_rope_theta),
            value_scale=float(attention_value_scale),
            top_k=int(experts_per_tok), scale=float(routed_scaling_factor),
            held=tuple(int(x) for x in held_experts),
            experts=int(num_experts_published), full_pages=self.full_pages)
        dtype = jnp.dtype(dtype)
        moe_layers = tuple(t == "sparse" for t in mlp_layer_types)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            heads=self.heads, kv_heads=self.kv_heads,
            window_kv_heads=self.window_kv_heads, head_dim=self.dh,
            value_dim=self.dv, layer_types=layer_types,
            moe_layers=moe_layers, dense_width=int(dense_width),
            expert_width=int(expert_width),
            router_width=int(num_experts_published),
            held=self.block.held[1], dtype=dtype)
        self._routed = [i for i, r in enumerate(moe_layers) if r]
        self._make_pools(num_pages, dtype, int(ring_entries), key_lanes)

    def _make_pools(self, num_pages, dtype, ring_entries, key_lanes):
        """The page run's two pools, K at ``key_lanes`` and V at its own
        width, a slab a full layer; the rings' two pools, a slab a
        window layer and ``ring_rows`` rows an entry, K at the same
        whole lanes (at 192 the compiler re-laid the whole ring pool out
        round every window layer's gather: the probe in
        ``tests/test_chip_compile_mimo.py``)."""
        self.allocator = RingRunManager(num_pages, ring_entries)
        run = (self.full_layers, num_pages, self.page_size, self.kv_heads)
        ring = (self.window_layers, ring_entries, self.ring_rows,
                self.window_kv_heads)
        self.k_pool = jnp.zeros(run + (key_lanes,), dtype)
        self.v_pool = jnp.zeros(run + (self.dv,), dtype)
        self.extra_pools = (jnp.zeros(ring + (key_lanes,), dtype),
                            jnp.zeros(ring + (self.dv,), dtype))

    def _observe(self, phase, report, rows):
        report = np.asarray(report)[self._routed]      # (routed, held + 1)
        if report.size:
            moe.count_load(phase, report[:, :-1], rows, self.block.top_k,
                           self.block.experts, int(report[:, -1].sum()))

    # -- what is resident ----------------------------------------------------

    def cache_rows(self, lens) -> dict:
        """Rows resident per kind of cache for sequences of ``lens``
        rows, summed over the layers of the kind: a full layer holds
        every row, a ring its newest ``ring_rows`` at most."""
        lens = np.asarray(lens, np.int64)
        return {"full": int(lens.sum()) * self.full_layers,
                "window": (int(np.minimum(lens, self.ring_rows).sum())
                           * self.window_layers)}

    def row_bytes(self, kind: str) -> int:
        """Bytes one token's K and V take in one layer of ``kind`` as
        PUBLISHED (heads x (192 + 128) numbers), not as stored."""
        heads = self.kv_heads if kind == "full" else self.window_kv_heads
        return heads * (self.dh + self.dv) * self.k_pool.dtype.itemsize

    def cache_bytes(self, lens) -> dict:
        rows = self.cache_rows(lens)
        return {kind: n * self.row_bytes(kind) for kind, n in rows.items()}

    # -- a step's counters ---------------------------------------------------

    def _dispatch(self, jitted, tokens, tables, lens):
        step = super()._dispatch(jitted, tokens, tables, lens)
        if step.next is not None:
            # the lengths after the step: what its full layers read
            step.next["lens"].copy_to_host_async()
        return step

    def step_collect(self, step):
        out = super().step_collect(step)
        if step.next is not None:
            _M_FULL_ROWS_READ.inc(int(np.asarray(step.next["lens"]).sum()))
            _M_RUN_PAGE_STEPS.inc(self.allocator.pages_in_use)
        return out
