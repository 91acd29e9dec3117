"""What a hybrid needs of the paged skeleton (``decode/model.py``): a
model whose *recurrent* layers keep one state a sequence, whatever its
length, beside the pages of its attention layers.  Seven models stand
on it: ``models/olmo_hybrid.py`` (the gated delta rule three layers of
four), ``models/granite_hybrid.py`` (Mamba-2 nine layers of ten),
``models/nemotron_h.py`` (Mamba-2 in eight groups 23 layers of 52, six
attention layers, and 23 layers that are experts ALONE: they keep
nothing, neither of a prompt nor of a step),
``models/phi4_flash.py`` (Mamba-1 beside rings and one shared run),
``models/ling_hybrid.py`` (the delta rule under a per-channel decay five
layers of six, beside ONE latent row a token in the sixth),
``models/lfm2_moe.py`` (gated short convs, whose state is a conv tail)
and ``models/mimo_v2.py``, whose "recurrent" layers are WINDOW attention
layers: what they keep of a sequence is of fixed size too, a ring of
K/V rows, so its entry holds the rings (in the two ``extra`` pools'
places) and its chunks continue over them.

Two resources a sequence, from the one cache manager
(``decode/paged_kv.py:CacheManager``): its page run, which the
attention layers alone write (pools ``(attention layers, N, pg, ...)``),
and ONE state entry: every recurrent layer's state and the last rows
that layer's conv saw (pools ``(recurrent layers, entries, ...)``, entry
0 the null entry that inactive slots address).  Its table row is the
page run's columns, then the entry.  The cache is ``(k_pool, v_pool,
state_pool, conv_pool)``, all four donated to every program.  What a
page holds is the block's to say (``StateEntryCache.page_mixer`` and
``store_pages``; ``StateEntryLM._make_pools``' ``page_heads`` and
``value_pool``): K and V heads in two pools by default, ONE latent row
in the first pool alone (the second a placeholder) for a latent layer.

What needs a state as it stood at an earlier row is refused by name
(``UnsupportedOverState``): a prefill over cached pages, a fork, the
speculative verify.

``StateEntryCache`` is the block's side (which layer is which, its slab
of its kind's pools, a slot's entry, the prompt's states and tails
written whole over the entry); ``StateEntryLM`` the model's (the
reservation, the table row, the gauges' rows and bytes, the refusals).
A model says how its own layers mix tokens and how a page holds its
heads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.bucket import bucket_dim
from paddle_tpu.decode.model import (
    _M_PREFILL_PADDED,
    _M_PREFILL_TOKENS,
    PagedDecoderLM,
    PageRunCache,
    _dense_blocks,
    _stack_reports,
)
from paddle_tpu.decode.paged_kv import CacheManager
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import phase
from paddle_tpu.pallas.conv_step import LANES, SILU, activate, conv_step

_F32 = jnp.float32

_M_CHUNK_ROWS = _metrics.counter(
    "decode_prefill_chunk_rows_total",
    "real prompt rows prefilled in chunks after the top bucket, by what "
    "the chunk continues over: over=\"state\" (a state entry: each "
    "chunk from the state the one before left there); over the window's "
    "decode_prefill_tokens_total it is the share of prompt rows that "
    "ran in chunks")


_M_CHUNK_PAIRS = _metrics.counter(
    "decode_prefill_chunk_pairs_total",
    "(query row, key row) pairs an attention layer of those chunks "
    "computes: a chunk's real rows x the rows done before it, and the "
    "chunk's own causal part (rows x (rows + 1) / 2); x 4 x heads x "
    "head size x attention layers it is the chunks' attention FLOPs")


class UnsupportedOverState(RuntimeError):
    """Asked of a model with recurrent layers what needs their state as
    it stood at a row that is not the sequence's last: a prefill over a
    shared prefix, a fork, the speculative verify's rollback.  Only the
    newest state is kept (ROADMAP R7)."""


def _pad_axis(x, axis, width):
    """``x`` with zeros appended along ``axis`` up to ``width``."""
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, width - x.shape[axis])
    return jnp.pad(x, pads) if pads[axis][1] else x


def _pad_last(x, width):
    return _pad_axis(x, -1, width)


def _rows_before(z, taps, before=None):
    """``z`` (T, C) with the ``taps - 1`` rows before row 0: zeros, or
    ``before`` (taps - 1, C), the tail an earlier chunk of the same
    sequence left."""
    if before is None:
        before = jnp.zeros((taps - 1, z.shape[1]), z.dtype)
    return jnp.concatenate([before.astype(z.dtype), z])


def causal_conv(z, w, before=None):
    """Depthwise causal conv of ``z`` (T, C) with taps ``w`` (K, C):
    row t is ``sum_j w[j] z[t - (K - 1) + j]``, with zeros before row 0
    or the rows ``before`` (``_rows_before``)."""
    K, T = w.shape[0], z.shape[0]
    zp = _rows_before(z, K, before)
    return sum(zp[j:j + T].astype(_F32) * w[j].astype(_F32)
               for j in range(K))


def conv_tail(z, taps, n, before=None):
    """Rows ``n - (taps - 1) .. n - 1`` of ``z`` (T, C), zeros (or
    ``before``) before row 0: what a conv of ``taps`` taps has to keep
    of a prompt, or of a chunk, of ``n`` real rows (the padding after
    them is left out)."""
    return jax.lax.dynamic_slice_in_dim(_rows_before(z, taps, before), n,
                                        taps - 1)


def tail_shape(taps, channels):
    """What a layer's conv keeps of a sequence, as an entry stores it:
    the ``taps - 1`` rows one after another, in rows of 128 lanes where
    the channels are whole lanes (an entry is then a block
    ``pallas/conv_step.py`` takes whole, and nothing is padded: a layer's
    entries as rows of one slab, or three rows an entry, are no whole
    tiles), else ``(taps - 1, channels)``.  The same bytes in the same
    order either way: a reshape of ``conv_tail``'s rows."""
    kept = taps - 1
    return ((kept * channels // LANES, LANES) if channels % LANES == 0
            else (kept, channels))


def step_conv(kept, row, w, b=None, activation=SILU):
    """A decode step's conv: the step's ``row`` (..., C) after the
    ``taps - 1`` rows an entry keeps, ``kept`` (..., taps - 1, C), both
    in the weights' dtype; taps ``w`` (taps, C), bias ``b`` (C,) or None
    -> (``act(sum_j w[j] rows[j] (+ b))`` float32, the rows the entry
    keeps now: ``rows[1:]``).  ``activation``: ``"silu"``, every conv in
    front of a recurrence, or None (a gated short conv has none).  Any
    leading shape: a slot's, or the slots'.  ``pallas/conv_step.py`` is
    this, in this order, on the entries where they lie."""
    rows = jnp.concatenate([kept, row[..., None, :]], axis=-2)
    acc = jnp.sum(rows.astype(_F32) * w.astype(_F32), axis=-2)
    if b is not None:
        acc = acc + b.astype(_F32)
    return activate(acc, activation), rows[..., 1:, :]


def conv_over_entries(tails, at, row, w, b=None, activation=SILU):
    """A recurrent layer's conv over a decode step's rows ``row`` (S, C),
    each after what its slot's entry ``at`` keeps in the tail pool seen
    flat, ``tails`` (entries, ``*tail_shape``) -> (the conv's output
    rows (S, C) float32, the pool with those entries moved on one row,
    in place).  By ONE ``pallas/conv_step.py`` call where
    ``pallas.use_conv_step`` says so, else gathered, advanced by
    ``step_conv`` and scattered in XLA, the kernel's reference."""
    (taps, C), S = w.shape, row.shape[0]
    if pk.use_conv_step(tails.dtype, tails.shape[1:], row.dtype, taps, C):
        return conv_step(tails, at, row, w, b, activation=activation,
                         interpret=pk.interpret_mode())
    out, kept = step_conv(tails[at].reshape(S, taps - 1, C), row, w, b,
                          activation)
    return out, tails.at[at].set(kept.reshape((S,) + tails.shape[1:]))


class PromptChunk(NamedTuple):
    """Where a chunk of one prompt lies: ``done`` (static, whole pages)
    rows of the sequence ran before it; ``n`` of the chunk's rows are
    real (traced; the rest padding); ``flat`` each chunk row's flat row
    of the page run; ``table`` the sequence's table row (the page run's
    columns, then the entry)."""

    done: int
    n: jax.Array
    flat: jax.Array
    table: jax.Array


class StateEntryCache(PageRunCache):
    """The cache side of a hybrid's block, a frozen dataclass with the
    fields ``layer_types``, ``full_pages`` (the table columns of the page
    run) and ``at`` (the layer this view of the block is) and the class
    attribute ``recurrent_kind`` (the ``layer_types`` entry of a layer
    that keeps a state; every other layer is attention over the page
    run)."""

    recurrent_kind = ""

    def layer(self, li):
        return dataclasses.replace(self, at=li)

    @property
    def recurrent(self) -> bool:
        return self.layer_types[self.at] == self.recurrent_kind

    @property
    def index_in_kind(self) -> int:
        """This layer's index among the layers of its own kind: its
        slab of that kind's pools."""
        kind = self.layer_types[self.at]
        return sum(t == kind for t in self.layer_types[:self.at])

    def entries_of(self, pool, addr):
        """Each slot's entry in this layer's slab of the state and conv
        pools seen flat (a bitcast; ``pool``: either, one that is no
        placeholder); an inactive slot's is the null entry 0."""
        E = pool.shape[1]
        return self.index_in_kind * E + addr.tables[:, self.full_pages]

    def store_prompts(self, cache, kept, where):
        """``where``: (the page run's flat rows (T,), the state entry).
        The attention layers' K/V rows as every paged model's; each
        recurrent layer's final state and conv tail written whole over
        the entry, so that a reused entry needs no reset."""
        flat, entry = where
        k_pool, v_pool, state_pool, conv_pool = cache
        rec = [t == self.recurrent_kind for t in self.layer_types]
        # a layer that mixes no tokens (experts alone) kept None
        full = [kv for kv, r in zip(kept, rec) if not r and kv is not None]
        lin = [sc for sc, r in zip(kept, rec) if r]
        k_pool, v_pool = self.store_pages((k_pool, v_pool), full, flat)
        if state_pool.ndim > 2:         # else a placeholder: no state kept
            states = _pad_last(jnp.stack([s for s, _ in lin]),
                               state_pool.shape[-1]).astype(state_pool.dtype)
            state_pool = state_pool.at[:, entry].set(states)
        tails = jnp.stack([c for _, c in lin]).astype(
            conv_pool.dtype).reshape((len(lin),) + conv_pool.shape[2:])
        return (k_pool, v_pool, state_pool,
                conv_pool.at[:, entry].set(tails))

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        if lone or x.ndim != 2:
            raise UnsupportedOverState(
                "a chunk of rows a sequence (a suffix prefill, the "
                "speculative verify) would need the state between them")
        if not self.recurrent:
            x, kv = self.page_mixer(lp, x, pos, cache[:2], li, addr, heads)
            return x, kv + tuple(cache[2:])
        return self.recurrent_step(lp, x, cache, addr)

    # -- the page side: K and V heads in two pools, unless the block says --

    def store_pages(self, pages, kept, flat):
        """``pages`` (the two pools) with what the attention layers
        ``kept`` of one prompt written at the page run's rows ``flat``."""
        return PageRunCache.store_prompts(self, pages, kept, flat)

    def page_mixer(self, lp, x, pos, pages, li, addr, heads):
        """An attention layer over a decode step's rows and the page
        run -> (the rows after the mixer's residual, the two pools)."""
        return PageRunCache.mixer(self, lp, x, pos, pages, li, addr, heads)

    def recurrent_step(self, lp, x, cache, addr):
        """A recurrent layer over a decode step's rows ``x`` (S, d), one
        token on each slot's entry -> (the rows after the mixer's
        residual, the four pools written in place)."""
        raise NotImplementedError

    # -- a chunk of ONE sequence's prompt, after the rows it has run --------

    def chunk_mixer(self, lp, x, pos, cache, li, chunk, heads):
        """Layer ``li`` over a chunk ``x`` (C, d) of one prompt that
        continues its own prefill (``chunk``: a ``PromptChunk``): a
        recurrent layer starts from what the sequence's entry holds,
        which IS the state at ``done`` rows, and leaves the state at the
        chunk's last real row there; an attention layer writes its rows
        to the page run and attends over the ``done`` cached rows and
        the chunk's own causal part -> (the rows after the mixer's
        residual, the four pools)."""
        if self.recurrent:
            return self.recurrent_chunk(lp, x, cache, chunk)
        x, pages = self.page_chunk(lp, x, pos, cache[:2], li, chunk, heads)
        return x, pages + tuple(cache[2:])

    def recurrent_chunk(self, lp, x, cache, chunk):
        """A recurrent layer over the chunk's rows from its entry.  The
        hook a layer kind fills (``models/lfm2_moe.py``'s short conv
        does); refused by name until it does."""
        raise UnsupportedOverState(
            f"a chunk of a prompt over a {self.recurrent_kind!r} layer's "
            "state entry: this block's chunked recurrence does not yet "
            "start from the state the entry holds "
            "(StateEntryCache.recurrent_chunk)")

    def page_chunk(self, lp, x, pos, pages, li, chunk, heads):
        """An attention layer over the chunk's rows, the cached rows of
        the page run before them."""
        raise UnsupportedOverState(
            "a chunk of a prompt over this block's page run is not laid "
            "out (StateEntryCache.page_chunk)")


class StateEntryLM(PagedDecoderLM):
    """The model's side: a subclass sets ``block`` and ``params``, calls
    ``_count_layers`` and then ``_make_pools``.

    The constructor's ``pages_per_seq`` is the page run's pages (kept as
    ``full_pages``); the attribute, which the session sizes its table
    rows by, counts the state entry's column too, and
    ``context_pages`` counts the entry as one unit of the reservation
    (``CacheManager``)."""

    supports_prefix_cache = False     # no state is kept at a prefix's end
    supports_fork = False             # nor copied for a beam's siblings
    supports_verify = False           # nor rolled back past rejected rows

    def _count_layers(self, layer_types, recurrent_kind):
        """``layer_types``: the kind of every layer that mixes tokens
        (all of them, but for a model with layers that are a
        feed-forward alone)."""
        recurrent = sum(t == recurrent_kind for t in layer_types)
        if not 0 < recurrent < len(layer_types):
            raise ValueError("a hybrid holds layers of both kinds")
        self.full_pages = self.pages_per_seq
        self.pages_per_seq = self.full_pages + 1
        self.linear_layers = recurrent
        self.full_layers = len(layer_types) - recurrent

    # the ``decode_cache_rows`` / ``decode_cache_bytes`` kind of the page
    # run's rows ("latent" where a page holds latent rows)
    page_kind = "full"

    def _make_pools(self, num_pages, dtype, state_entries, page_heads,
                    state_shape, tail_shape, value_pool=True):
        """``page_heads``: what a page's row is stored as ((heads, their
        width), or (lanes,) of a latent row); ``value_pool``: whether
        the rows have values in a second pool of the same shape, else it
        is a placeholder of one element a layer that every program
        threads through and none reads; ``state_shape``: a layer's state
        as stored, float32, or None for a layer whose whole state is its
        conv tail (the state pool is then such a placeholder too);
        ``tail_shape``: what a layer's conv keeps, in ``dtype``."""
        self.allocator = CacheManager(num_pages, state_entries)
        shape = (self.full_layers, num_pages, self.page_size, *page_heads)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape if value_pool
                                else (self.full_layers, 1), dtype)
        self.extra_pools = (
            jnp.zeros((self.linear_layers, 1) if state_shape is None else
                      (self.linear_layers, state_entries, *state_shape),
                      _F32),
            jnp.zeros((self.linear_layers, state_entries, *tail_shape),
                      dtype))

    @property
    def state_pool(self):
        return self.extra_pools[0]

    @property
    def conv_pool(self):
        return self.extra_pools[1]

    def _forward(self, tokens):
        """-> (logits (T, V), what each layer keeps of the prompt: an
        attention layer's K/V rows, a recurrent layer's final state and
        conv tail, None)."""
        x, kept, _ = _dense_blocks(self.block, self.params, tokens,
                                   self.heads, None)
        return self.block.head(self.params, x), kept, None

    # -- the reservation: the page run, then the entry -----------------------

    def context_pages(self, prompt, max_new_tokens: int) -> int:
        return super().context_pages(prompt, max_new_tokens) + 1

    def pool_table(self, pages) -> np.ndarray:
        run = self.allocator.pages_of(pages)
        t = np.zeros((self.pages_per_seq,), np.int32)
        t[:len(run)] = run
        t[self.full_pages] = self.allocator.entry_of(pages)
        return t

    def _prompt_rows(self, pages, bucket: int, n: int):
        """(the page run's flat row of each bucket row, as every paged
        model has them; the sequence's state entry)."""
        table = self.pool_table(pages)
        rows = np.arange(bucket)
        flat = (table[rows // self.page_size] * self.page_size
                + rows % self.page_size).astype(np.int32)
        return flat, np.int32(table[self.full_pages])

    def entry_bytes(self) -> int:
        """Bytes of one state entry, all recurrent layers: the states
        and the conv tails (a placeholder pool holds no entry)."""
        return sum(int(np.prod(p.shape[2:])) * p.dtype.itemsize * p.shape[0]
                   for p in self.extra_pools if p.ndim > 2)

    def cache_rows(self, lens) -> dict:
        """What is resident for sequences of ``lens`` rows, by kind of
        cache, summed over the layers of the kind: an attention layer
        holds every row; a recurrent layer one state a sequence,
        whatever its length."""
        return {self.page_kind: int(np.sum(lens)) * self.full_layers,
                "state": len(lens) * self.linear_layers}

    @property
    def page_row_bytes(self) -> int:
        """Bytes one token's row takes in one attention layer, as
        stored: both pools' (a placeholder pool holds no row)."""
        return sum(int(np.prod(p.shape[3:])) * p.dtype.itemsize
                   for p in (self.k_pool, self.v_pool) if p.ndim > 3)

    def cache_bytes(self, lens) -> dict:
        return {self.page_kind: (int(np.sum(lens)) * self.full_layers
                                 * self.page_row_bytes),
                "state": len(lens) * self.entry_bytes()}

    # -- a long prompt: the top bucket, then chunks over the entry ------------

    # what a chunk continues over, as ``decode_prefill_chunk_rows_total``
    # and ``.._pairs_total`` label it ("ring": ``models/mimo_v2.py``,
    # whose entry holds its window layers' rings)
    chunk_over = "state"

    # Rows of the top bucket and of a chunk after it (whole pages), on a
    # model whose block fills ``StateEntryCache.recurrent_chunk`` and
    # ``page_chunk``; None: one program holds a prompt or it is refused.
    prefill_rows = chunk_rows = None

    def _chunked(self, prefill_rows, chunk_rows):
        if prefill_rows % self.page_size or chunk_rows % self.page_size:
            raise ValueError(
                "prefill_rows and chunk_rows are whole pages of "
                f"{self.page_size} rows: a chunk starts on a page")
        self.prefill_rows, self.chunk_rows = int(prefill_rows), int(chunk_rows)

    @property
    def prefill_cap(self) -> int:
        cap = super().prefill_cap
        return min(cap, self.prefill_rows) if self.prefill_rows else cap

    def _chunk_of(self, rest: int) -> int:
        """Rows the program of a chunk with ``rest`` rows to go computes."""
        return min(self.chunk_rows,
                   max(bucket_dim(rest), 64, self.page_size))

    def prompt_chunks(self, n: int):
        """[(rows done before it, rows its program computes, real rows)]
        of the chunks an ``n``-row prompt runs after the top bucket."""
        done, out = self.prefill_cap, []
        while done < n:
            C = self._chunk_of(n - done)
            out.append((done, C, min(C, n - done)))
            done += out[-1][2]
        return out

    def prefill_bucket(self, n: int) -> int:
        """Rows the prefill of an ``n``-token prompt computes: its
        bucket, or the top bucket and the chunks that follow it."""
        longest = min(self.max_len, self.seq_rows)
        if n <= self.prefill_cap or not self.chunk_rows:
            return super().prefill_bucket(n)
        if n > longest:
            raise ValueError(
                f"a prompt of {n} tokens is outside 1..{longest}, the "
                "rows one sequence of this model can hold")
        return self.prefill_cap + sum(C for _, C, _ in self.prompt_chunks(n))

    def prefill(self, prompt, pages, cached_len: int = 0):
        """As ``PagedDecoderLM.prefill``.  A prompt of more rows than the
        top bucket runs as that bucket and then consecutive chunks, one
        after another inside this call, each from the state the one
        before left in the sequence's entry.  A prefix hit
        (``cached_len``) is refused: no state is kept at its end."""
        if cached_len:
            raise UnsupportedOverState(
                "a prefill over cached pages needs the recurrent layers' "
                "state as it stood at the cached length; it is not kept")
        T, cap = len(prompt), self.prefill_cap
        if T <= cap or not self.chunk_rows:
            return super().prefill(prompt, pages)
        self.prefill_bucket(T)                    # refuses what is too long
        super().prefill(prompt[:cap], pages)
        table = jnp.asarray(self.pool_table(pages))
        for done, C, real in self.prompt_chunks(T):
            toks = np.zeros((C,), np.int32)
            toks[:real] = prompt[done:done + real]
            with phase("decode.prefill_chunk", done=done, rows=real,
                       bucket=C), self._donating():
                logits, k_pool, v_pool, report, extra = _prefill_state_chunk(
                    self.params, self.k_pool, self.v_pool, table, toks,
                    np.int32(real), heads=self.heads,
                    page_size=self.page_size, block=self.block, done=done,
                    extra=self.extra_pools)
                self._set_cache(k_pool, v_pool, *extra)
                if done + real == T:
                    with phase("decode.prefill_wait"):
                        logits = np.asarray(logits)
                self._observe("prefill", report, C)
            _M_PREFILL_TOKENS.inc(real)
            _M_PREFILL_PADDED.inc(C)
            _M_CHUNK_ROWS.inc(real, over=self.chunk_over)
            _M_CHUNK_PAIRS.inc(real * done + real * (real + 1) // 2,
                               over=self.chunk_over)
        return T, [], logits

    # -- refused by name -----------------------------------------------------

    def copy_page(self, src: int, dst: int) -> None:
        raise UnsupportedOverState(
            "a copy-on-write split follows a fork, which would have to "
            "copy the sequence's state entry; this model refuses it")

    def verify_chunk(self, tokens, states, tables, lens):
        raise UnsupportedOverState(
            "a speculative verify writes k rows into the state and may "
            "reject some: the state before them is not kept")


@functools.partial(jax.jit, static_argnames=("heads", "page_size", "block",
                                             "done"),
                   donate_argnums=(1, 2), donate_argnames=("extra",))
def _prefill_state_chunk(params, k_pool, v_pool, table, tokens, n, *, heads,
                         page_size, block, done, extra):
    """A chunk of ONE prompt after the ``done`` rows (whole pages, all
    real) that ran before it: ``tokens`` (C,) at positions ``done +
    0..C-1``, the first ``n`` of them real -> the logits of row ``n -
    1``, both pools, the layers' reports, the state pools.  Every layer
    by the block's ``chunk_mixer``.  Its shape depends on (C, ``done``)
    alone.  Padding rows are not ``live``; those past the sequence's
    page run go to the null page."""
    C, P = tokens.shape[0], block.full_pages
    pos = done + jnp.arange(C, dtype=jnp.int32)
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, pos)
    flat = jnp.where(
        pos < P * page_size,
        table[jnp.minimum(pos // page_size, P - 1)] * page_size, 0) \
        + pos % page_size
    live = jnp.arange(C, dtype=jnp.int32) < n
    cache, chunk = (k_pool, v_pool, *extra), PromptChunk(done, n, flat, table)
    reports = []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, cache = lb.chunk_mixer(lp, x, pos, cache, li, chunk, heads)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, live)
        reports.append(report)
    with jax.named_scope("blk_head"):
        logits = block.head(
            params, jax.lax.dynamic_slice_in_dim(x, n - 1, 1))[0]
    return (logits, *cache[:2], _stack_reports(reports), tuple(cache[2:]))
