"""Decoder-only LMs over paged KV: the skeleton, and the GPT-2 block.

``PagedDecoderLM`` is the self-attention consumer of the ragged
paged-attention kernel (the seq2seq adapter pages a *static*
cross-attention context; this exercises the growing-KV case), written
ONCE over a *block*: pure functions of (parameters, rows, positions)
for the embedding, the pre-attention norm and q/k/v, the
post-attention projection, the feed-forward and the head.  The GPT-2
block (``Gpt2Block``, served as ``TinyDecoderLM``) is defined here;
OLMoE's lives in ``paddle_tpu/models/olmoe.py``, K-EXAONE's in
``paddle_tpu/models/exaone_moe.py``.  The jitted programs take the
block as a static argument.  A block also says how a layer mixes
tokens and what it keeps of them (``PageRunCache``: attention, every
layer every row, in one page run a sequence; K-EXAONE's window layers
keep a bounded ring each, beside a full layer, in the same pool;
MiMo-V2.5's keep their rings in an ENTRY a sequence beside SEVERAL full
layers' page run, K and V pools of different widths:
``models/mimo_v2.py``, over ``decode/state_entry.py``;
a latent layer keeps ONE compressed row a token in the place of K and V
heads, the first pool alone, and defines both mixers itself:
``models/kanana_mla.py``;
a hybrid's recurrent layers keep a state a sequence in buffers of
their own, ``extra``, beside the pages of its attention layers:
``decode/state_entry.py``, under ``models/olmo_hybrid.py``,
``models/granite_hybrid.py`` and, the pages holding latent rows,
``models/ling_hybrid.py``; a decoder-hybrid-decoder has layers that
keep NOTHING: they read another layer's page run, or an activation an
earlier layer hands on inside the program, and its prefill goes on with
the prompt's last row alone from the layer after which no other row
reaches a cache: ``models/phi4_flash.py``).

A LAYER is what the block says it is.  The loops below ask every layer
for its mixer and then for its feed-forward, each under its scope
(``blk_mixer``, ``blk_mlp``) with its own pre-norm and residual inside;
a block whose layers are ONE part alone (Nemotron-H: a Mamba-2 layer, an
attention layer or an expert layer, ``x <- x + part(norm(x))``) answers
for the part a layer does not have with the rows as they came, keeps
None of the prompt there and reports None: no instruction is emitted
under that scope, so ``blk_mixer`` still times the mixers and
``blk_mlp`` the feed-forwards (``models/nemotron_h.py``).

Prefill is ONE jitted program per length *bucket* (the shared pow2
ladder of ``paddle_tpu/bucket.py``, from 64 up to the sequence
capacity).  The prompt is padded on the right to its bucket, the
program runs the dense causal forward (``dense_prefill_attention`` —
the flash-attention path when the bucket's shape fits), scatters the
K/V rows into the donated pools in place and returns the logits of the
last real token alone.  Causal attention hides the padding from every
real row; the padding's own K/V rows land past the prompt's length
inside the sequence's pages (never read: ``lens`` masks them, decode
overwrites them) or, past its pages, in the reserved null page 0.
Every decode step appends one K/V row per sequence into its pages and
attends over its page table.  The decode step is ONE jitted
fixed-shape function of ``(pools, page_tables, lens, tokens)`` — batch
composition churn never re-traces.  It also chooses each slot's greedy
token on the device: the host is handed ``(S,)`` ids, and the
vocabulary-wide logits stay on the device unless somebody reads them
(``StepLogits``).  And it hands on what the next step reads: its ids
are the next tokens, ``lens + live`` the next lengths, and the tables
it was given stay where they are, so a step can be entered with what
the device already holds (``StepInFlight.next``) and is called in two
halves, ``step_dispatch`` and ``step_collect``.

Every program here takes the cache's buffers DONATED (both pools, and
the block's ``extra`` ones) and hands back the same buffers: a layer's
new rows are one scatter into the pool seen flat (``_write_rows``), and
the kernels read the whole pool through page tables moved by the
layer's offset (``_layer_pages``), so no program produces a value of a
pool's or a layer slab's size.  A donated call that fails on the device
has consumed them all: ``_donating``.

Weights are randomly initialized from a seed: these models exist to
prove the kernel + session mechanics (tests pin the paged decode
against a dense incremental oracle and a float32 reference) and to
feed the benchmark, not to be trained LMs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.bucket import bucket_dim
from paddle_tpu.decode.attention import (
    dense_prefill_attention,
    paged_attention,
    paged_chunk_attention,
)
from paddle_tpu.decode.paged_kv import PageAllocator, PoolsLost
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability.events import phase

_M_PREFILL_TOKENS = _metrics.counter(
    "decode_prefill_tokens_total",
    "prompt tokens of the bucketed (full-prompt) prefills")
_M_PREFILL_PADDED = _metrics.counter(
    "decode_prefill_padded_tokens_total",
    "rows the bucketed prefills computed: each prompt padded to its "
    "bucket; over decode_prefill_tokens_total it is what buckets cost")
_M_PREFILL_PROGRAMS = _metrics.counter(
    "decode_prefill_programs_total",
    "prefill programs traced, by bucket (one per bucket and model "
    "shape; a rise under traffic is a compile in the serving path)")

_M_POOL_REBUILDS = _metrics.counter(
    "decode_pool_rebuilds_total",
    "times a program that had consumed its donated K/V pools failed and "
    "both pools were made anew, every page's rows lost")

_F32 = jnp.float32


def _init_params(key, vocab, d, heads, layers, max_len):
    ks = jax.random.split(key, 2 + layers)
    s = 0.02
    params = {
        "emb": jax.random.normal(ks[0], (vocab, d), _F32) * s,
        "pos": jax.random.normal(ks[1], (max_len, d), _F32) * s,
        "ln_f": jnp.ones((d,), _F32),
        "layers": [],
    }
    for i in range(layers):
        lk = jax.random.split(ks[2 + i], 6)
        params["layers"].append({
            "ln1": jnp.ones((d,), _F32),
            "ln2": jnp.ones((d,), _F32),
            "wq": jax.random.normal(lk[0], (d, d), _F32) * s,
            "wk": jax.random.normal(lk[1], (d, d), _F32) * s,
            "wv": jax.random.normal(lk[2], (d, d), _F32) * s,
            "wo": jax.random.normal(lk[3], (d, d), _F32) * s,
            "w1": jax.random.normal(lk[4], (d, 4 * d), _F32) * s,
            "w2": jax.random.normal(lk[5], (4 * d, d), _F32) * s,
        })
    return params


def _ln(x, scale):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * scale


class Addressing(NamedTuple):
    """Where the rows of a step go and what they see, as the skeleton
    reckons it for every model: the flat row of the page run each new
    row lands in, the slots' table rows (a block may keep more in a row
    than the page run: rings, a state entry) and the rows each slot had
    cached before the step."""

    flat: jax.Array
    tables: jax.Array
    lens: jax.Array


class PageRunCache:
    """The cache side of a block, as the programs below ask for it, for
    a model whose every layer keeps every row of a sequence in the one
    page run the session gave it: pools ``(L, N, pg, H, dh)``, layer
    ``li`` reached by moving the same table by ``li * N``.

    The programs ask a layer for its token mixer (``prompt_mixer`` over
    a whole prompt, ``mixer`` over a step's rows) and thread the block's
    cache through it: the two K/V pools and whatever further buffers the
    block keeps (``extra``), all donated.  The default mixer is
    attention over the page run: ``qkv``, the three calls below,
    ``attn_out``.  A block whose layers differ in what they keep
    defines the three itself (``models/exaone_moe.py``: window layers
    on rings beside a full layer); one whose layers mix tokens another
    way defines the mixers (``decode/state_entry.py``: a recurrent
    state a sequence beside the pages of the attention layers)."""

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        """Layer ``self.at`` over one whole prompt ``x`` (T, d) ->
        (the rows after the mixer's residual, what the layer keeps of
        the prompt for ``store_prompts``).  ``live`` (T,) bool or None:
        the rows that are not the bucket's padding, which causal
        attention need not be told.  ``kept``: what the layers before
        this one keep, for a layer that reads ANOTHER layer's cache or
        an activation it hands on; ``last``: the one row whose logits
        are wanted, or None for all: a layer from which on no other row
        reaches a cache may hand back that row alone, (1, d), and the
        layers after it get it (``models/phi4_flash.py``)."""
        q, k, v = self.qkv(lp, x, pos, heads)
        a = self.prompt_attention(q, k, v)
        return self.attn_out(lp, x, a.reshape(x.shape[0], -1)), (k, v)

    def store_prompts(self, cache, kept, where):
        """``cache`` with what every layer ``kept`` of one prompt
        written at ``where`` (the model's ``_prompt_rows``: here the
        page run's flat row of each bucket row; a block that stores
        another way takes what its model reckons, pages for
        ``models/phi4_flash.py``)."""
        k_pool, v_pool = cache
        ks = jnp.stack([k for k, _ in kept])
        vs = jnp.stack([v for _, v in kept])
        return (self.store_prompt(k_pool, ks, where),
                self.store_prompt(v_pool, vs, where))

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        """Layer ``li`` of a step over the rows ``x`` ((S, d) a decode
        step's, (S, T, d) a chunk's; ``lone``: (T, d), one sequence's
        suffix, ``addr`` its table row and length) -> (the rows after
        the mixer's residual, the cache's buffers written in place)."""
        q, k, v = self.qkv(lp, x, pos, heads)
        if lone:
            a, k_pool, v_pool = self.cached_attention(
                *cache, li, q[None], k, v, addr.flat, addr.tables[None],
                addr.lens)
            a = a[0]
        else:
            a, k_pool, v_pool = self.cached_attention(
                *cache, li, q, k, v, addr.flat, addr.tables, addr.lens)
        return (self.attn_out(lp, x, a.reshape(x.shape[:-1] + (-1,))),
                (k_pool, v_pool))

    def layer(self, li):
        """The block as layer ``li`` has it: every layer the same."""
        return self

    def prompt_attention(self, q, k, v):
        """Attention of one whole prompt, (T, heads, dh) each."""
        with jax.named_scope("attn_full"):
            return dense_prefill_attention(q, k, v, causal=True)

    def store_prompt(self, pool, rows, flat):
        """``pool`` with a prompt's ``rows`` (L, T, H, dh) written:
        row ``i`` of every layer at flat pool row ``flat[i]``."""
        L, N, pg, H, dh = pool.shape
        return (pool.reshape(L, N * pg, H, dh).at[:, flat]
                .set(rows.astype(pool.dtype)).reshape(L, N, pg, H, dh))

    def cached_attention(self, k_pool, v_pool, li, q, k, v, flat, tables,
                         lens):
        """Layer ``li`` of a step: the new K/V rows written at the flat
        rows ``flat`` of the donated pools, then ``q`` attending over
        the slots' pages -> (the heads' outputs, shaped as ``q``, both
        pools).  ``q`` (S, H, dh) is a decode step's, one row a slot
        after ``lens`` cached rows; (S, T, H, dh) a chunk's."""
        H, dh = k_pool.shape[3:]
        with jax.named_scope("attn_full"):
            k_pool = _write_rows(k_pool, li, flat, k.reshape(-1, H, dh))
            v_pool = _write_rows(v_pool, li, flat, v.reshape(-1, H, dh))
            pages = _layer_pages(k_pool, v_pool, li, tables)
            a = (paged_attention(q, *pages, lens + 1) if q.ndim == 3
                 else paged_chunk_attention(q, *pages, lens))
        return a, k_pool, v_pool


@dataclasses.dataclass(frozen=True)
class Gpt2Block(PageRunCache):
    """The GPT-2 block as the paged skeleton below takes a block: pure
    functions of (parameters, rows, positions), hashable so that the
    jitted programs take it as a static argument.  ``rows`` have any
    leading shape ((T,), (S,) or (S, T)); ``pos`` their absolute
    positions, same shape (``embed`` also takes the slice ``0:T`` from
    the dense forward, so that a position table is sliced, not
    gathered).

    Learned positions added to the embedding, pre-LayerNorm, no bias
    anywhere, tanh-GELU, the head tied to the embedding, float32."""

    def embed(self, params, tokens, pos):
        return params["emb"][tokens] + params["pos"][pos]

    def qkv(self, lp, x, pos, heads):
        """Pre-attention norm and the three projections, each split to
        (..., heads, dh); ``k`` and ``v`` are the rows the page gets."""
        h = _ln(x, lp["ln1"])
        split = x.shape[:-1] + (heads, x.shape[-1] // heads)
        return ((h @ lp["wq"]).reshape(split), (h @ lp["wk"]).reshape(split),
                (h @ lp["wv"]).reshape(split))

    def attn_out(self, lp, x, a):
        """``a`` (..., d): the heads' outputs side by side."""
        return x + a @ lp["wo"]

    def mlp(self, lp, x, live):
        """-> (rows after the feed-forward, what the layer reports of
        this call or None).  ``live`` (rows' leading shape, bool; None:
        all) marks the rows that are neither padding nor an inactive
        slot's."""
        h2 = _ln(x, lp["ln2"])
        return x + jax.nn.gelu(h2 @ lp["w1"]) @ lp["w2"], None

    def head(self, params, x):
        return _ln(x, params["ln_f"]) @ params["emb"].T


GPT2 = Gpt2Block()


def _stack_reports(reports):
    """Per-layer reports of ``block.mlp`` as one array (L, ...), over
    the layers that report alone (a layer that is a mixer and nothing
    else reports None: ``models/nemotron_h.py``), or None for a block
    that reports nothing."""
    reports = [r for r in reports if r is not None]
    return jnp.stack(reports) if reports else None


def _dense_blocks(block, params, tokens, heads, live, last=None):
    """The dense causal forward over (T,) tokens up to the head: the
    last block's output, what each layer keeps of the prompt
    (attention: its K/V rows (T, heads, dh)) and the layers' reports.
    The output is every row's (T, d), or with ``last`` (a prefill's row
    ``n - 1``) that row's alone (1, d): a block may then stop the other
    rows at the layer after which they reach no cache
    (``PageRunCache.prompt_mixer``), and the layers below it run on the
    one row.  Pure: the eager oracle and the jitted prefill both run
    it."""
    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, slice(0, T))
    kept, reports = [], []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, keep = lb.prompt_mixer(lp, x, pos, heads, live, kept, last)
        if x.shape[0] != pos.shape[0]:
            pos, live = last[None], None     # row ``last`` alone goes on
        kept.append(keep)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, live)
        reports.append(report)
    if last is not None and x.shape[0] != 1:
        x = jax.lax.dynamic_slice_in_dim(x, last, 1)
    return x, kept, _stack_reports(reports)


class StepLogits:
    """What a decode or verify step hands the host in place of its
    logits: ``ids``, the greedy choice the step made of them on the
    device (int32, the logits' shape less the vocabulary, on the host;
    the first index on a tie, as ``np.argmax``), and the logits
    themselves, left on the device until somebody indexes or converts
    this — then the whole array comes to the host (jax keeps that
    copy, so it comes once)."""

    __slots__ = ("ids", "_dev")

    def __init__(self, dev, ids):
        self.ids, self._dev = ids, dev

    @property
    def shape(self):
        return self._dev.shape

    def __array__(self, dtype=None, copy=None):
        return self._dev.__array__(dtype, copy=copy)

    def __getitem__(self, idx):
        return np.asarray(self)[idx]


class StepInFlight:
    """A step between its two halves: dispatched, its results not yet
    waited for.  ``next`` maps ``tokens`` / ``tables`` / ``lens`` to
    what the device holds of them for the step after this one (a decode
    step's ids, the tables it was given, ``lens + live``); any of them
    can be handed to the next ``step_dispatch`` in place of the host's
    array, which then uploads nothing for it, before this step has been
    collected too.  None after a verify step, whose accepted counts
    only the host knows."""

    __slots__ = ("next", "_pools_in", "_logits", "_ids", "_report")

    def __init__(self, pools_in, logits, ids, report, next_inputs):
        self._pools_in = pools_in
        self._logits, self._ids, self._report = logits, ids, report
        self.next = next_inputs


class PagedDecoderLM:
    """The paged skeleton of a decoder-only LM behind ``/generate``:
    page allocator and tables, the bucketed prefill, suffix prefill,
    verify and decode steps over K/V pools, written once over a block
    definition (``Gpt2Block`` here, ``models/olmoe.py``'s).  A subclass
    sets ``block`` and ``params`` (``layers``: a list of one dict a
    layer), then calls ``_make_pools``."""

    grows_kv = True
    supports_prefix_cache = True      # prefill accepts cached_len
    emits_probs = False               # decode returns raw logits
    state_specs: List[Tuple[tuple, type]] = []   # position == KV length
    block = GPT2

    def __init__(self, vocab, d_model, num_heads, num_layers, max_len,
                 page_size, pages_per_seq, bos_id, eos_id):
        self.vocab, self.d = int(vocab), int(d_model)
        self.heads = int(num_heads)
        self.dh = self.d // self.heads
        self.layers = int(num_layers)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        # rows one sequence can hold: its page run's
        self.seq_rows = self.pages_per_seq * self.page_size
        self.bos_id, self.eos_id = int(bos_id), int(eos_id)

    # the block's cache buffers beyond the two K/V pools (a recurrent
    # state's), donated to every program with them
    extra_pools: tuple = ()

    def _make_pools(self, num_pages: int, dtype) -> None:
        self.allocator = PageAllocator(num_pages)
        shape = (self.layers, num_pages, self.page_size, self.heads, self.dh)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype)

    def _cache(self) -> tuple:
        """Every buffer of the cache, as the programs are handed them."""
        return (self.k_pool, self.v_pool, *self.extra_pools)

    def _set_cache(self, k_pool, v_pool, *extra) -> None:
        self.k_pool, self.v_pool, self.extra_pools = k_pool, v_pool, extra

    @contextlib.contextmanager
    def _donating(self, pools_in=None):
        """Round a call of a program that is given the cache's buffers
        DONATED and the wait for its results (a failure on the device
        shows at the wait, not at the dispatch).  A call that failed
        before it consumed them leaves them as they were.  One that
        failed after has lost every page's rows and every state entry:
        all the buffers are made anew together, and ``PoolsLost`` tells
        the session.  A step's wait comes in a later call than its
        dispatch (``step_collect``) and is rounded again, with the
        buffers the dispatch was handed (``pools_in``); no program runs
        between the two but a step dispatched behind it, which that
        failure takes with it (the session drops it: it ran on the
        failed step's pools)."""
        pools_in = pools_in or self._cache()
        try:
            yield
        except BaseException as exc:
            if not any(p.is_deleted() for p in pools_in):
                self._set_cache(*pools_in)
                raise
            self._set_cache(*(jnp.zeros(p.shape, p.dtype)
                              for p in pools_in))
            _M_POOL_REBUILDS.inc()
            raise PoolsLost(
                "a program failed after it had consumed the cache's "
                f"pools ({type(exc).__name__}: {exc}); all were made "
                "anew, empty") from exc

    def _observe(self, phase: str, report, rows: int) -> None:
        """What the layers reported of one prefill or step (None for a
        block that reports nothing) over the ``rows`` rows its program
        handed each layer, on the host."""

    # -- dense forward (prefill + test oracle) ------------------------------

    def _forward(self, tokens: jnp.ndarray):
        """Full dense causal forward over (T,) tokens -> (logits (T, V),
        per-layer K/V rows (L, T, heads, dh))."""
        x, kept, _ = _dense_blocks(self.block, self.params, tokens,
                                   self.heads, None)
        return (self.block.head(self.params, x),
                jnp.stack([k for k, _ in kept]),
                jnp.stack([v for _, v in kept]))

    def dense_greedy(self, prompt: Sequence[int],
                     max_new_tokens: int) -> List[int]:
        """The no-cache oracle: re-run the full forward per token."""
        ids = list(prompt)
        out = []
        for _ in range(max_new_tokens):
            logits, _, _ = self._forward(jnp.asarray(ids, jnp.int32))
            tok = int(jnp.argmax(logits[-1]))
            out.append(tok)
            if tok == self.eos_id:
                break
            ids.append(tok)
        return out

    # -- session contract ---------------------------------------------------

    def context_pages(self, prompt, max_new_tokens: int) -> int:
        total = len(prompt) + int(max_new_tokens)
        return max(1, -(-total // self.page_size))

    def pool_table(self, pages: Sequence[int]) -> np.ndarray:
        t = np.zeros((self.pages_per_seq,), np.int32)
        t[:len(pages)] = np.asarray(pages, np.int32)
        return t

    def prefill_bucket(self, n: int) -> int:
        """Rows the prefill program of an ``n``-token prompt computes:
        the next power of two from 64 (or the page size) up, capped at
        what one sequence can hold — a model smaller than 64 rows has
        that capacity as its one bucket."""
        cap = self.prefill_cap
        if not 0 < n <= cap:
            raise ValueError(
                f"a prompt of {n} tokens is outside 1..{cap}, the rows "
                "one sequence of this model can hold")
        return min(max(bucket_dim(n), 64, self.page_size), cap)

    @property
    def prefill_cap(self) -> int:
        """The ladder's top bucket: the rows one sequence can hold."""
        return min(self.max_len, self.seq_rows)

    def packed_prefill_rows(self, n: int) -> int:
        """Rows the ladder would compute for ``n`` prompt rows laid end
        to end in as few programs as hold them: top buckets while they
        fill one, then the rest's own bucket.  No program packs prompts
        so; the tick's account sets it beside the rows its admissions
        did run (``decode_admit_tick_rows_total``)."""
        whole, rest = divmod(n, self.prefill_cap)
        return whole * self.prefill_cap + (
            self.prefill_bucket(rest) if rest else 0)

    def prefill(self, prompt: Sequence[int], pages: Sequence[int],
                cached_len: int = 0):
        """Page the prompt's K/V and return (ctx_len, states, last
        logits).  The prompt is padded on the right to its bucket and
        runs as that bucket's one jitted program, whatever its page
        count: the K/V of bucket rows past ``pages`` go to the null
        page 0, those past the prompt inside ``pages`` are padding
        that ``lens`` hides and decode overwrites.  The pools are
        donated to the program, and the call returns once the logits
        row is on the host.

        With ``cached_len`` > 0 (a prefix-cache hit) the first
        ``cached_len`` rows already live in ``pages`` — only the suffix
        is computed, attending over the cached pages through the chunked
        paged kernel, and only the suffix's K/V rows are written."""
        T = len(prompt)
        if cached_len:
            toks = jnp.asarray(list(prompt), jnp.int32)
            if not (0 < cached_len < T and cached_len % self.page_size == 0):
                raise ValueError(
                    f"cached_len {cached_len} must be a positive multiple "
                    f"of page_size strictly inside the {T}-token prompt")
            table = self.pool_table(pages)
            with self._donating():
                logits, k_pool, v_pool, report, extra = _prefill_chunk(
                    self.params, self.k_pool, self.v_pool,
                    jnp.asarray(table), np.int32(cached_len),
                    toks[cached_len:], heads=self.heads,
                    page_size=self.page_size, block=self.block,
                    extra=self.extra_pools)
                self._set_cache(k_pool, v_pool, *extra)
                with phase("decode.prefill_wait"):
                    logits = np.asarray(logits[-1])
                self._observe("prefill", report, T - cached_len)
            return T, [], logits
        bucket = self.prefill_bucket(T)
        toks = np.zeros((bucket,), np.int32)
        toks[:T] = prompt
        flat = self._prompt_rows(pages, bucket, T)
        with self._donating():
            logits, k_pool, v_pool, report, extra = _prefill_bucket(
                self.params, self.k_pool, self.v_pool, toks, flat,
                np.int32(T), heads=self.heads, block=self.block,
                extra=self.extra_pools)
            self._set_cache(k_pool, v_pool, *extra)
            # the program's run and the row's copy to the host: what of
            # `decode.prefill` a device program covers
            with phase("decode.prefill_wait"):
                logits = np.asarray(logits)
            self._observe("prefill", report, bucket)
        _M_PREFILL_TOKENS.inc(T)
        _M_PREFILL_PADDED.inc(bucket)
        return T, [], logits

    def _prompt_rows(self, pages, bucket: int, n: int) -> np.ndarray:
        """The flat pool row of each bucket row of an ``n``-token
        prompt: the table is null past the sequence's pages, so those
        rows scribble on page 0, as inactive decode slots do."""
        rows = np.arange(bucket)
        return (self.pool_table(pages)[rows // self.page_size]
                * self.page_size + rows % self.page_size).astype(np.int32)

    def cache_rows(self, lens) -> dict:
        """K/V rows resident for sequences of ``lens`` rows, by kind of
        cache, summed over layers: every layer keeps every row."""
        return {"full": int(np.sum(lens)) * self.layers}

    def copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page across both pools (the CoW split)."""
        with self._donating():
            self.k_pool, self.v_pool = _copy_pools_page(
                self.k_pool, self.v_pool, np.int32(src), np.int32(dst))

    def verify_chunk(self, tokens: np.ndarray, states, tables: np.ndarray,
                     lens: np.ndarray):
        """Speculative verification: feed ``k`` tokens per slot in ONE
        step (tokens (S, k)), appending all k K/V rows and attending
        with per-row causal offsets.  Returns logits (S, k, V) — row j
        scores the token *after* tokens[:, j] — as ``StepLogits``,
        whose ``ids`` are (S, k).  Rollback of rejected
        rows is the caller's business: stale K/V past ``lens`` is
        unreachable through the length mask."""
        return self.step_collect(
            self._dispatch(_verify_step, tokens, tables, lens))

    def decode(self, tokens: np.ndarray, states, tables: np.ndarray,
               lens: np.ndarray):
        """-> (logits (S, V) as ``StepLogits``, whose ``ids`` are (S,),
        new states).  The one-shot call: the two halves back to back,
        with everything uploaded."""
        return self.step_collect(
            self.step_dispatch(tokens, states, tables, lens))

    def step_dispatch(self, tokens, states, tables, lens) -> StepInFlight:
        """The first half of ``decode``: upload what the host hands in
        and dispatch the step; returns before the device is done.  Each
        of ``tokens`` (S, 1), ``tables`` and ``lens`` is the host's
        numpy array, or what the previous step's ``next`` holds of it
        on the device: a steady tick hands in all three of those and
        uploads nothing.  Until ``step_collect`` has been called on the
        step, the one program of this model that may be is another
        ``step_dispatch`` fed by this step's ``next`` alone (the device
        runs the two back to back; nothing of ``next`` is donated, so
        this step's ids stay readable); the steps are collected in the
        order of their dispatch."""
        if isinstance(tokens, np.ndarray):
            tokens = tokens[:, 0]
        return self._dispatch(_decode_step, tokens, tables, lens)

    def step_collect(self, step: StepInFlight):
        """The second half: wait for the device and for the copy of
        what the host always wants of a step, the ids it chose and the
        block's report -> (``StepLogits``, new states).  The logits
        stay where they are.  A failure on the device shows here."""
        with self._donating(step._pools_in):
            with phase("decode.logits_to_host"):
                ids = np.asarray(step._ids)
                self._observe("decode", step._report, ids.size)
                return StepLogits(step._logits, ids), []

    def _dispatch(self, jitted, tokens, tables, lens) -> StepInFlight:
        """Dispatch one jitted step over every slot.  What comes as a
        numpy array is uploaded first (``decode.upload``, written only
        when something is)."""
        inputs = (tables, lens, tokens)
        if any(isinstance(a, np.ndarray) for a in inputs):
            with phase("decode.upload"):
                tables, lens, tokens = (
                    jnp.asarray(a.astype(np.int32))
                    if isinstance(a, np.ndarray) else a for a in inputs)
        pools_in = self._cache()
        with self._donating(pools_in):
            with phase("decode.dispatch"):
                logits, k_pool, v_pool, report, ids, *more, extra = jitted(
                    self.params, *pools_in[:2], tables, lens, tokens,
                    heads=self.heads, page_size=self.page_size,
                    block=self.block, extra=pools_in[2:])
                self._set_cache(k_pool, v_pool, *extra)
                # queued behind the step, so the collect's wait ends with
                # both on the host and asks the device for nothing more
                ids.copy_to_host_async()
                if report is not None:
                    report.copy_to_host_async()
        handed_on = ({"tokens": ids, "tables": tables, "lens": more[0]}
                     if more else None)
        return StepInFlight(pools_in, logits, ids, report, handed_on)


class TinyDecoderLM(PagedDecoderLM):
    """The GPT-2 block over the paged skeleton, float32 end to end."""

    block = GPT2

    def __init__(self, vocab: int = 64, d_model: int = 32,
                 num_heads: int = 4, num_layers: int = 2,
                 max_len: int = 64, num_pages: int = 32,
                 page_size: int = 8, pages_per_seq: int = 8,
                 bos_id: int = 1, eos_id: int = 0, seed: int = 0):
        super().__init__(vocab, d_model, num_heads, num_layers, max_len,
                         page_size, pages_per_seq, bos_id, eos_id)
        self.params = _init_params(jax.random.key(seed), vocab, self.d,
                                   self.heads, self.layers, self.max_len)
        self._make_pools(num_pages, _F32)


def _write_rows(pool, li, flat, rows):
    """``pool`` with layer ``li``'s ``rows`` (R, H, dh) written at its
    flat rows ``flat`` (R,): one scatter into the pool's own (donated)
    buffer.  The pool seen as (L * N * pg, H, dh) is a bitcast, and
    layer ``li``'s rows start at ``li * N * pg``."""
    L, N, pg, H, dh = pool.shape
    return (pool.reshape(L * N * pg, H, dh).at[li * N * pg + flat]
            .set(rows.astype(pool.dtype)).reshape(pool.shape))


def _layer_pages(k_pool, v_pool, li, tables):
    """What the paged kernels take for layer ``li``: they pick every
    K/V block through the page table, so they get each whole pool as
    (L * N, pg, H, dh) pages (a bitcast, no slab) and the tables moved
    by ``li * N``.  The null page 0 of an inactive slot becomes layer
    ``li``'s own page 0."""
    L, N, pg, H, dh = k_pool.shape
    return (k_pool.reshape(L * N, pg, H, dh),
            v_pool.reshape(L * N, pg, H, dh), tables + li * N)


@functools.partial(jax.jit, static_argnames=("heads", "block"),
                   donate_argnums=(1, 2), donate_argnames=("extra",))
def _prefill_bucket(params, k_pool, v_pool, tokens, flat, n, *, heads,
                    block=GPT2, extra=()):
    """The whole prefill of one prompt padded to ``tokens.shape[0]``
    rows: the dense forward (of every layer on every row, or of a block
    that stops half-way down, on row ``n - 1`` alone from the layer it
    says), what each layer keeps written to the donated pools at
    ``flat`` (the model's ``_prompt_rows``: K/V row ``i`` at the flat
    pool row ``flat[i]``, or whatever else the block's
    ``store_prompts`` takes as its ``where``), and the logits of row
    ``n - 1`` (the vocabulary-wide head runs on that row alone).  Its
    shape depends on the bucket only, not on the prompt's length or
    pages.
    Rows from ``n`` on are padding: not ``live`` to the block.
    ``extra``: the block's cache buffers beyond the two pools, donated
    with them and handed back last, as by every program here."""
    _M_PREFILL_PROGRAMS.inc(bucket=str(tokens.shape[0]))   # at trace
    live = jnp.arange(tokens.shape[0], dtype=jnp.int32) < n
    last, kept, report = _dense_blocks(block, params, tokens, heads, live,
                                       n - 1)
    with jax.named_scope("blk_store"):
        k_pool, v_pool, *extra = block.store_prompts(
            (k_pool, v_pool, *extra), kept, flat)
    with jax.named_scope("blk_head"):
        logits = block.head(params, last)[0]
    return logits, k_pool, v_pool, report, tuple(extra)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_pools_page(k_pool, v_pool, src, dst):
    return (k_pool.at[:, dst].set(k_pool[:, src]),
            v_pool.at[:, dst].set(v_pool[:, src]))


@functools.partial(jax.jit, static_argnames=("heads", "page_size", "block"),
                   donate_argnums=(1, 2), donate_argnames=("extra",))
def _prefill_chunk(params, k_pool, v_pool, table, cached_len, tokens, *,
                   heads, page_size, block=GPT2, extra=()):
    """Suffix prefill over cached pages: the suffix's Ts tokens are one
    chunk at positions cached_len..cached_len+Ts-1; attention sees the
    cached prefix rows plus the causal part of the suffix itself.
    Retraces per suffix length (the full-prompt prefill does not)."""
    Ts = tokens.shape[0]
    pos = cached_len + jnp.arange(Ts, dtype=jnp.int32)
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, pos)                # (Ts, d)
    flat = table[pos // page_size] * page_size + pos % page_size
    lens1 = cached_len[None] if jnp.ndim(cached_len) == 0 else cached_len
    cache, addr = (k_pool, v_pool, *extra), Addressing(flat, table, lens1)
    reports = []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, cache = lb.mixer(lp, x, pos, cache, li, addr, heads,
                                lone=True)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, None)      # every suffix row is real
        reports.append(report)
    with jax.named_scope("blk_head"):
        logits = block.head(params, x)
    return (logits, *cache[:2], _stack_reports(reports), tuple(cache[2:]))


def _greedy_ids(logits):
    """The greedy choice over the last axis, int32: the first index on
    a tie, as ``np.argmax`` of the same numbers on the host."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("heads", "page_size", "block"),
                   donate_argnums=(1, 2), donate_argnames=("extra",))
def _verify_step(params, k_pool, v_pool, tables, lens, tokens, *,
                 heads, page_size, block=GPT2, extra=()):
    """k tokens for every slot in one step (the speculative verify):
    append all k K/V rows, attend with per-row causal offsets through
    the chunked kernel.  Fixed-shape per (S, k) — compiled once.
    Outputs as ``_decode_step``'s, the ids (S, k)."""
    S, T = tokens.shape
    pos = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (S, T)
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, pos)                # (S, T, d)
    flat = (jnp.take_along_axis(tables, pos // page_size, axis=1)
            * page_size + pos % page_size).reshape(-1)      # (S*T,)
    # an inactive slot holds the null table: its rows are not live
    live = jnp.broadcast_to(tables[:, :1] > 0, (S, T))
    cache, addr = (k_pool, v_pool, *extra), Addressing(flat, tables, lens)
    reports = []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, cache = lb.mixer(lp, x, pos, cache, li, addr, heads)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, live)
        reports.append(report)
    with jax.named_scope("blk_head"):
        logits = block.head(params, x)
        ids = _greedy_ids(logits)
    return (logits, *cache[:2], _stack_reports(reports), ids,
            tuple(cache[2:]))


@functools.partial(jax.jit, static_argnames=("heads", "page_size", "block"),
                   donate_argnums=(1, 2), donate_argnames=("extra",))
def _decode_step(params, k_pool, v_pool, tables, lens, tokens, *,
                 heads, page_size, block=GPT2, extra=()):
    """One token for every slot: append K/V into pages, attend over the
    page tables.  Fixed-shape in every argument — compiled once.
    -> (logits (S, V), both pools, the layers' reports, the greedy
    choice of every slot (S,) int32, inactive slots included, and the
    lengths the next step starts from: ``lens + 1`` where the slot is
    live, so that a step can follow this one on the device's own ids
    and lengths)."""
    S = tokens.shape[0]
    with jax.named_scope("blk_embed"):
        x = block.embed(params, tokens, lens)               # (S, d)
    # flat pool row each slot's new KV lands in: its page at
    # lens // page_size, offset lens % page_size.  Inactive slots hold
    # the null table -> they scribble on reserved page 0, harmlessly.
    flat = (tables[jnp.arange(S), lens // page_size] * page_size
            + lens % page_size)                            # (S,)
    live = tables[:, 0] > 0
    cache, addr = (k_pool, v_pool, *extra), Addressing(flat, tables, lens)
    reports = []
    for li, lp in enumerate(params["layers"]):
        lb = block.layer(li)
        with jax.named_scope("blk_mixer"):
            x, cache = lb.mixer(lp, x, lens, cache, li, addr, heads)
        with jax.named_scope("blk_mlp"):
            x, report = lb.mlp(lp, x, live)
        reports.append(report)
    with jax.named_scope("blk_head"):
        logits = block.head(params, x)
        ids = _greedy_ids(logits)
    # an activation a layer hands to later ones rides behind the buffers
    # in the tuple the mixers thread: the buffers alone are handed back
    return (logits, *cache[:2], _stack_reports(reports), ids,
            lens + live.astype(lens.dtype), tuple(cache[2:2 + len(extra)]))
