"""Block-paged KV storage: host-side allocator + device pool.

The pool is one preallocated device array of ``num_pages`` fixed-size
pages; sequences own disjoint page sets named by their page table, so
ragged contexts share the allocation with zero per-sequence reshapes.
The allocator is pure host bookkeeping (a free list); exhaustion is an
*admission* signal (``PoolExhausted``) so the scheduler refuses new
sequences instead of corrupting live ones — the graceful-degradation
twin of the serving engine's 503 path.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.observability import metrics as _metrics

_M_PAGES_IN_USE = _metrics.gauge(
    "decode_pages_in_use", "KV-cache pages currently owned by sequences")
_M_PAGE_ALLOCS = _metrics.counter(
    "decode_page_allocs_total", "pages handed out by the allocator")
_M_PAGE_FREES = _metrics.counter(
    "decode_page_frees_total", "pages returned to the allocator free list")
_M_PAGE_REFS = _metrics.gauge(
    "decode_page_refs", "total references held on allocated pages "
    "(> pages_in_use means copy-on-write sharing is active)")
_M_PAGES_SHARED = _metrics.gauge(
    "decode_pages_shared", "pages with refcount > 1 (aliased by forks, "
    "beams, or the prefix cache)")
_M_STATE_ENTRIES = _metrics.gauge(
    "decode_state_entries",
    "state entries of the cache manager (one a seated sequence of a "
    "model with recurrent layers), by state: in_use, free")
_M_COW_COPIES = _metrics.counter(
    "decode_cow_copies_total",
    "shared pages copied before a write (copy-on-write splits)")


class PoolExhausted(RuntimeError):
    """The page pool cannot satisfy an allocation: refuse admission."""


class PoolsLost(RuntimeError):
    """A program that had consumed its donated pools failed; the model
    made them anew, empty.  Every page's rows are gone: of every seated
    sequence and of the prefix cache."""


class PageAllocator:
    """Refcounted free-list page allocator.  Pages are ints in
    [0, num_pages).

    Page 0 is reserved as the *null page*: inactive slots' page tables
    point at it, so a fixed-shape gather never indexes freed memory.

    Sharing model (copy-on-write substrate): ``alloc`` hands out pages
    at refcount 1; ``fork`` aliases an existing page run by bumping each
    refcount (the forked sequence, beam sibling, or prefix-cache node
    now co-owns the pages); ``free`` *releases* one reference per page
    and only returns a page to the free list when its count hits zero.
    A writer must check ``is_shared`` first and copy the page before
    mutating it (see ``PagedPool.copy_page`` / the session's CoW step).
    """

    NULL_PAGE = 0

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        # LIFO free list: a just-freed (still-hot) page is reused first
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._refs: dict = {}               # page -> live reference count
        self._in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self._in_use

    @property
    def total_refs(self) -> int:
        return sum(self._refs.values())

    @property
    def pages_shared(self) -> int:
        return sum(1 for c in self._refs.values() if c > 1)

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def is_shared(self, page: int) -> bool:
        return self._refs.get(int(page), 0) > 1

    def _set_gauges(self) -> None:
        _M_PAGES_IN_USE.set(self._in_use)
        _M_PAGE_REFS.set(self.total_refs)
        _M_PAGES_SHARED.set(self.pages_shared)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages (each at refcount 1) or raise
        ``PoolExhausted`` (taking none)."""
        if n > len(self._free):
            raise PoolExhausted(
                f"page pool exhausted: need {n} pages, "
                f"{len(self._free)} free of {self.num_pages - 1} usable")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self._in_use += n
        _M_PAGE_ALLOCS.inc(n)
        self._set_gauges()
        return pages

    def fork(self, pages: Sequence[int]) -> List[int]:
        """Alias an existing page run: bump each page's refcount and
        return the same ids as a fresh list the new owner may mutate
        (list-structurally — the *pages* stay shared until CoW)."""
        out = []
        for p in pages:
            p = int(p)
            if self._refs.get(p, 0) < 1:
                raise ValueError(f"cannot fork unallocated page {p}")
            self._refs[p] += 1
            out.append(p)
        self._set_gauges()
        return out

    def free(self, pages: Sequence[int]) -> List[int]:
        """Release one reference per page; pages whose count hits zero
        return to the free list.  Returns the ids actually freed.
        Releasing a page with no live reference is the double-free
        corruption and raises (covering duplicates inside one call
        whenever they exceed the page's live count)."""
        freed = []
        for p in pages:
            p = int(p)
            if p == self.NULL_PAGE:
                raise ValueError("cannot free the reserved null page")
            if not (0 < p < self.num_pages) or self._refs.get(p, 0) < 1:
                raise ValueError(f"double free / bad page id {p}")
            self._refs[p] -= 1
            if self._refs[p] == 0:
                del self._refs[p]
                self._free.append(p)
                self._in_use -= 1
                freed.append(p)
        _M_PAGE_FREES.inc(len(freed))
        self._set_gauges()
        return freed


class CacheManager(PageAllocator):
    """Pages and, beside them, state entries: the second resource of a
    model whose recurrent layers keep a state of fixed size a sequence
    (``decode/state_entry.py``), from the one object the session asks.
    What an entry holds is the model's: a recurrent state and a conv
    tail, or a window layer's rings (``models/mimo_v2.py``, whose
    ``RingRunManager`` adds a gauge that names both resources).

    A sequence's reservation of ``n`` units is ``n - 1`` pages and ONE
    state entry (the model's ``context_pages`` counts the entry in, as
    a model with rings counts its rings' pages in).  ``alloc(n)`` hands
    out the pages' ids followed by the entry's, which is
    ``num_pages + e`` for entry ``e`` of ``1 .. state_entries - 1``: one
    id space, so that ``free`` takes back whatever list it is given, in
    any order, and a table row can hold both.  Entry 0 is the null
    entry, as page 0 is the null page: what an inactive slot's row
    names.  All or nothing: with too few pages or no entry free,
    ``can_alloc`` is false, ``alloc`` raises ``PoolExhausted`` and
    takes neither, and the request waits.  An entry is one sequence's:
    ``fork`` of a list that holds one raises."""

    def __init__(self, num_pages: int, state_entries: int):
        super().__init__(num_pages)
        if state_entries < 2:
            raise ValueError("need at least 2 state entries (entry 0 is "
                             "reserved)")
        self.state_entries = int(state_entries)
        self._free_entries: List[int] = list(
            range(self.state_entries - 1, 0, -1))
        self.gauge_entries()

    @property
    def free_entries(self) -> int:
        return len(self._free_entries)

    @property
    def entries_in_use(self) -> int:
        return self.state_entries - 1 - len(self._free_entries)

    def entry_of(self, ids: Sequence[int]) -> int:
        """The state entry among a sequence's ``ids`` (0: none)."""
        held = [int(i) - self.num_pages for i in ids
                if int(i) >= self.num_pages]
        if len(held) > 1:
            raise ValueError(f"{len(held)} state entries in one sequence")
        return held[0] if held else self.NULL_PAGE

    def pages_of(self, ids: Sequence[int]) -> List[int]:
        return [int(i) for i in ids if int(i) < self.num_pages]

    def gauge_entries(self) -> None:
        """``decode_state_entries{state}`` as it stands (the session
        sets it every tick; ``alloc`` and ``free`` when it changes)."""
        _M_STATE_ENTRIES.set(self.entries_in_use, state="in_use")
        _M_STATE_ENTRIES.set(self.free_entries, state="free")

    def _set_gauges(self) -> None:
        super()._set_gauges()
        self.gauge_entries()

    def can_alloc(self, n: int) -> bool:
        return bool(self._free_entries) and super().can_alloc(n - 1)

    def alloc(self, n: int) -> List[int]:
        if n < 2:
            raise ValueError("a sequence holds a page at least, and an "
                             "entry")
        if not self._free_entries:
            raise PoolExhausted(
                f"no state entry free of {self.state_entries - 1} usable")
        pages = super().alloc(n - 1)
        entry = self._free_entries.pop()
        self.gauge_entries()
        return pages + [self.num_pages + entry]

    def fork(self, pages: Sequence[int]) -> List[int]:
        if self.entry_of(pages):
            raise ValueError("a state entry is one sequence's: it cannot "
                             "be forked")
        return super().fork(pages)

    def free(self, pages: Sequence[int]) -> List[int]:
        entry = self.entry_of(pages)
        if entry and not (0 < entry < self.state_entries
                          and entry not in self._free_entries):
            raise ValueError(f"double free / bad state entry {entry}")
        freed = super().free(self.pages_of(pages))
        if entry:
            self._free_entries.append(entry)
            self.gauge_entries()
        return freed


def _scatter_pages(pool, idx, buf):
    return pool.at[idx].set(buf)


def _scatter_row(pool, page, off, row):
    return pool.at[page, off].set(row)


def _copy_page(pool, src, dst):
    return pool.at[dst].set(pool[src])


class PagedPool:
    """Device-resident page pool: ``(num_pages, page_size) + feature``.

    The array lives as a ``jax.Array`` and is updated functionally —
    every write returns the new pool value, which callers feed back
    into the fixed-shape decode program (feeding a device array is
    zero-copy through the executor's feed conversion).  Writes go
    through jitted scatters (one compile per page-count, then ~50us
    dispatches): an eager ``.at[].set`` costs ~0.6 ms per call on CPU,
    which dominated per-sequence prefill before batching even starts.
    """

    def __init__(self, num_pages: int, page_size: int,
                 feature_shape: Tuple[int, ...], dtype="float32"):
        import jax.numpy as jnp

        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.feature_shape = tuple(int(d) for d in feature_shape)
        self.allocator = PageAllocator(num_pages)
        self.data = jnp.zeros(
            (self.num_pages, self.page_size) + self.feature_shape, dtype)
        import jax

        # not donated, so each write copies the pool: these writes are
        # off the per-token path (one per admission or appended row of
        # the seq2seq adapter, the only user; the decoder-only models
        # keep their pools in ``decode/model.py`` and donate them)
        self._scatter = jax.jit(_scatter_pages)
        self._scatter_one = jax.jit(_scatter_row)
        self._copy = jax.jit(_copy_page)

    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` rows."""
        return max(1, -(-int(length) // self.page_size))

    def write_rows(self, pages: Sequence[int], rows: np.ndarray) -> None:
        """Write ``rows`` (T, *feature) into ``pages`` front-to-back,
        zero-padding the final partial page."""
        import jax.numpy as jnp

        n = len(pages)
        cap = n * self.page_size
        if rows.shape[0] > cap:
            raise ValueError(
                f"{rows.shape[0]} rows do not fit {n} pages "
                f"({cap} row capacity)")
        buf = np.zeros((cap,) + self.feature_shape, self.data.dtype)
        buf[:rows.shape[0]] = rows
        buf = buf.reshape((n, self.page_size) + self.feature_shape)
        self.data = self._scatter(
            self.data, jnp.asarray(np.asarray(pages, np.int32)), buf)

    def append_row(self, pages: Sequence[int], position: int,
                   row: np.ndarray) -> None:
        """Write one row at logical ``position`` within the sequence's
        pages (the growing-KV decode case)."""
        page = pages[position // self.page_size]
        off = position % self.page_size
        self.data = self._scatter_one(
            self.data, np.int32(page), np.int32(off),
            np.asarray(row, self.data.dtype))

    def copy_page(self, src: int, dst: int) -> None:
        """Device copy of one page's rows (the CoW split)."""
        self.data = self._copy(self.data, np.int32(src), np.int32(dst))
        _M_COW_COPIES.inc()

    def page_table(self, pages: Sequence[int], width: int) -> np.ndarray:
        """Fixed-width page-table row, null-padded past the owned pages."""
        t = np.full((width,), PageAllocator.NULL_PAGE, np.int32)
        t[:len(pages)] = np.asarray(pages, np.int32)
        return t


class SequencePages:
    """One sequence's page ownership + logical length."""

    __slots__ = ("pages", "length", "capacity")

    def __init__(self, pages: List[int], length: int, page_size: int):
        self.pages = pages
        self.length = int(length)
        self.capacity = len(pages) * page_size

    def grow_needed(self) -> bool:
        return self.length >= self.capacity


def alloc_sequence(pool: PagedPool, length: int,
                   reserve_growth: int = 0) -> SequencePages:
    """Allocate pages for a ``length``-row context (+ optional headroom
    for per-step KV growth).  Raises ``PoolExhausted`` without partial
    allocation."""
    n = pool.pages_for(max(1, length + reserve_growth))
    pages = pool.allocator.alloc(n)
    return SequencePages(pages, length, pool.page_size)


def fork_sequence(pool: PagedPool, seq: SequencePages) -> SequencePages:
    """Alias ``seq``'s pages into a new SequencePages (refcounts bumped);
    the fork diverges from its parent page-by-page via CoW writes."""
    return SequencePages(pool.allocator.fork(seq.pages), seq.length,
                         pool.page_size)


def free_sequence(pool: PagedPool, seq: Optional[SequencePages]) -> None:
    if seq is not None and seq.pages:
        pool.allocator.free(seq.pages)
        seq.pages = []


def cow_split(allocator: PageAllocator, pages: List[int], page_idx: int,
              copiers) -> Optional[int]:
    """Make ``pages[page_idx]`` private before a write: when shared,
    allocate a fresh page, run each ``copier(src, dst)`` device copy,
    release the shared original, and patch the page list in place.
    Returns the new page id (or None when the page was already private).
    Raises ``PoolExhausted`` without touching anything when no page is
    free for the copy; a copy that raises gives the fresh page back."""
    old = pages[page_idx]
    if not allocator.is_shared(old):
        return None
    (new,) = allocator.alloc(1)
    try:
        for copy in copiers:
            copy(old, new)
    except BaseException:
        allocator.free([new])
        raise
    allocator.free([old])
    pages[page_idx] = new
    return new
