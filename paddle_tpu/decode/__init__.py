"""Paged-KV decode engine: ragged batched generation.

The serving engine (paddle_tpu/serving, PR 13) coalesces dense batches
but falls back to solo execution for ragged/LoD models — exactly the
shape of autoregressive generation.  This package closes that gap with
the design from "Ragged Paged Attention" (PAPERS.md): sequences of
different lengths share one preallocated device pool of fixed-size
*pages*; a per-sequence page table names which pages hold its context;
and the decode step is ONE fixed-shape compiled program over
``(pool, page_tables, lengths, tokens, states)`` that never re-traces
as sequences join and finish.

Pieces:

- ``paged_kv``    — host-side page allocator (free-list reuse, pool
                    exhaustion -> admission refusal) + the device pool
                    writer helpers.
- ``attention``   — the Pallas ragged paged-attention decode kernel
                    (one query token per slot attending over its page
                    table) + a jnp reference, and the dense-prefill
                    path reusing ``pallas/flash_attention``.
- ``session``     — ``DecodeSession``: continuous batching at token
                    granularity.  Each step: admit pending sequences
                    into open slots (prefill joins), run one fixed-shape
                    decode step for every active slot, evict finished
                    sequences and return their pages to the pool.
- ``seq2seq``     — ``PagedSeq2SeqModel``: adapts a v1 ``beam_search``
                    spec (the NMT demo) to the session — prefill runs
                    the encoder once and writes its states into pages;
                    the decode step attends over the paged context
                    through the verifier-checked Program executor.
- ``model``       — ``TinyDecoderLM``: a pure-JAX decoder-only
                    transformer whose decode step consumes the ragged
                    paged-attention kernel directly (growing KV: each
                    step appends one K/V row into the sequence's pages).
- ``engine``      — ``GenerationEngine``: the serving front (background
                    stepper thread, admission control, token streaming)
                    that ``paddle serve`` mounts at ``POST /generate``.
"""

from paddle_tpu.decode.paged_kv import (
    PageAllocator,
    PagedPool,
    PoolExhausted,
    PoolsLost,
)
from paddle_tpu.decode.session import (
    AdmissionRefused,
    DecodeRequest,
    DecodeSession,
)
from paddle_tpu.decode.seq2seq import PagedSeq2SeqModel
from paddle_tpu.decode.engine import GenerationEngine

__all__ = [
    "AdmissionRefused", "DecodeRequest", "DecodeSession",
    "GenerationEngine", "PageAllocator", "PagedPool",
    "PagedSeq2SeqModel", "PoolExhausted", "PoolsLost",
]
