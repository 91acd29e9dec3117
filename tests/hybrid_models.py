"""The two models over state entries (``decode/state_entry.py``) at toy
widths, for the tests that hold both to the shared base
(``test_state_entry.py``) and each to its own reference
(``test_olmo_hybrid.py``, ``test_granite_hybrid.py``).  CPU, float32."""

import dataclasses

import numpy as np

import jax.numpy as jnp

from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import olmo_hybrid as oh
from perf.reference import granite_hybrid_block, olmo_hybrid_block


@dataclasses.dataclass(frozen=True)
class Hybrid:
    name: str
    cls: type
    ref: object              # the plain reference's module
    kernel: str              # the state kernel pallas_dispatch_total names
    recurrent: str           # the layer_types entry that keeps a state
    sizes: dict              # off a TPU: the XLA path of a decode step
    kernel_sizes: dict       # sizes the step kernel's fits() accepts
    geometry: tuple          # the block's fields its reference is told

    def make(self, kernel=False, seed=3, **over):
        return self.cls(seed=seed, **{
            **(self.kernel_sizes if kernel else self.sizes), **over})

    @property
    def types(self):
        return self.sizes["layer_types"]


_OLMO = dict(vocab=96, d_model=32, num_heads=4, head_dim=8,
             layer_types=(oh.LINEAR, oh.LINEAR, oh.LINEAR, oh.FULL) * 2,
             intermediate_size=48, linear_num_key_heads=4,
             linear_num_value_heads=4, linear_key_head_dim=6,
             linear_value_head_dim=10, max_len=256, num_pages=40,
             page_size=8, pages_per_seq=32, state_entries=5,
             dtype="float32")
# toy widths that keep the ratios: heads of 64 on fewer K/V heads, two
# mamba heads of 64 channels a row of lanes, a state of 128, a period
# of ten
_GRANITE = dict(vocab=96, d_model=32, num_heads=4, num_kv_heads=2,
                head_dim=64,
                layer_types=(gh.MAMBA,) * 5 + (gh.ATTENTION,)
                + (gh.MAMBA,) * 4,
                intermediate_size=48, mamba_n_heads=2, mamba_d_head=64,
                mamba_d_state=128, max_len=256, num_pages=40, page_size=8,
                pages_per_seq=32, state_entries=5, dtype="float32")

OLMO = Hybrid("olmo", oh.OlmoHybridLM, olmo_hybrid_block,
              "gated_delta_step", oh.LINEAR, _OLMO,
              # d_v a whole tile of 8 rows, and the conv's 4 x (2 x 8 +
              # 16) channels a row of lanes: what the kernels' fits() ask
              {**_OLMO, "linear_key_head_dim": 8,
               "linear_value_head_dim": 16},
              ("lin_heads", "d_k", "d_v"))
GRANITE = Hybrid("granite", gh.GraniteHybridLM, granite_hybrid_block,
                 "ssd_step", gh.MAMBA, _GRANITE, _GRANITE,
                 ("mamba_n_heads", "mamba_d_head", "mamba_d_state"))
HYBRIDS = {h.name: h for h in (OLMO, GRANITE)}
_OF = {h.cls: h for h in HYBRIDS.values()}


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 96, n).tolist()


def reference(m, ids, rows=None, ablate=None):
    """The plain reference's logits for the model's own weights."""
    b, h = m.block, _OF[type(m)]
    return np.asarray(h.ref.forward(
        m.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=m.heads, head_dim=b.head_dim, eps=b.eps, ablate=ablate,
        rows=rows, **{name: getattr(b, name) for name in h.geometry}))


def greedy_by_reference(m, ids, n):
    """The no-cache oracle: the reference's full forward per token
    (``dense_greedy`` runs the block's own dense forward op by op, a
    compile a shape)."""
    ids = list(ids)
    start = len(ids)
    for _ in range(n):
        ids.append(int(np.argmax(reference(m, ids, [len(ids) - 1])[0])))
    return ids[start:]


def through_the_cache(m, ids, tokens, slots=4, slot=2):
    """Prefill through the bucket's program, then the tokens teacher-
    forced through decode steps: the len(tokens) + 1 logits rows."""
    pages = m.allocator.alloc(m.context_pages(ids, len(tokens)))
    try:
        ctx, _, last = m.prefill(ids, pages)
        rows = [np.asarray(last)]
        tables = np.zeros((slots, m.pages_per_seq), np.int32)
        tables[slot] = m.pool_table(pages)
        lens = np.zeros((slots,), np.int32)
        lens[slot] = ctx
        for tok in tokens:
            step = np.full((slots, 1), m.bos_id, np.int64)
            step[slot, 0] = tok
            logits, _ = m.decode(step, [], tables, lens)
            lens[slot] += 1
            rows.append(np.asarray(logits[slot]))
    finally:
        m.allocator.free(pages)
    return np.stack(rows)


def lowered_texts(m, slots=4, bucket=64, platforms=None):
    """The lowered text, with scopes, of the model's decode step and of
    one prefill bucket; ``platforms``: what to lower for instead of the
    backend (``("tpu",)``: kernels as custom calls, nothing runs)."""
    from paddle_tpu.decode import model as dm

    cache = m._cache()

    def text(program, *args, **kw):
        return program.trace(*args, **kw).lower(
            lowering_platforms=platforms).as_text(debug_info=True)

    return {
        "_decode_step": text(
            dm._decode_step, m.params, *cache[:2],
            np.zeros((slots, m.pages_per_seq), np.int32),
            np.zeros((slots,), np.int32), np.zeros((slots,), np.int32),
            heads=m.heads, page_size=m.page_size, block=m.block,
            extra=cache[2:]),
        "_prefill_bucket": text(
            dm._prefill_bucket, m.params, *cache[:2],
            np.zeros((bucket,), np.int32),
            (np.zeros((bucket,), np.int32), np.int32(0)), np.int32(3),
            heads=m.heads, block=m.block, extra=cache[2:])}


def steps_by(h, kernels, ids, tokens):
    """Prefill + the tokens teacher-forced, by the step's Pallas kernels
    interpreted (the state's and ``conv_step``) or by the XLA paths
    alone, each traced afresh -> (the logits rows, the tail pool as the
    steps leave it, how many times ``conv_step`` was dispatched by
    path)."""
    import jax

    from paddle_tpu import pallas as pk
    from paddle_tpu.observability import metrics

    fam = metrics.REGISTRY.get("pallas_dispatch_total")

    def count():
        return {path: fam.value(kernel="conv_step", path=path)
                for path in ("interpret", "reference")}

    before = count()
    pk.enable(bool(kernels), interpret=bool(kernels))
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        m = h.make(kernel=True)
        rows = through_the_cache(m, ids, tokens)
        tails = np.asarray(m.conv_pool)
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()
    return rows, tails, {p: n - before[p] for p, n in count().items()}
