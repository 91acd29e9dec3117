"""Hand-written Pallas TPU kernels for hot ops (north star: the
reference's hand-written CUDA kernels — paddle/operators/math/*.cu,
paddle/cuda/src/hl_cuda_lstm.cu etc. — reimplemented for the MXU/VPU),
and the one place where the choice between a kernel and its jnp/XLA
lowering is made.

Thirteen kernel families, twenty-four ``pl.pallas_call``s: the fused
whole-sequence LSTM (``lstm.py``, 1), the row softmax (``softmax.py``,
1), flash attention forward and backward (``flash_attention.py``, 3;
also run by ring attention's chunks and by the decoder's prefill),
ragged paged attention (``decode/attention.py``, 3 kernel bodies, 5
calls, 4 names: the chunk kernel, a walk of a slot's live pages since
PR 58, is also called on grouped heads, Hq query heads on Hkv K/V
heads, as ``ragged_paged_attention_gqa``, and since PR 60 on the decode
step's one row on ungrouped heads, as ``ragged_paged_attention``, the
name of the first kernel, whose grid step a (slot, table column) is
left the pools whose pages the compiled walk does not take,
``walk_fits``; the chunk's page arithmetic under a window
mask reckoned from positions, a grid step a (slot, ring column), reads
a window layer's ring of pages where they lie in the pool, as
``ring_paged_attention``: taken
for pages stored heads-major, ``decode/attention.py:
paged_ring_attention``; of row-major pages XLA fuses the gather of a
ring into the scores' product and the kernel read slower), the gated
delta rule's one-token step over a decode step's state entries
(``gated_delta.py``, 1), Mamba-2's on the same layout
(``ssd_step.py``, 1), Mamba-1's, whose decay is as large as the state
and is made in VMEM (``s6_step.py``, 1), the depthwise conv before
any of them, over the
same entries' kept rows (``conv_step.py``, 1), the gated delta rule
chunked over a prefill bucket's rows, the state in VMEM from chunk to
chunk (``gated_delta_chunked.py``, 1), both again under a decay that
is a vector over a head's key channels (Kimi Delta Attention,
``kda.py``, 2: the step with a row of decays where a scalar stood, the
chunked rule built a block of 16 query rows at a time against one
reference row, so that no factor of a per-channel ratio leaves
float32), and absorbed latent attention
over paged latent rows, every head on the one stored row, which is key
and value both (``latent_attention.py``, 1: a grid step a slot, the
slot's live pages walked by a dynamic loop and copied by hand through a
double buffer, the pattern of ``decode/attention.py``'s walk; only
the ring's kernel and ``ragged_paged_attention`` on the pages that walk
refuses take a grid step a table column).  The latent pool's rows are stored at 640 lanes for the 576
the algorithm needs: at 576 the chip's compiler lays the pool out at
640 anyway and refuses the kernel's page copy ("slice shape must be
aligned to tiling (128)"; ``tests/test_chip_compile.py``, PR 45).  The last is the grouped GEMM
of a routed layer's experts over a prefill bucket's sorted rows
(``grouped_gemm.py``, 2, one kernel body: ``grouped_gemm`` and, two
matrices a visit and ``silu(g) * u`` written, ``grouped_gemm_gate_up``):
a grid step a (row tile, expert) pair that overlaps, each hit expert's
matrix copied by hand through a double buffer one expert ahead, so read
once; its row tile is ``grouped_gemm.ROW_TILE`` (the MXU's 128 rows,
near the rows a group holds), its column block the widest that stays
resident, and a call of fewer rows than a tile is ``ragged_dot``'s.
Sparse latent attention's four are in ``sparse_latent.py`` (its head
has their contracts): the indexer's scores over a slot's live index
pages at a decode step, over dense rows at a prefill, the selection
(each row's ``index_topk``-th score, its ties and the bias of the
chosen set from one read of the score matrix, a block of rows over the
whole key width in VMEM: a prefill's query rows, a decode step's slots)
and a prefill's flash attention under that bias; a decode step's bias
is an operand of ``latent_attention.py``'s walk.

Mode (``enable()``; a process starts in ``auto``, not interpreted):

- ``auto``: on a TPU backend a kernel dispatches where its ``fits()``
  accepts the shape and its threshold below holds: the LSTM at
  ``H <= LSTM_MAX_HIDDEN``, the softmax at ``cols <=
  SOFTMAX_MAX_COLS``, flash attention at ``S >= FLASH_MIN_SEQ``; the
  decode kernels (ragged paged attention, prefill flash attention,
  the gated delta, KDA, SSD, S6 and conv steps, the chunked gated delta
  and KDA rules of a hybrid's prefill, latent paged attention, the experts' grouped
  GEMM, sparse latent attention's four) have no threshold.  All three
  thresholds come from an earlier setup.  Flash attention at S=2048
  and the decode kernels are what the LM and generate cells run; the
  LSTM's and the softmax's thresholds are not re-measured on this chip
  and no cell runs them.
- ``on``: every kernel wherever ``fits()`` holds (tests force kernels
  at toy shapes with ``enable(True, interpret=True)``).
- ``off``: the jnp/XLA lowerings only (the reference ``chip_smoke.py``
  compares each kernel against).

A kernel's tile is an argument or a constant of its own module, set
from or held against a sweep on the chip that ``PERF.md`` §6 records
(the step kernels' ``BLOCK_BYTES``, the chunked rule's ``CHUNK`` and
``HEAD_BLOCK``, flash attention's ``BLOCK_PREF``, the grouped GEMM's
``ROW_TILE``, ``COL_CHUNK`` and ``VMEM_BUDGET``; the softmax's
``BLOCK_ROWS`` is, like its threshold, from an earlier setup): no kernel entry point consults anything else, and a
shape that wants another tile gets a rule here.

Off a TPU the kernels run under ``interpret=True`` for numerics tests.
On a TPU backend a kernel that dispatches runs compiled or raises —
interpret mode is ignored there, and the jnp/XLA reference is chosen
only by ``fits()`` and the mode.  Every dispatch decision is counted at
trace time in ``pallas_dispatch_total{kernel, path}`` (path =
compiled | interpret | reference; a kernel with more than one body
names the body it took behind an underscore, ``compiled_stored``:
``decode/attention.py:page_form``).
"""

from __future__ import annotations

from paddle_tpu.observability import metrics as _metrics

_M_DISPATCH = _metrics.counter(
    "pallas_dispatch_total",
    "Pallas kernel dispatch decisions, counted at trace time, by kernel "
    "and path (compiled | interpret | reference = the jnp/XLA lowering; "
    "_<form> behind it where a kernel has several bodies)")

# The auto-mode thresholds, each written here and nowhere else.
LSTM_MAX_HIDDEN = 384    # earlier setup: XLA won at H>=512
SOFTMAX_MAX_COLS = 256   # earlier setup: XLA won at cols=512
FLASH_MIN_SEQ = 1024     # below it XLA's fused unblocked attention won

_STATE = {"mode": "auto", "interpret": False}


def enable(flag=True, interpret: bool | None = None):
    """enable(True)='on', enable(False)='off', enable('auto')='auto'.
    Strings: '1'/'on'/'true', '0'/'off'/'false', 'auto'."""
    if isinstance(flag, str):
        norm = {"1": "on", "on": "on", "true": "on",
                "0": "off", "off": "off", "false": "off",
                "auto": "auto"}.get(flag.lower())
        if norm is None:
            raise ValueError(f"pallas.enable: unknown mode {flag!r}")
        _STATE["mode"] = norm
    else:
        _STATE["mode"] = "on" if flag else "off"
    if interpret is not None:
        _STATE["interpret"] = bool(interpret)


def mode() -> str:
    return _STATE["mode"]


def tpu_backend() -> bool:
    """Whether traced work lands on a TPU: the platform of jax's default
    device when one is pinned (``jax.default_device`` — the Executor
    sets it for CPUPlace), else the default backend."""
    import jax

    dev = jax.config.jax_default_device
    platform = (getattr(dev, "platform", dev) if dev is not None
                else jax.default_backend())
    return platform == "tpu"


def interpret_mode() -> bool:
    """Interpret mode is for hosts without a TPU: on a TPU backend a
    dispatched kernel is always compiled, whatever the flag says."""
    return _STATE["interpret"] and not tpu_backend()


def auto_ok() -> bool:
    # auto mode dispatches real kernels only on a TPU backend; interpret
    # mode works off-TPU (CPU numerics tests set it explicitly)
    return _STATE["interpret"] or tpu_backend()


def dispatch(kernel: str, use: bool, form: str = "") -> bool:
    """Count one dispatch decision (trace time) and return it.  ``form``:
    which of a kernel's bodies the call takes, where it has several and
    this is not the plain one; it rides the ``path`` label of a call that
    runs the kernel."""
    path = ("reference" if not use
            else "interpret" if interpret_mode() else "compiled")
    if use and form:
        path += "_" + form
    _M_DISPATCH.inc(kernel=kernel, path=path)
    return use


def policy(fits: bool, auto: bool) -> bool:
    """fits() gates everything; 'on' forces, 'auto' asks the threshold."""
    if _STATE["mode"] == "off" or not fits:
        return False
    return _STATE["mode"] == "on" or (auto_ok() and auto)


def use_lstm(b: int, h: int) -> bool:
    from paddle_tpu.pallas import lstm as _l

    return dispatch("lstm", policy(_l.fits(b, h), h <= LSTM_MAX_HIDDEN))


def use_softmax(rows: int, cols: int) -> bool:
    from paddle_tpu.pallas import softmax as _s

    return dispatch("softmax", policy(_s.fits(rows, cols),
                                      cols <= SOFTMAX_MAX_COLS))


def use_flash_attention(bh: int, s_q: int, s_k: int, d: int) -> bool:
    """Blocked online-softmax attention.  On an earlier setup it beat
    the jnp softmax(QK^T)V lowering from S=FLASH_MIN_SEQ up, where the
    S x S score tensor stops fitting cache-friendly fusions; below that
    XLA's fused unblocked attention won on kernel count.  The threshold
    is not re-measured."""
    from paddle_tpu.pallas import flash_attention as _f

    return dispatch("flash_attention", policy(
        _f.fits(1, bh, s_q, d) and s_q == s_k, s_q >= FLASH_MIN_SEQ))


def use_gated_delta_step(state_dtype, heads: int, d_v: int,
                         wide: int) -> bool:
    """A hybrid's decode step advances its slots' state entries by the
    kernel wherever ``fits()`` holds (the decode kernels' rule: no
    threshold), else slot by slot in XLA."""
    from paddle_tpu.pallas import gated_delta as _g

    return dispatch("gated_delta_step", policy(
        _g.fits(state_dtype, heads, d_v, wide), True))


def use_gated_delta_chunked(state_dtype, rows: int, heads: int, d_v: int,
                            d_k: int) -> bool:
    """A hybrid's prefill runs the gated delta rule over a bucket's
    ``rows`` by the kernel wherever ``fits()`` holds (a float32 state, a
    bucket of whole chunks), else chunked in XLA
    (``models/olmo_hybrid.py:chunked_gated_delta``, its reference)."""
    from paddle_tpu.pallas import gated_delta_chunked as _g

    return dispatch("gated_delta_chunked", policy(
        _g.fits(state_dtype, rows, heads, d_v, d_k), True))


def use_kda_step(state_dtype, heads: int, d_v: int, wide: int) -> bool:
    """The per-channel-decay delta rule's decode step (``kda.py``), by
    ``use_gated_delta_step``'s rule: the kernel wherever ``step_fits()``
    holds, else the slots' entries gathered, advanced and scattered in
    XLA (``models/ling_hybrid.py:step_kda``)."""
    from paddle_tpu.pallas import kda as _k

    return dispatch("kda_step", policy(
        _k.step_fits(state_dtype, heads, d_v, wide), True))


def use_kda_chunked(state_dtype, rows: int, heads: int, d_v: int, d_k: int,
                    lower_bound: float) -> bool:
    """That rule over a prefill bucket's ``rows`` by the kernel wherever
    ``chunked_fits()`` holds (a float32 state, a bucket of whole chunks,
    keys of whole lanes, a log-decay bounded below by ``lower_bound``),
    else chunked in XLA (``models/ling_hybrid.py:chunked_kda``, its
    reference)."""
    from paddle_tpu.pallas import kda as _k

    return dispatch("kda_chunked", policy(
        _k.chunked_fits(state_dtype, rows, heads, d_v, d_k, lower_bound),
        True))


def use_ssd_step(state_dtype, rows: int, d_state: int, lanes: int,
                 groups: int = 1) -> bool:
    """A state-space layer's decode step over entries ``(rows, d_state,
    lanes)`` whose heads read B and C in ``groups`` groups, by the same
    rule as ``use_gated_delta_step``: the kernel wherever ``fits()``
    holds, else gathered and scattered in XLA."""
    from paddle_tpu.pallas import ssd_step as _s

    return dispatch("ssd_step", policy(
        _s.fits(state_dtype, rows, d_state, lanes, groups), True))


def use_s6_step(state_dtype, d_state: int, channels: int) -> bool:
    """A Mamba-1 layer's decode step over entries ``(d_state,
    channels)``, by the same rule as ``use_ssd_step``."""
    from paddle_tpu.pallas import s6_step as _s

    return dispatch("s6_step", policy(
        _s.fits(state_dtype, d_state, channels), True))


def use_conv_step(pool_dtype, entry_shape, row_dtype, taps: int,
                  channels: int) -> bool:
    """A recurrent layer's conv over a decode step's rows and the rows
    its slots' entries keep, by the same rule: the kernel wherever
    ``fits()`` holds (the tail pool's dtype and an entry's shape as
    stored), else gathered and scattered in XLA."""
    from paddle_tpu.pallas import conv_step as _c

    return dispatch("conv_step", policy(
        _c.fits(pool_dtype, entry_shape, row_dtype, taps, channels), True))


def use_latent_paged_attention(pool_dtype, page_size: int, rows: int,
                               width: int, v_width: int) -> bool:
    """A latent layer's decode step (and a verify chunk of a few rows;
    a sparse layer's step under its selected sets as a bias)
    attends over the slots' pages of latent rows by the kernel wherever
    ``fits()`` holds (rows of whole 128-lane tiles, a q block of ``rows``
    = chunk rows x heads that stays resident), by the decode kernels'
    rule: no threshold; else gathered in XLA
    (``latent_paged_attention_reference``)."""
    from paddle_tpu.pallas import latent_attention as _l

    return dispatch("latent_paged_attention", policy(
        _l.fits(pool_dtype, page_size, rows, width, v_width), True))


def use_grouped_gemm(row_dtype, w_dtype, rows: int, d: int, f: int) -> bool:
    """A routed layer's grouped GEMMs over ``rows`` sorted rows of
    width ``d`` (experts of ``d x f``: the matrices in front of the
    activation together, gate and up or up alone, then down)
    by the kernel wherever ``fits()`` holds both ways round,
    by the decode kernels' rule: no threshold; else
    ``jax.lax.ragged_dot`` (``grouped_gemm_reference``)."""
    from paddle_tpu.pallas import grouped_gemm as _g

    return dispatch("grouped_gemm", policy(
        _g.fits(row_dtype, w_dtype, rows, d, f)
        and _g.fits(row_dtype, w_dtype, rows, f, d), True))


from paddle_tpu.pallas.softmax import softmax as pallas_softmax  # noqa: E402
from paddle_tpu.pallas.lstm import lstm_seq as pallas_lstm_seq  # noqa: E402
from paddle_tpu.pallas.flash_attention import (  # noqa: E402
    flash_attention as pallas_flash_attention)


def use_paged_index_scores(pool_dtype, page_size: int, heads: int,
                           dim: int) -> bool:
    """A sparse latent layer's decode step scores the slots' cached
    index rows by the kernel wherever ``paged_fits()`` holds, by the
    decode kernels' rule: no threshold; else gathered in XLA
    (``paged_index_scores_reference``)."""
    from paddle_tpu.pallas import sparse_latent as _s

    return dispatch("paged_index_scores", policy(
        _s.paged_fits(pool_dtype, page_size, heads, dim), True))


def use_index_scores(rows: int, keys: int, heads: int, dim: int) -> bool:
    """A prefill's index scores of ``rows`` query rows on ``keys`` key
    rows by the kernel wherever ``dense_fits()`` holds (whole blocks of
    128 rows and up); else ``index_scores_reference``."""
    from paddle_tpu.pallas import sparse_latent as _s

    return dispatch("index_scores", policy(
        _s.dense_fits(rows, keys, heads, dim), True))


def use_selection_bias(rows: int, keys: int, dtype) -> bool:
    """The selected sets of a prefill's query rows or a decode step's
    slots, from the index scores to the bias the attention runs under,
    by the kernel wherever ``selection_fits()`` holds (a row block
    resident over the whole key width); else ``glm_dsa.selection_mask``,
    34 passes over the scores in XLA."""
    from paddle_tpu.pallas import sparse_latent as _s

    return dispatch("selection_bias", policy(
        _s.selection_fits(rows, keys, dtype), True))


def use_selected_flash_attention(heads: int, rows: int, keys: int,
                                 dim: int) -> bool:
    """A prefill's attention under the selection's mask by the kernel
    wherever ``flash_fits()`` holds; else
    ``selected_attention_reference``."""
    from paddle_tpu.pallas import sparse_latent as _s

    return dispatch("selected_flash_attention", policy(
        _s.flash_fits(heads, rows, keys, dim), True))
