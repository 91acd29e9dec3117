"""Sparse latent attention (a learned indexer chooses the cached rows a
latent layer reads): the bytes the algorithm needs, computed from shapes
and from the program's counters, and the readers' shared arithmetic.
Kept with the benchmark: a share of a roofline is these numbers over a
device time.

The program's scopes (``paddle_tpu/models/glm_dsa.py``), all under
``attn_latent``, so the skeleton's ``blk_mixer`` still holds them:
``attn_index`` (the indexer's projections, the index row's norm and
rotation, and the scores: ``paged_index_scores`` in a decode step,
``index_scores`` in a prefill), ``attn_index_select`` (the selection:
``top_k`` in a step, the bisection and the mask in a prefill) and
``attn_sparse`` (a step's fetch of the selected latent rows and absorbed
attention on them, ``latent_paged_attention`` over the fetched rows; a
prefill's expansion and ``selected_flash_attention``).  Its counters:
``attn_index_rows_scored_total`` and ``attn_index_rows_selected_total``
(one layer's, by the decode steps that selected) and
``attn_index_prefill_pairs_total``.  A program without the scopes or the
counters (another model's, the parent's) has nothing to read, and every
reader says None.
"""

from perf.harness import hlo, hlo_ops, modules
from perf.harness.linear_attn import (DECODE_MODULE, DECODE_PROGRAM,  # noqa: F401
                                      HOLDS_OTHERS, PREFILL_MODULE,
                                      PREFILL_PROGRAMS, scope_seconds)
from perf.harness.readers import registry_count

INDEX_SCOPE = r"/attn_index/"
SELECT_SCOPE = r"/attn_index_select/"
SPARSE_SCOPE = r"/attn_sparse/"
# inside ``attn_sparse``, what is not the read: a step's two absorbed
# products round the kernel
ABSORB_SCOPE = r"/attn_latent_absorb/"
INDEX_KERNEL = r"paged_index_scores"
SCORED, SELECTED = ("attn_index_rows_scored_total",
                    "attn_index_rows_selected_total")


def sizes(record):
    """(layers, index row bytes, latent row bytes as the algorithm has
    them) of the configuration as run, or None for one without an
    indexer."""
    cfg = record["config"]
    if "index_head_dim" not in cfg or "kv_lora_rank" not in cfg:
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["generate"]["dtype"]]
    return (cfg["num_hidden_layers"], cfg["index_head_dim"] * itemsize,
            (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize)


def ms_per_step(record, scope):
    """Device ms of the decode step's instructions under ``scope``, all
    layers, per decode step."""
    got = scope_seconds(record, DECODE_PROGRAM, DECODE_MODULE, scope)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3


def _step_seconds(record, names_of):
    """Seconds, inside the decode step's runs, of the instructions
    ``names_of(compiled text)`` names (those that hold others left out),
    or None."""
    texts = [t for k, t in record.get("compiled_text", {}).items()
             if k.startswith(DECODE_PROGRAM)]
    if not record.get("trace") or not texts:
        return None
    names = {n for text in texts for n in names_of(text)
             if not HOLDS_OTHERS.match(n)}
    got = names and modules.seconds_in(
        record["trace"], record.get("trace_modules"), DECODE_MODULE, names)
    return got[0] if got and got[1] else None


def index_kernel_seconds(record):
    """Seconds of the ``paged_index_scores`` custom calls inside the
    decode step's runs, or None."""
    return _step_seconds(
        record, lambda text: hlo.kernel_instructions(text, INDEX_KERNEL))


def read_seconds(record):
    """Seconds of the decode step's read of the selected rows: what lies
    under ``attn_sparse`` less the absorbed products (the fetch by row
    and the kernel over the fetched rows), or None."""
    return _step_seconds(
        record, lambda text: hlo_ops.instructions(text, SPARSE_SCOPE)
        - hlo_ops.instructions(text, ABSORB_SCOPE))


def share_of_hbm(record, counter, row_bytes_at, seconds):
    """100 x (the counter's rows x layers x bytes a row) / seconds over
    the chip's HBM bandwidth, or None where any is missing."""
    shape, rows = sizes(record), registry_count(record, counter)
    if not shape or not rows or not seconds:
        return None
    return (100.0 * rows * shape[0] * shape[row_bytes_at] / seconds
            / record["peaks"]["hbm_bytes_per_s"])
