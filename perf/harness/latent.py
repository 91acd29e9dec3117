"""Latent attention (MLA): the bytes and operations the algorithm
needs, computed from shapes and from the program's counters, and the
readers' shared arithmetic.  Kept with the benchmark: a share of a
roofline is these numbers over a device time.

The program's scopes (``paddle_tpu/models/kanana_mla.py``):
``attn_latent`` holds a layer's whole mixer; inside it
``attn_latent_down`` (what makes the stored row), ``attn_latent_absorb``
(a step's two per-head products round the kernel),
``attn_latent_expand`` (a prefill's up-projection of the rows) and the
decode kernel under its own name, ``latent_paged_attention``.  The
prefill's attention is the flash kernel inside ``_prefill_bucket``'s
runs.  A program without the scopes (another model's, the parent's)
has nothing to read, and every reader says None.
"""

from perf.harness import hlo, modules
from perf.harness.linear_attn import (DECODE_MODULE, DECODE_PROGRAM,  # noqa: F401
                                      PREFILL_MODULE, PREFILL_PROGRAMS,
                                      scope_seconds)

ANY_SCOPE = r"/attn_latent/"
DECODE_KERNEL = r"latent_paged_attention"
PREFILL_KERNEL = r"_flash_fwd_impl"
PAIRS_COUNTER = "attn_latent_prefill_pairs_total"


def sizes(record):
    """(layers, heads, rank, rope, q/k head size, v head size) of the
    configuration as run, or None for one without latent layers."""
    cfg = record["config"]
    if "kv_lora_rank" not in cfg:
        return None
    return (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_rope_head_dim"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def row_bytes(rank, rope, itemsize=2):
    """Bytes the ALGORITHM keeps a token a layer: the compression and
    the shared rotated key (what is stored may be padded to whole
    tiles: the configuration's file says)."""
    return float((rank + rope) * itemsize)


def step_bytes(rows, layers, rank, rope, itemsize=2):
    """Bytes the decode steps' kernel has to read for ``rows`` live
    cached rows (one layer's count): each row once a layer, as key and
    as value both."""
    return rows * layers * row_bytes(rank, rope, itemsize)


def step_flops(rows, layers, heads, rank, rope):
    """FLOPs of absorbed attention over ``rows`` live cached rows: a
    head's score over rank + rope lanes and its weighted sum over rank
    lanes, 2 each, per layer."""
    return 2.0 * rows * layers * heads * ((rank + rope) + rank)


def prefill_flops(pairs, layers, heads, qk, v):
    """FLOPs of expanded causal attention over ``pairs`` (query row,
    key row) pairs of real rows (one layer's count): a head's score
    over ``qk`` lanes and its weighted sum over ``v`` lanes."""
    return 2.0 * pairs * layers * heads * (qk + v)


def kernel_seconds(record, program_prefix, module_pattern, kernel):
    """(seconds, events, runs) of the Pallas custom calls whose op_name
    matches ``kernel`` over the compiled texts whose key starts with
    ``program_prefix``, inside the runs of the modules matching
    ``module_pattern``.  None when the trace or the texts hold none."""
    texts = [t for k, t in record.get("compiled_text", {}).items()
             if k.startswith(program_prefix)]
    if not record.get("trace") or not texts:
        return None
    names = set()
    for text in texts:
        names |= hlo.kernel_instructions(text, kernel)
    if not names:
        return None
    got = modules.seconds_in(record["trace"], record.get("trace_modules"),
                             module_pattern, names)
    return None if not got or not got[1] else got


def step_kernel(record):
    """What the two shares of the decode kernel are made of: (the
    configuration's sizes, the kernel's seconds inside the decode step's
    runs, the live rows those steps read, one layer's count), or None
    where any is missing."""
    shape = sizes(record)
    got = kernel_seconds(record, DECODE_PROGRAM, DECODE_MODULE, DECODE_KERNEL)
    rows = record.get("latent_rows")
    if not shape or not got or not rows:
        return None
    return shape, got[0], rows
