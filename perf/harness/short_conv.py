"""Gated short-conv layers and a prompt's chunks over a state entry
(``paddle_tpu/models/lfm2_moe.py``, ``decode/state_entry.py``): the
operations the algorithm needs, computed from shapes and from the
program's counters, and the readers' shared arithmetic.  Kept with the
benchmark: a share of a peak is these numbers over a device time.

The program's scopes: ``short_conv`` holds a conv layer's mixer less its
two projections (both gates and the conv); inside it ``short_conv_step``
(a decode step's one ``conv_step`` call over the slots' tails) and
``short_conv_scan`` (a bucket's or a chunk's causal conv); ``attn_chunk``,
inside ``attn_full``, holds a chunk's gather of the cached run and its
two-part attention.  Its programs: the skeleton's decode step and
buckets, and ``_prefill_state_chunk`` (one a (rows, done)), whose
compiled texts the driver keeps as ``prefill_state_chunk_<rows>_over_
<done>``.  Its counters: ``decode_prefill_chunk_rows_total`` (real rows
run in chunks) and ``decode_prefill_chunk_pairs_total`` ((query, key)
pairs an attention layer of those chunks computes).
"""

from perf.harness import skeleton
from perf.harness import trace as tr
from perf.harness.linear_attn import (  # noqa: F401
    DECODE_MODULE, DECODE_PROGRAM, PREFILL_MODULE, PREFILL_PROGRAMS,
    scope_seconds)
from perf.harness.readers import registry_count

ANY_SCOPE = r"/short_conv/"
CHUNK_ATTENTION_SCOPE = r"/attn_chunk/"
CHUNK_PROGRAMS, CHUNK_MODULE = "prefill_state_chunk_", r"_prefill_state_chunk"
CHUNK_ROWS = "decode_prefill_chunk_rows_total"
CHUNK_PAIRS = "decode_prefill_chunk_pairs_total"
ATTENTION = "full_attention"


def attention_sizes(record):
    """(attention layers, query heads, head size) of the configuration
    as run, or None for one without this model's layers."""
    cfg = record["config"]
    if "conv_L_cache" not in cfg:
        return None
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    heads = cfg["num_attention_heads"]
    return (sum(t == ATTENTION for t in kept), heads,
            cfg.get("head_dim") or cfg["hidden_size"] // heads)


def chunk_attention_flops(pairs, layers, heads, head_dim):
    """Model FLOPs of the chunks' attention over ``pairs`` (query row,
    key row) pairs a layer: q.k and p.v, 2 x head size each, a query
    head (K/V heads are shared, not recomputed)."""
    return 4.0 * pairs * layers * heads * head_dim


def counted(record, name):
    """The window's delta of a counter of this PR's, or None where the
    program has none (a parent commit) or it did not move."""
    return registry_count(record, name) or None


def program_seconds(record, program_prefix, module_pattern):
    """(seconds, runs) of every device event inside the window's runs of
    the programs whose module name matches, an instruction that holds
    others left to the ones it holds.  None without a trace, without
    such a compiled text or such a run."""
    trace = record.get("trace")
    if not trace or not trace.get("devices") or not any(
            k.startswith(program_prefix)
            for k in record.get("compiled_text", {})):
        return None
    plane = sorted(trace["devices"])[0]
    groups, runs = skeleton._runs(trace, record.get("trace_modules"),
                                  module_pattern, plane)
    secs = sum(ev[2] for evs in groups.values() for ev in evs
               if not skeleton.HOLDS_OTHERS.match(tr.bare(ev[0]))) / 1e9
    return (secs, runs) if secs else None
