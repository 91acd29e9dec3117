"""MiMo-V2.5's share of a chip (``paddle_tpu/models/mimo_v2.py``): the
bytes its two kinds of cached row need, computed from the PUBLISHED
shapes and from the program's counters, and the readers' shared
arithmetic.  Kept with the benchmark: a share of a roofline is these
numbers over a device time.

A full layer keeps 4 K/V heads of 192 + 128 numbers a token; a window
layer 8 such heads a row of its ring, 256 rows at most.  The program
stores a key at 256 lanes (zeros behind the 192); the rooflines count
the 320 published numbers a head a row, whatever is stored, so a share
reads under what the stored bytes would give by 320 / 384.

The program's scopes: ``attn_full`` (the run's write and the grouped
walk kernel, ``ragged_paged_attention_gqa``) and ``attn_window`` (the
ring's write, its gather, scores, sink and softmax in plain XLA) in the
decode step, the buckets and the chunk programs.  Its counters:
``decode_full_rows_read_total`` (rows one full layer read, summed over
the window's steps) and ``decode_run_pages_in_use_steps_total`` (run
pages in use, summed over the steps).
"""

from perf.harness.readers import registry_count

FULL_ROWS = "decode_full_rows_read_total"
RUN_PAGE_STEPS = "decode_run_pages_in_use_steps_total"
STEPS = "decode_steps_total"


def sizes(record):
    """The configuration's cached rows as published: (full layers, window
    layers, K/V heads of a full layer, of a window layer, numbers a head
    a row, ring rows, itemsize), or None for a configuration without
    these layers."""
    cfg = record["config"]
    if "hybrid_layer_pattern" not in cfg:
        return None
    kept = cfg["hybrid_layer_pattern"][:cfg["num_hidden_layers"]]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["generate"]["dtype"]]
    return (sum(1 for w in kept if not w), sum(1 for w in kept if w),
            cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"],
            cfg["head_dim"] + cfg["v_head_dim"],
            2 * cfg["generate"]["page_size"], itemsize)


def full_bytes(rows, layers, kv_heads, numbers, itemsize):
    """Bytes the full layers' kernel has to read for ``rows`` (slot,
    step, cached row) triples of one layer."""
    return float(rows) * layers * kv_heads * numbers * itemsize


def ring_bytes(slot_steps, resident_rows, layers, kv_heads, numbers,
               itemsize):
    """Bytes the window layers have to read over ``slot_steps`` (seated
    slot, step) pairs whose rings hold ``resident_rows`` rows each (the
    ring's rows, or the sequence's if it is shorter)."""
    return (float(slot_steps) * resident_rows * layers * kv_heads * numbers
            * itemsize)


def counted(record, name):
    """The window's delta of a counter of this PR's, or None where the
    program has none (a parent commit) or it did not move."""
    return registry_count(record, name) or None
