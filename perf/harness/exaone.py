"""K-EXAONE's share of a chip: the bytes its layers need, computed from
shapes and from the program's counters and gauges, and the readers'
shared arithmetic.  Kept with the benchmark: a share of a roofline is
these numbers over a device time.
"""

from perf.harness import hlo_ops, modules, moe

ATTN_WINDOW_SCOPE = r"/attn_window/"
MOE_SHARED_SCOPE = r"/moe_shared/"
GQA_KERNEL = r"ragged_paged_attention_gqa"


def kv_row_bytes(kv_heads, head_dim, itemsize):
    """Bytes of one row of K and V in one layer."""
    return 2.0 * kv_heads * head_dim * itemsize


def held_expert_bytes(experts_hit, d_model, expert_width, itemsize):
    """Bytes of the gate, up and down matrices of ``experts_hit``
    (held expert, layer, step) triples."""
    return moe.expert_weight_bytes(experts_hit, d_model, expert_width,
                                   itemsize)


def sizes(record):
    """(hidden size, the ROUTED experts' width, itemsize): the
    configuration's ``intermediate_size`` is its dense layer's."""
    cfg = record["config"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["generate"]["dtype"]]
    return cfg["hidden_size"], cfg["moe_intermediate_size"], itemsize


def decode_scope_seconds(record, scope):
    """(seconds, events) of the decode step's instructions under
    ``scope`` alone (no instruction is named by its own name), inside
    the decode step's runs.  None when the trace or the text holds
    none."""
    text = record.get("compiled_text", {}).get(moe.DECODE_PROGRAM)
    if not record.get("trace") or not text:
        return None
    names = hlo_ops.instructions(text, scope)
    if not names:
        return None
    got = modules.seconds_in(record["trace"], record.get("trace_modules"),
                             moe.DECODE_MODULE, names)
    return None if not got or not got[1] else got[:2]
