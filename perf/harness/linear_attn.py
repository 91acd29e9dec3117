"""Linear-attention layers (the gated delta rule): the bytes and
operations the algorithm needs, computed from shapes and from the
program's counters and gauges, and the readers' shared arithmetic.
Kept with the benchmark: a share of a roofline is these numbers over a
device time.

The program's scopes (``paddle_tpu/models/olmo_hybrid.py``):
``lin_attn`` holds a linear layer's mixer less its projections; inside
it ``lin_attn_conv`` (both paths), ``lin_attn_state`` (a decode step's
one-token update of the slots' entries) and ``lin_attn_scan`` (a
prefill's chunked scan).  A loop's instruction (the slots' scan, the
chunks') spans its body's, which are the ones counted.
"""

import re

from perf.harness import hlo_ops, modules

ANY_SCOPE = r"/lin_attn/"
STATE_SCOPE = r"/lin_attn_state/"
SCAN_SCOPE = r"/lin_attn_scan/"
DECODE_PROGRAM, DECODE_MODULE = "decode_step", r"_decode_step"
PREFILL_PROGRAMS, PREFILL_MODULE = "prefill_bucket_", r"_prefill_bucket"
HOLDS_OTHERS = re.compile(r"^(while|conditional|call)\b")
LINEAR = "linear_attention"


def sizes(record):
    """(linear layers, heads, d_k, d_v) of the configuration as run, or
    None for one without such layers."""
    cfg = record["config"]
    if "linear_key_head_dim" not in cfg:
        return None
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return (sum(t == LINEAR for t in kept), cfg["linear_num_key_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def state_bytes(layers, heads, d_k, d_v, itemsize=4):
    """Bytes of one sequence's recurrent states, all linear layers, as
    the algorithm holds them: heads x d_v x d_k float32 a layer."""
    return float(layers * heads * d_k * d_v * itemsize)


def step_state_bytes(slots, layers, heads, d_k, d_v):
    """Bytes a decode step over ``slots`` slots has to move: every
    slot's states read once and written once."""
    return 2.0 * slots * state_bytes(layers, heads, d_k, d_v)


def scan_flops(rows, layers, heads, d_k, d_v):
    """FLOPs of the gated delta rule over ``rows`` rows: a row reads
    the state with k and with q and writes one outer product, 2 d_k d_v
    each, per head and layer."""
    return 6.0 * rows * layers * heads * d_k * d_v


def scope_seconds(record, program_prefix, module_pattern, scope):
    """(seconds, events, runs) of the instructions under ``scope`` over
    all compiled texts whose key starts with ``program_prefix``, inside
    the runs of the modules matching ``module_pattern``.  None when the
    trace or the texts hold none (a program without the scope)."""
    texts = [t for k, t in record.get("compiled_text", {}).items()
             if k.startswith(program_prefix)]
    if not record.get("trace") or not texts:
        return None
    names = set()
    for text in texts:
        names |= hlo_ops.instructions(text, scope)
    names = {n for n in names if not HOLDS_OTHERS.match(n)}
    if not names:
        return None
    got = modules.seconds_in(record["trace"], record.get("trace_modules"),
                             module_pattern, names)
    return None if not got or not got[1] else got
