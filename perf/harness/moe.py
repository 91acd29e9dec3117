"""The routed expert layer: the bytes and operations the algorithm
needs, computed from shapes and from the program's load counters, and
the readers' shared arithmetic.  Kept with the benchmark: a share of a
roofline is these numbers over a device time.

The counters (``paddle_tpu/models/moe.py``) carry a ``phase`` label,
"decode" (the decode and verify steps) or "prefill".
"""

from perf.harness import hlo_ops, modules

# where the layer's instructions are in a compiled text: under one of
# the four named scopes, or a grouped-matmul custom call the TPU
# compiler made of jax.lax.ragged_dot (it drops the scope from them)
ANY_SCOPE = r"/moe_(router|dispatch|experts|combine)/"
EXPERTS_SCOPE = r"/moe_experts/"
RAGGED_DOT = r"^ragged-dot"
DECODE_PROGRAM, DECODE_MODULE = "decode_step", r"_decode_step"
PREFILL_PROGRAMS, PREFILL_MODULE = "prefill_bucket_", r"_prefill_bucket"


def expert_weight_bytes(experts_hit, d_model, expert_width, itemsize):
    """Bytes of the gate, up and down matrices of ``experts_hit``
    (expert, layer, step) triples: what the grouped GEMMs of those
    steps had to read of the weights.  The rows themselves (a few
    hundred of ``d_model``) are left out: under 1% at decode."""
    return 3.0 * experts_hit * d_model * expert_width * itemsize


def expert_flops(assignments, d_model, expert_width):
    """FLOPs of the three grouped GEMMs over ``assignments`` (row,
    expert) pairs: 2 d f each."""
    return 6.0 * assignments * d_model * expert_width


def phase_delta(record, name, phase):
    """Delta over the window of counter ``name`` at label ``phase``;
    None when the program has no such family."""
    reg = record.get("registry")
    if not reg or name not in reg["after"]:
        return None

    def at(snap):
        return sum(v["value"] for v in snap.get(name, {"values": []})["values"]
                   if v["labels"].get("phase") == phase)
    return at(reg["after"]) - at(reg["before"])


def scope_seconds(record, program_prefix, module_pattern, scope):
    """(seconds, events) of the instructions under ``scope`` (and the
    ragged-dot custom calls, which belong to ``moe_experts``) over all
    compiled texts whose key starts with ``program_prefix``, inside the
    runs of the modules matching ``module_pattern``.  None when the
    trace or the texts hold none."""
    texts = [t for k, t in record.get("compiled_text", {}).items()
             if k.startswith(program_prefix)]
    if not record.get("trace") or not texts:
        return None
    names = set()
    for text in texts:
        names |= hlo_ops.instructions(text, scope, RAGGED_DOT)
    if not names:
        return None
    got = modules.seconds_in(record["trace"], record.get("trace_modules"),
                             module_pattern, names)
    return None if not got or not got[1] else got[:2]


def model_sizes(record):
    cfg = record["config"]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["generate"]["dtype"]]
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_experts"], itemsize)
