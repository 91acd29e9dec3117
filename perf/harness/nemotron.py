"""Nemotron-H's share of a chip (``paddle_tpu/models/nemotron_h.py``):
the bytes a decode step has to move, reckoned from the PUBLISHED sizes
and from the program's counters, and the readers' shared arithmetic.
Kept with the benchmark: a share of a roofline is these numbers over a
device time.

An expert is TWO matrices (``W_up`` 2,688 x 1,856, ``W_down`` 1,856 x
2,688: ``W_down relu(W_up m)^2``), where ``perf/harness/moe.py`` counts a
SwiGLU's three.  The program stores each at 1,920 columns (zeros behind
the published ones); the roofline counts the published 1,856 whatever
is stored, so a share reads under what the stored bytes would give by
1,856 / 1,920.  A Mamba-2 layer's state is 64 heads x 64 channels x 128
float32 a sequence, read and written once a step; a token's K and V in
the six attention layers 2 heads x 128 x 2 B x 2 each: 6,144 B.

The program's scopes: ``ssm_proj`` (new in PR 64: a Mamba-2 layer's in-
and out-projection, which ``ssm`` leaves out), ``ssm`` / ``ssm_conv`` /
``ssm_state`` / ``ssm_scan`` as Granite's, ``attn_full``, ``moe_*`` and
``moe_shared``.  Its counters are ``models/moe.py``'s.
"""

from perf.harness import moe, ssm
from perf.harness.readers import registry_count

PROJ_SCOPE = r"/ssm_proj/"
EXPERTS, ATTENTION = "experts", "attention"
STEPS = "decode_steps_total"


def sizes(record):
    """The configuration as published: (hidden size, an expert's width,
    experts held, routed layers, attention layers, K/V heads, head size,
    itemsize), or None for a configuration without such layers."""
    cfg = record["config"]
    if "hybrid_override_pattern" not in cfg:
        return None
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    itemsize = {"bfloat16": 2, "float32": 4}[cfg["generate"]["dtype"]]
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], sum(t == EXPERTS for t in kept),
            sum(t == ATTENTION for t in kept), cfg["num_key_value_heads"],
            cfg["head_dim"], itemsize)


def plain_expert_bytes(experts_hit, d_model, expert_width, itemsize):
    """Bytes of the up and down matrices of ``experts_hit`` (held
    expert, layer, step) triples: TWO matrices an expert."""
    return 2.0 * experts_hit * d_model * expert_width * itemsize


def state_bytes(record, slot_steps):
    """Bytes the decode steps move of the Mamba-2 states for
    ``slot_steps`` live slot-steps: read once, written once."""
    return ssm.step_state_bytes(slot_steps, *ssm.sizes(record))


def kv_bytes(rows, layers, kv_heads, head_dim, itemsize):
    """Bytes of K and V of ``rows`` cached rows in ``layers`` layers."""
    return 2.0 * rows * layers * kv_heads * head_dim * itemsize


def held_assignments(record):
    """The live rows' assignments to held experts over the window's
    decode steps (``moe_assignments_total`` counts held experts alone),
    or None where the program has no such counter."""
    return moe.phase_delta(record, "moe_assignments_total", "decode")


def steps(record):
    return registry_count(record, STEPS)
