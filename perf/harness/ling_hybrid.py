"""A hybrid of per-channel-decay delta-rule (KDA) layers and latent
attention under a router with a group step
(``paddle_tpu/models/ling_hybrid.py``): what its own per-layer metrics
need beyond the accepted readers' arithmetic.  Kept with the benchmark:
a share of a roofline is these numbers over a device time.

The KDA layers stand under the accepted linear-attention scopes
(``perf/harness/linear_attn.py``), so both of their kernels' shares are
read by the yardstick that reads the scalar-decay rule's.  New scopes:
``lin_attn_gate`` (a KDA layer's decay and beta: ``W_f``, ``W_b`` and
the bounded gate, beside ``lin_attn``, not inside it, so that
``lin_attn`` holds what it holds elsewhere) and ``moe_group`` (the
router's group step, inside ``moe_dispatch``).  New counter:
``moe_groups_chosen_total{group, phase}``.  A program without them (the
parent's, another model's) has nothing to read, and every reader says
None.
"""

from perf.harness import latent
from perf.harness import tick_account as ta
from perf.harness.linear_attn import (DECODE_MODULE, DECODE_PROGRAM,
                                      scope_seconds)
from perf.harness.readers import registry_count

GATE_SCOPE = r"/lin_attn_gate/"
ROUTE_SCOPE = r"/moe_(router|dispatch)/"
GROUPS_COUNTER = "moe_groups_chosen_total"
LATENT = "latent_attention"


def latent_layers(record):
    """The latent layers of the configuration as run, or None for one
    that does not say which of its layers are."""
    cfg = record["config"]
    if "layer_types" not in cfg or "kv_lora_rank" not in cfg:
        return None
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return sum(t == LATENT for t in kept) or None


def latent_kernel_share(record):
    """The bytes of the live latent rows the window's decode steps had
    to read (live rows x LATENT layers x (rank + rope) x itemsize: the
    algorithm's 576 numbers a row) over the device time of the
    ``latent_paged_attention`` kernel in the decode step, as a share of
    the chip's HBM bandwidth."""
    layers = latent_layers(record)
    got = latent.kernel_seconds(record, DECODE_PROGRAM, DECODE_MODULE,
                                latent.DECODE_KERNEL)
    rows = record.get("latent_rows")
    if not layers or not got or not rows:
        return None
    cfg = record["config"]
    need = latent.step_bytes(rows, layers, cfg["kv_lora_rank"],
                             cfg["qk_rope_head_dim"])
    return 100.0 * need / got[0] / record["peaks"]["hbm_bytes_per_s"]


def step_scope_ms(record, scope):
    """Device time of the decode step's instructions under ``scope``,
    all layers, per decode step, in ms."""
    got = scope_seconds(record, DECODE_PROGRAM, DECODE_MODULE, scope)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3


def group_load_max_over_mean(record, phase="decode"):
    """How uneven the router's group step was over the window's decode
    steps: the live rows that kept the most-kept group over the mean
    rows a group was kept by.  1 is perfectly even; a router with
    ``topk_group`` of ``n_group`` cannot pass ``n_group / topk_group``."""
    reg = record.get("registry")
    groups = int(record["config"].get("n_group", 0))
    if not reg or GROUPS_COUNTER not in reg["after"] or not groups:
        return None
    rows = [ta.total(reg["after"], GROUPS_COUNTER, group=g, phase=phase)
            - ta.total(reg["before"], GROUPS_COUNTER, group=g, phase=phase)
            for g in range(groups)]
    return max(rows) / (sum(rows) / groups) if sum(rows) else None
