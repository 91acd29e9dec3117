"""Pallas flash attention: the FLOPs causal attention needs in one
step (from the shapes; the backward's recomputation does not count)
over the kernel's device time, as a share of the bf16 peak.  Bound:
FLOP/s."""

from perf.harness.flops import causal_attention_train_flops
from perf.harness.readers import kernel_seconds
from perf.layer_metrics.flash_attn_ms_per_step import PATTERN, PROGRAM


def read(record):
    got = kernel_seconds(record, PROGRAM, PATTERN)
    if not got or not record.get("steps"):
        return None
    cfg, traffic = record["config"], record["traffic"]
    flops = causal_attention_train_flops(
        int(traffic["batch"]), cfg["n_head"], int(traffic["seq"]),
        cfg["head_dim"], cfg["train"]["n_layer"])
    per_step = got[0] / record["steps"]
    return 100.0 * flops / per_step / record["peaks"]["bf16_flops_per_s"]
