"""Linear-attention layers: the FLOPs of the gated delta rule over the
real prompt rows of the window's bucketed prefills (6 x heads x d_k x
d_v a row a layer, ``decode_prefill_tokens_total``) over the device
time under ``lin_attn_scan``, as a share of the chip's bf16 peak.
Bound: FLOP/s.  The chunked form multiplies more than the recurrence
needs (the chunk's square matrices) and the bucket's padding rows too;
neither is work the algorithm needs, so both are in the time and not
in the FLOPs."""

from perf.harness import linear_attn as la
from perf.harness.readers import registry_count


def read(record):
    shape = la.sizes(record)
    got = la.scope_seconds(record, la.PREFILL_PROGRAMS, la.PREFILL_MODULE,
                           la.SCAN_SCOPE)
    rows = registry_count(record, "decode_prefill_tokens_total")
    if not shape or not got or not rows:
        return None
    return (100.0 * la.scan_flops(rows, *shape) / got[0]
            / record["peaks"]["bf16_flops_per_s"])
