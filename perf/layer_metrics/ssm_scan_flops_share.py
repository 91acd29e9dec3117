"""State-space layers: the FLOPs of the recurrence over the real prompt
rows of the window's bucketed prefills (6 x heads x channels x state
size a row a layer, ``decode_prefill_tokens_total``) over the device
time under ``ssm_scan``, as a share of the chip's bf16 peak.  Bound:
FLOP/s.  The chunked form multiplies more than the recurrence needs
(the chunk's square masks), in float32 at six passes, and the bucket's
padding rows too; none of that is work the algorithm needs, so all of
it is in the time and not in the FLOPs."""

from perf.harness import ssm
from perf.harness.readers import registry_count


def read(record):
    shape = ssm.sizes(record)
    got = ssm.scope_seconds(record, ssm.PREFILL_PROGRAMS, ssm.PREFILL_MODULE,
                            ssm.SCAN_SCOPE)
    rows = registry_count(record, "decode_prefill_tokens_total")
    if not shape or not got or not rows:
        return None
    return (100.0 * ssm.scan_flops(rows, *shape) / got[0]
            / record["peaks"]["bf16_flops_per_s"])
