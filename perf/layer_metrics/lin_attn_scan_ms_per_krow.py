"""Linear-attention layers: device time of the bucketed prefills'
instructions under ``lin_attn_scan`` (the chunked gated delta rule:
the per-chunk matmuls, the triangular solve and the scan over chunks),
all linear layers, per 1,000 bucket rows the window's prefills
computed (``decode_prefill_padded_tokens_total``), in ms."""

from perf.harness import linear_attn as la
from perf.harness.readers import registry_count


def read(record):
    got = la.scope_seconds(record, la.PREFILL_PROGRAMS, la.PREFILL_MODULE,
                           la.SCAN_SCOPE)
    rows = registry_count(record, "decode_prefill_padded_tokens_total")
    if not got or not rows:
        return None
    return got[0] * 1e3 / (rows / 1000.0)
