"""State-space layers: device time of the bucketed prefills'
instructions under ``ssm_scan`` (the chunked recurrence: the decay
masks, the per-chunk matmuls and the scan over chunks), all mamba
layers, per 1,000 bucket rows the window's prefills computed
(``decode_prefill_padded_tokens_total``), in ms."""

from perf.harness import ssm
from perf.harness.readers import registry_count


def read(record):
    got = ssm.scope_seconds(record, ssm.PREFILL_PROGRAMS, ssm.PREFILL_MODULE,
                            ssm.SCAN_SCOPE)
    rows = registry_count(record, "decode_prefill_padded_tokens_total")
    if not got or not rows:
        return None
    return got[0] * 1e3 / (rows / 1000.0)
