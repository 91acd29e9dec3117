"""Mamba-1 layers: device time of the bucketed prefills' instructions
under ``ssm_scan`` (the selective scan, row by row), all Mamba layers, per
1,000 LIVE prompt rows the window's prefills took in
(``decode_prefill_tokens_total``: a bucket's padding costs scan time and
is no row of a prompt), in ms."""

from perf.harness import dhd
from perf.harness.readers import registry_count


def read(record):
    got = dhd.scope_seconds(record, dhd.PREFILL_PROGRAMS, dhd.PREFILL_MODULE,
                            dhd.SCAN_SCOPE)
    rows = registry_count(record, "decode_prefill_tokens_total")
    if not dhd.sizes(record) or not got or not rows:
        return None
    return got[0] * 1e3 / (rows / 1000.0)
