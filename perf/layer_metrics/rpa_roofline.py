"""Ragged paged attention: the K and V bytes of the live tokens the
window's decode steps had to read over the kernel's device time, as a
share of the chip's HBM bandwidth.  Bound: bytes/s."""

from perf.harness.readers import kernel_seconds
from perf.layer_metrics.rpa_ms_per_step import PATTERN, PROGRAM


def read(record):
    got = kernel_seconds(record, PROGRAM, PATTERN)
    if not got or not record.get("kv_bytes"):
        return None
    return (100.0 * record["kv_bytes"] / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
