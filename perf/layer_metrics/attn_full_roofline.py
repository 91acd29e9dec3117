"""Full-attention layer on grouped heads: the K and V bytes of the live
rows the window's decode steps had to read in the full layers (live
rows x 2 x K/V heads x head size x itemsize: the K/V heads' bytes,
whatever the query heads) over the device time of the
``ragged_paged_attention_gqa`` kernel in the decode step, as a share of
the chip's HBM bandwidth.  Bound: bytes/s."""

from perf.harness import exaone
from perf.harness.readers import kernel_seconds
from perf.layer_metrics.rpa_ms_per_step import PROGRAM


def read(record):
    got = kernel_seconds(record, PROGRAM, exaone.GQA_KERNEL)
    if not got or not record.get("kv_bytes"):
        return None
    return (100.0 * record["kv_bytes"] / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
