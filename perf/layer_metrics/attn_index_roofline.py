"""Sparse latent attention: the bytes of the index rows the window's
decode steps had to score (``attn_index_rows_scored_total`` x layers x
index_head_dim x itemsize: 256 B a row) over the device time of the
``paged_index_scores`` kernel in the decode step, as a share of the
chip's HBM bandwidth.  Bound: bytes/s (32 FLOP a byte).  The kernel
reads whole turns of 25 pages: a slot's last turn is part padding, which
is in the time and not in the bytes."""

from perf.harness import sparse_latent as sp


def read(record):
    return sp.share_of_hbm(record, sp.SCORED, 1,
                           sp.index_kernel_seconds(record))
