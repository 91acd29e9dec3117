"""Sparse latent attention: the bytes of the latent rows the window's
decode steps selected (``attn_index_rows_selected_total`` x layers x
(rank + rope) x itemsize: the ALGORITHM's 1,152 B a row, read once as
key and value both; stored at 640 lanes the ceiling is 90%) over the
device time of the read (what lies under ``attn_sparse`` less the two
absorbed products: the fetch by row and the ``latent_paged_attention``
kernel over the fetched rows), as a share of the chip's HBM bandwidth.
Bound: bytes/s.  A fetch that writes the rows and a kernel that reads
them again move each row three times: that is in the time."""

from perf.harness import sparse_latent as sp


def read(record):
    return sp.share_of_hbm(record, sp.SELECTED, 2, sp.read_seconds(record))
