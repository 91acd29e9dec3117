"""Sparse latent attention: device time of the decode step's
instructions under ``attn_sparse`` (the fetch of the selected latent
rows through the page table, the two absorbed products and the
``latent_paged_attention`` kernel over the fetched rows), all layers,
per decode step, in ms."""

from perf.harness import sparse_latent as sp


def read(record):
    return sp.ms_per_step(record, sp.SPARSE_SCOPE)
