"""Sparse latent attention: device time of the decode step's
instructions under ``attn_index`` (the indexer's projections, the index
row's norm, rotation and write, and the ``paged_index_scores`` kernel
over the slots' cached index rows), all layers, per decode step, in
ms."""

from perf.harness import sparse_latent as sp


def read(record):
    return sp.ms_per_step(record, sp.INDEX_SCOPE)
