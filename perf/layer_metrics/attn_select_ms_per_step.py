"""Sparse latent attention: device time of the decode step's
instructions under ``attn_index_select`` (the choice of each slot's
``index_topk`` rows of largest index score), all layers, per decode
step, in ms."""

from perf.harness import sparse_latent as sp


def read(record):
    return sp.ms_per_step(record, sp.SELECT_SCOPE)
