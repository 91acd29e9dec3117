"""Sparse latent attention: device time of the prefill programs'
instructions under ``attn_index`` and ``attn_index_select`` (the
indexer's projections, the ``index_scores`` kernel, the bisection for
each row's ``index_topk``-th score and the mask), all layers, per run of
a prefill program (a bucket's, or a chunk's over cached rows), in ms."""

from perf.harness import sparse_latent as sp
from perf.harness.readers import registry_count


def read(record):
    got = [sp.scope_seconds(record, sp.PREFILL_PROGRAMS, sp.PREFILL_MODULE,
                            scope)
           for scope in (sp.INDEX_SCOPE, sp.SELECT_SCOPE)]
    if not all(got):
        return None
    # a rehearsal's trace has no line of module runs: the program's count
    runs = got[0][2] or registry_count(record, "decode_prefill_seconds")
    return sum(g[0] for g in got) / runs * 1e3 if runs else None
