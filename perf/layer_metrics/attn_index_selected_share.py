"""Sparse latent attention: latent rows the window's decode steps read
over cached rows their indexer scored
(``attn_index_rows_selected_total`` / ``attn_index_rows_scored_total``),
in %: what of the cache a step still reads."""

from perf.harness import sparse_latent as sp
from perf.harness.readers import registry_count


def read(record):
    scored = registry_count(record, sp.SCORED)
    selected = registry_count(record, sp.SELECTED)
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
