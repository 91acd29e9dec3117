"""A prompt's chunks over a state entry: the real prompt rows the
window's prefills ran in chunks after the top bucket
(``decode_prefill_chunk_rows_total``) over all the prompt rows they ran
(``decode_prefill_tokens_total``), in %."""

from perf.harness import short_conv as sc
from perf.harness.readers import registry_count


def read(record):
    rows = sc.counted(record, sc.CHUNK_ROWS)
    total = registry_count(record, "decode_prefill_tokens_total")
    if not rows or not total:
        return None
    return 100.0 * rows / total
