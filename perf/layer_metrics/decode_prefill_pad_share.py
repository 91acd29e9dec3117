"""Decode engine: rows the window's bucketed prefills computed for
padding, as a share of all the rows they computed:
``decode_prefill_padded_tokens_total`` against
``decode_prefill_tokens_total``.  A program without the two counters
(prefill not bucketed) has nothing to read."""

from perf.harness.readers import registry_count


def read(record):
    padded = registry_count(record, "decode_prefill_padded_tokens_total")
    real = registry_count(record, "decode_prefill_tokens_total")
    if not padded or real is None:
        return None
    return 100.0 * (padded - real) / padded
