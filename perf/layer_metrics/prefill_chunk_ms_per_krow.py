"""A prompt's chunks over a state entry: device time of the window's
runs of the chunk programs (``_prefill_state_chunk``: every layer of a
chunk), per 1,000 REAL chunk rows (``decode_prefill_chunk_rows_total``),
in ms: what a row past the top bucket costs, to set beside a bucket
row's price (``prefill_*_ms`` over the bucket's rows)."""

from perf.harness import short_conv as sc


def read(record):
    got = sc.program_seconds(record, sc.CHUNK_PROGRAMS, sc.CHUNK_MODULE)
    rows = sc.counted(record, sc.CHUNK_ROWS)
    if not got or not rows:
        return None
    return got[0] * 1e3 / (rows / 1000.0)
