"""A prefill that stops half-way down: the rows the window's bucketed
prefills computed in the layers that read another layer's cache (the
cross-decoder) over the rows they computed in the layers that fill one
(the self-decoder), ``decode_prefill_rows_total{part}``, in %.  One row
a prompt over its bucket's rows when the prefill stops (0.03% at a mean
bucket of 3,500 rows); 100% when every layer runs on every row."""

from perf.harness import dhd


def read(record):
    own = dhd.prefill_rows(record, "self")
    cross = dhd.prefill_rows(record, "cross")
    if not own or cross is None:
        return None
    return 100.0 * cross / own
