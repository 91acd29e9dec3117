"""Serving front, seen by the client: the 95th percentile of the time
from a send to the first streamed token, over every request sent in
the window: the tail that a prefill ahead in the tick and an admission
convoy make, which the mean of the middle half leaves out."""

from perf.harness import stats


def read(record):
    ttft = (record.get("client") or {}).get("ttft_ms")
    return stats.percentile(ttft, 0.95) if ttft else None
