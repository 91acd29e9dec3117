"""Decode engine, seen by the client: 95th percentile of the gap
between consecutive streamed tokens of a request."""

from perf.harness import stats


def read(record):
    itl = (record.get("client") or {}).get("itl_ms")
    return stats.percentile(itl, 0.95) if itl else None
