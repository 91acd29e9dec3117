"""Serving front: the clients' mean time to first token minus the
window's mean of ``decode_ttft_seconds`` (which starts at admission,
not at the client's send): HTTP parse, admission, stream write."""

from perf.harness.readers import registry_mean_ms


def read(record):
    ttft = (record.get("client") or {}).get("ttft_ms")
    engine = registry_mean_ms(record, "decode_ttft_seconds")
    if not ttft or engine is None:
        return None
    return sum(ttft) / len(ttft) - engine
