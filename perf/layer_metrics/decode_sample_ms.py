"""Decode engine: the per-slot end of a tick on the host (expiry check,
argmax or sampling over the vocabulary, emission, eviction): self time
of the program's ``decode.sample`` span per tick that dispatched."""

from perf.harness import program_spans as ps


def read(record):
    trace = record.get("trace")
    n = ps.count(trace, "decode.sample") if trace else 0
    if not n:
        return None
    return ps.self_seconds(trace, "decode.sample") / n * 1e3
