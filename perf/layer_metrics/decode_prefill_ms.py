"""Decode engine: ``decode_prefill_seconds`` sum/count delta over the
window: one sequence's prefill at admission, on the host's clock."""

from perf.harness.readers import registry_mean_ms


def read(record):
    return registry_mean_ms(record, "decode_prefill_seconds")
