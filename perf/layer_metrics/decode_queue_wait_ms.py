"""Decode engine: ``decode_queue_wait_seconds`` sum/count delta over
the window: from a request's submit to the admission that seats it (its
prefill comes after)."""

from perf.harness.readers import registry_mean_ms


def read(record):
    return registry_mean_ms(record, "decode_queue_wait_seconds")
