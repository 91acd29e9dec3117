"""Decode engine: the program's ``decode.logits_to_host`` span, the wait
for the decode step on the device plus the logits' copy to the host,
mean."""

from perf.harness.program_spans import trace_span_mean_ms


def read(record):
    return trace_span_mean_ms(record, "decode.logits_to_host")
