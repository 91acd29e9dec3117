"""Decode engine: share of the window in which the device ran nothing
while an admission (``decode.admit``: the eager prefill and the first
token) was open on the stepper thread."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.admit"])
