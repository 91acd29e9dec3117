"""Serving front: share of the window in which the device ran nothing
because the stepper had no request (``decode.idle_wait``)."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.idle_wait"])
