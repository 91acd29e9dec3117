"""Decode engine: ticks of the window that took far longer than their
kind does (``decode_slow_ticks_total``, any phase): 0 in a sound
window.  Each one is in the run's log with its seconds by phase."""

from perf.harness import tick_account as ta


def read(record):
    return ta.delta(record, "decode_slow_ticks_total")
