"""Decode engine: seconds of one tick that seated no request
(``admitting="0"``), mean over the window, in ms: what a tick costs
when no prefill runs inside it."""

from perf.harness import tick_account as ta


def read(record):
    return ta.ms_per_tick(record, ta.IN_TICK, admitting="0")
