"""Decode engine: the host's work of one tick that no step in flight
covers, admissions apart, mean over the window's ticks in ms: the
phases ``decide``, ``sweep``, ``cow``, ``upload``, ``dispatch``,
``other`` (between the ids' arrival and the next dispatch) and
``between`` (from a tick's end to the next one's start)."""

from perf.harness import tick_account as ta


def read(record):
    return ta.ms_per_tick(record, ta.EXPOSED)
