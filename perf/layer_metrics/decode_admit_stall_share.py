"""Decode engine: share of the window's slot-time in which a seated
sequence produced nothing because another request was being admitted:
``decode_admit_stalled_slot_seconds_total`` (every admission's seconds
times the slots live before it) over ``decode_slot_seconds_total``
(every tick's seconds, with the ``between`` before it, times its live
slots)."""

from perf.harness import tick_account as ta


def read(record):
    return ta.share(
        ta.delta(record, "decode_admit_stalled_slot_seconds_total"),
        ta.delta(record, "decode_slot_seconds_total"))
