"""Decode engine: share of the window's tick seconds (``between``
apart) spent under ``decode.admit``: prefill inside the tick, with the
seating and the first token's emission."""

from perf.harness import tick_account as ta


def read(record):
    return ta.share(ta.seconds(record, ["admit"]),
                    ta.seconds(record, ta.IN_TICK))
