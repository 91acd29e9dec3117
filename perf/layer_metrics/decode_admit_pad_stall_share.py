"""Decode engine: the points of ``decode_admit_stall_share`` spent on
padding, if a prefill's seconds go by its rows: the ``kind="pad"``
children of ``decode_admit_stalled_slot_seconds_total`` (every seated
admission's seconds x the slots live before it x pad rows / bucket
rows) over ``decode_slot_seconds_total``, in %."""

from perf.harness import skeleton as sk
from perf.harness import tick_account as ta


def read(record):
    return ta.share(
        sk.family_delta(record, "decode_admit_stalled_slot_seconds_total",
                        kind="pad"),
        ta.delta(record, "decode_slot_seconds_total"))
