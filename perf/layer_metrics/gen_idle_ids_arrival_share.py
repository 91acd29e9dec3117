"""Decode engine: share of the window in which the device ran nothing
while the stepper waited for a step's ids (``decode.logits_to_host``):
from the device's last op to the ids in the stepper's hands.  The first
part of ``gen_idle_tick_share``."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.logits_to_host"])
