"""Decode engine: share of the window in which the device ran nothing
while the next step was uploaded and dispatched (``decode.step``; a
step run whole has its collect inside, which is
``gen_idle_ids_arrival_share``'s).  The second part of
``gen_idle_tick_share``."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.step"],
                      outside=["decode.logits_to_host"])
