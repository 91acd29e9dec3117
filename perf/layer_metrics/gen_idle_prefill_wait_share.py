"""Decode engine: share of the window in which the device ran nothing
while the stepper waited for a prefill program's logits row
(``decode.prefill_wait``: the program's run and the row's copy to the
host; the device is idle here before the program starts and after its
last op).  With ``gen_idle_prefill_host_share`` and
``gen_idle_seat_share`` it tiles ``gen_idle_prefill_share``."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.prefill_wait"],
                      witness="decode.prefill_wait")
