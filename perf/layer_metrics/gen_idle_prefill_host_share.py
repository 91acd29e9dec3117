"""Decode engine: share of the window in which the device ran nothing
inside a prefill call but outside its wait (``decode.prefill`` less
``decode.prefill_wait``): the host arrays, the jitted call's dispatch,
the layers' report.  With ``gen_idle_prefill_wait_share`` and
``gen_idle_seat_share`` it tiles ``gen_idle_prefill_share``."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.prefill"],
                      outside=["decode.prefill_wait"],
                      witness="decode.prefill_wait")
