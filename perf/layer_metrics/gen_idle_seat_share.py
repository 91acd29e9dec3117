"""Decode engine: share of the window in which the device ran nothing
inside an admission but outside its prefill (``decode.admit`` less
``decode.prefill``): pages, tables, the first token's emission.
``gen_idle_prefill_share`` less the idle inside the prefill call."""

from perf.harness.program_spans import idle_share


def read(record):
    return idle_share(record.get("trace"), ["decode.admit"],
                      outside=["decode.prefill"])
