"""Decode engine: share of the window in which the device ran nothing
inside a ``decode.tick`` but outside its admissions: upload, dispatch,
the logits' copy, sampling, scheduling.  A tick in flight when the
profile starts or stops is not in the profile, but its children that
ran inside it are: they stand for it."""

from perf.harness.program_spans import idle_share

TICK = ["decode.tick", "decode.sweep", "decode.cow", "decode.step",
        "decode.sample"]


def read(record):
    return idle_share(record.get("trace"), TICK, outside=["decode.admit"])
