"""Gated short-conv layers: device time of the decode step's
instructions under ``short_conv`` (both gates and the one ``conv_step``
call over the slots' tails; not the two projections), all conv layers,
per decode step, in ms."""

from perf.harness import short_conv as sc
from perf.harness.readers import registry_count


def read(record):
    got = sc.scope_seconds(record, sc.DECODE_PROGRAM, sc.DECODE_MODULE,
                           sc.ANY_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
