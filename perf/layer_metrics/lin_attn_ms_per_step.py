"""Linear-attention layers: device time of the decode step's
instructions under ``lin_attn`` (the conv over the slots' tails, the
one-token update of their states, the gated per-head norm; not the
projections), all linear layers, per decode step, in ms."""

from perf.harness import linear_attn as la
from perf.harness.readers import registry_count


def read(record):
    got = la.scope_seconds(record, la.DECODE_PROGRAM, la.DECODE_MODULE,
                           la.ANY_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
