"""State-space layers: device time of the decode step's instructions
under ``ssm`` (the conv over the slots' tails, the one-token update of
their states, the skip, the gate and the norm; not the two
projections), all mamba layers, per decode step, in ms."""

from perf.harness import ssm
from perf.harness.readers import registry_count


def read(record):
    got = ssm.scope_seconds(record, ssm.DECODE_PROGRAM, ssm.DECODE_MODULE,
                            ssm.ANY_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
