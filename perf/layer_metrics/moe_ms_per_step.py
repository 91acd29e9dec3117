"""Expert layer: device time of the decode step's instructions under
a ``moe_`` scope (router, dispatch, the grouped GEMMs, combine), all
layers, per decode step."""

from perf.harness import moe
from perf.harness.readers import registry_count


def read(record):
    got = moe.scope_seconds(record, moe.DECODE_PROGRAM, moe.DECODE_MODULE,
                            moe.ANY_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
