"""Shared expert: device time of the decode step's instructions under
``moe_shared`` (the always-on SwiGLU beside the routed experts), all
routed layers, per decode step."""

from perf.harness import exaone
from perf.harness.readers import registry_count


def read(record):
    got = exaone.decode_scope_seconds(record, exaone.MOE_SHARED_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
