"""The one page run, read by its owner and by every cross layer: device
time of the decode step's instructions under ``attn_shared`` (the
owner's write of the step's row and the eight grouped paged-attention
calls over the same pages), per decode step, in ms."""

from perf.harness import dhd
from perf.harness.readers import registry_count


def read(record):
    got = dhd.scope_seconds(record, dhd.DECODE_PROGRAM, dhd.DECODE_MODULE,
                            dhd.SHARED_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
