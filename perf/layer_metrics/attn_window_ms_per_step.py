"""Window layers on rings: device time of the decode step's
instructions under ``attn_window`` (the new rows' write into the ring,
the ring's gather, scores, mask and softmax in plain XLA), all sliding
layers, per decode step."""

from perf.harness import exaone
from perf.harness.readers import registry_count


def read(record):
    got = exaone.decode_scope_seconds(record, exaone.ATTN_WINDOW_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
