"""Ragged paged attention: device time of the kernel's events in one
decode step (all layers)."""

from perf.harness.readers import kernel_seconds, registry_count

# every Pallas custom call of the jitted decode step is the ragged
# paged-attention kernel (paddle_tpu/decode/attention.py)
PROGRAM, PATTERN = "decode_step", r"_decode_step"


def read(record):
    got = kernel_seconds(record, PROGRAM, PATTERN)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
