"""Pallas flash attention: device time of the kernel's forward and
backward events in one training step."""

from perf.harness.readers import kernel_seconds

# the jitted wrappers of paddle_tpu/pallas/flash_attention.py: a Pallas
# custom call's op_name holds the jitted function it was traced in
PROGRAM, PATTERN = "step", r"_flash_(fwd|bwd)_impl"


def read(record):
    got = kernel_seconds(record, PROGRAM, PATTERN)
    if not got or not record.get("steps"):
        return None
    return got[0] / record["steps"] * 1e3
