"""Pallas flash attention, backward: device time in one training step
of the dq and the dk/dv kernels' events, found by the kernels' own names
(``flash_attention_bwd_dq``, ``flash_attention_bwd_dkv``)."""

from perf.harness.program_spans import kernel_ms_per_step


def read(record):
    return kernel_ms_per_step(record, "step", r"flash_attention_bwd_")
