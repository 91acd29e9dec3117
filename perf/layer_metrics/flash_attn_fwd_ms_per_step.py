"""Pallas flash attention, forward: device time in one training step of
the kernel events whose op_name holds the kernel's own name
(``pl.pallas_call(name="flash_attention_fwd")``)."""

from perf.harness.program_spans import kernel_ms_per_step


def read(record):
    return kernel_ms_per_step(record, "step", r"flash_attention_fwd")
