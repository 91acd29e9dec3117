"""Lowerings + AMP: the LM's FLOPs of one step (blocks, head and causal
attention per token, times 3 for backward) over the device-busy time of
one step from the trace, as a share of the chip's bf16 peak."""

from perf.harness.readers import step_flops_share as read  # noqa: F401
