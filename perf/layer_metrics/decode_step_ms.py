"""Decode engine: ``decode_step_seconds`` sum/count delta over the
window: one batched decode step on the host's clock (dispatch, the
wait for the device and the logits' copy to the host)."""

from perf.harness.readers import registry_mean_ms


def read(record):
    return registry_mean_ms(record, "decode_step_seconds")
