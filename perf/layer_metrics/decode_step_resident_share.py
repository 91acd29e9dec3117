"""Decode engine: share of the window's dispatched steps whose tables,
lengths and tokens were all what the device already held
(``decode_step_inputs_total{source="resident"}``): a steady tick
uploads nothing."""

from perf.harness import tick_account as ta


def read(record):
    resident = ta.delta(record, "decode_step_inputs_total",
                        source="resident")
    return ta.share(resident, ta.delta(record, "decode_step_inputs_total"))
