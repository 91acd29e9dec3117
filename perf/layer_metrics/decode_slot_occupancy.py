"""Decode engine: live slots over lanes computed, in the window's
decode steps: ``decode_active_slot_steps_total`` over
``decode_steps_total`` x the session's slots (a masked lane is computed
and thrown away)."""

from perf.harness.readers import registry_count


def read(record):
    live = registry_count(record, "decode_active_slot_steps_total")
    steps = registry_count(record, "decode_steps_total")
    if not live or not steps:
        return None
    return 100.0 * live / (steps * int(record["traffic"]["gen_slots"]))
