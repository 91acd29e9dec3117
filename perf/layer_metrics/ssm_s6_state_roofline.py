"""Mamba-1 layers: the bytes of the live slots' recurrent states the
window's decode steps had to read and write (live slot-steps,
``decode_active_slot_steps_total``, x Mamba layers x channels x state
size float32, once each way; a slot seated nowhere moves the null entry
and is not counted) over the device time under ``ssm_state`` (the
``s6_step`` kernel), as a share of the chip's HBM bandwidth.  Bound:
bytes/s."""

from perf.harness import dhd
from perf.harness.readers import registry_count


def read(record):
    shape = dhd.sizes(record)
    got = dhd.scope_seconds(record, dhd.DECODE_PROGRAM, dhd.DECODE_MODULE,
                            dhd.STATE_SCOPE)
    live = registry_count(record, "decode_active_slot_steps_total")
    if not shape or not got or not live:
        return None
    return (100.0 * dhd.step_state_bytes(live, *shape) / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
