"""Window layers on rings with keys of 192 on values of 128: the bytes
the window's decode steps had to read of the seated slots' rings (seated
slots x the ring's rows x 8 K/V heads x (192 + 128) numbers x itemsize x
window layers: the PUBLISHED numbers, not the lanes a key is stored at)
over the device time of the decode step's instructions under
``attn_window`` (the new rows' write, the rings' gather, scores, sink and
softmax in plain XLA), as a share of the chip's HBM bandwidth.  Bound:
bytes/s.  Every prompt of the cell's traffic is longer than a ring, so a
seated slot's ring is full."""

from perf.harness import exaone, mimo
from perf.harness.readers import registry_count


def read(record):
    sizes = mimo.sizes(record)
    got = exaone.decode_scope_seconds(record, exaone.ATTN_WINDOW_SCOPE)
    slot_steps = registry_count(record, "decode_active_slot_steps_total")
    if not sizes or not got or not slot_steps:
        return None
    _, window_layers, _, kv_heads, numbers, ring_rows, itemsize = sizes
    return (100.0 * mimo.ring_bytes(slot_steps, ring_rows, window_layers,
                                    kv_heads, numbers, itemsize)
            / got[0] / record["peaks"]["hbm_bytes_per_s"])
