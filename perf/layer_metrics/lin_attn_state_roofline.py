"""Linear-attention layers: the bytes of the slots' recurrent states a
decode step has to read and write (slots x linear layers x heads x d_v
x d_k float32, once each way: the bytes the algorithm holds, not the
128-lane width the entries are stored at) over the device time under
``lin_attn_state``, as a share of the chip's HBM bandwidth.  Bound:
bytes/s."""

from perf.harness import linear_attn as la
from perf.harness.readers import registry_count


def read(record):
    shape = la.sizes(record)
    got = la.scope_seconds(record, la.DECODE_PROGRAM, la.DECODE_MODULE,
                           la.STATE_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not shape or not got or not steps:
        return None
    slots = int(record["traffic"]["gen_slots"])
    return (100.0 * steps * la.step_state_bytes(slots, *shape) / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
