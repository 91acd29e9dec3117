"""Expert layer: the bytes of the gate, up and down matrices of the
experts the window's decode steps hit (``moe_experts_hit_total`` at
phase "decode" x 3 x d x f x itemsize) over the device time under
``moe_experts`` in the decode step, as a share of the chip's HBM
bandwidth.  Bound: bytes/s."""

from perf.harness import moe


def read(record):
    hit = moe.phase_delta(record, "moe_experts_hit_total", "decode")
    got = moe.scope_seconds(record, moe.DECODE_PROGRAM, moe.DECODE_MODULE,
                            moe.EXPERTS_SCOPE)
    if not hit or not got:
        return None
    d, f, _, _, itemsize = moe.model_sizes(record)
    return (100.0 * moe.expert_weight_bytes(hit, d, f, itemsize) / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
