"""Expert layer, a chip's share: the bytes of the gate, up and down
matrices of the HELD experts the window's decode steps hit
(``moe_experts_hit_total`` at phase "decode", which counts held experts
only, x 3 x d x the routed experts' width x itemsize) over the device
time under ``moe_experts`` in the decode step, as a share of the chip's
HBM bandwidth.  Bound: bytes/s."""

from perf.harness import exaone, moe


def read(record):
    hit = moe.phase_delta(record, "moe_experts_hit_total", "decode")
    got = moe.scope_seconds(record, moe.DECODE_PROGRAM, moe.DECODE_MODULE,
                            moe.EXPERTS_SCOPE)
    if not hit or not got:
        return None
    d, f, itemsize = exaone.sizes(record)
    return (100.0 * exaone.held_expert_bytes(hit, d, f, itemsize) / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
