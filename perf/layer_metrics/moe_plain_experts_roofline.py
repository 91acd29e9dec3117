"""Expert layer of two-matrix experts, a chip's share: the bytes of the
up and down matrices of the HELD experts the window's decode steps hit
(``moe_experts_hit_total`` at phase "decode", which counts held experts
only, x 2 x d x the routed experts' PUBLISHED width x itemsize:
``perf/harness/nemotron.py``) over the device time under ``moe_experts``
in the decode step, as a share of the chip's HBM bandwidth.  Bound:
bytes/s.  None for a configuration whose experts are not of this form."""

from perf.harness import moe, nemotron


def read(record):
    shape = nemotron.sizes(record)
    hit = moe.phase_delta(record, "moe_experts_hit_total", "decode")
    got = moe.scope_seconds(record, moe.DECODE_PROGRAM, moe.DECODE_MODULE,
                            moe.EXPERTS_SCOPE)
    if not shape or not hit or not got:
        return None
    d, f, _, _, _, _, _, itemsize = shape
    return (100.0 * nemotron.plain_expert_bytes(hit, d, f, itemsize) / got[0]
            / record["peaks"]["hbm_bytes_per_s"])
