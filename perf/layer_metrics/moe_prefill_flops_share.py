"""Expert layer: the FLOPs of the three grouped GEMMs over the real
prompt rows of the window's bucketed prefills (6 x d x f x top-k a row
a layer = 6 d f per assignment, ``moe_assignments_total`` at phase
"prefill") over the device time under ``moe_experts`` in the prefill
programs, as a share of the chip's bf16 peak.  Bound: FLOP/s.  The
kernel also multiplies the bucket's padding rows; they are not work
the algorithm needs, so they are in the time and not in the FLOPs."""

from perf.harness import moe


def read(record):
    pairs = moe.phase_delta(record, "moe_assignments_total", "prefill")
    got = moe.scope_seconds(record, moe.PREFILL_PROGRAMS,
                            moe.PREFILL_MODULE, moe.EXPERTS_SCOPE)
    if not pairs or not got:
        return None
    d, f = moe.model_sizes(record)[:2]
    return (100.0 * moe.expert_flops(pairs, d, f) / got[0]
            / record["peaks"]["bf16_flops_per_s"])
