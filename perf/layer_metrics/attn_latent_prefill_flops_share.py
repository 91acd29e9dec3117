"""Latent attention, expanded: the FLOPs of causal attention over the
real rows of the window's bucketed prefills (n (n + 1) / 2 pairs a
prompt of n rows, ``attn_latent_prefill_pairs_total``, x layers x heads
x (q/k head size + v head size) x 2) over the device time of the flash
kernel inside the prefill programs' runs, as a share of the chip's bf16
peak.  Bound: FLOP/s.  The kernel also multiplies the bucket's padding
rows, the causal blocks' masked halves and the zero lanes the values
are padded with to the keys' head size; none of that is work the
algorithm needs, so it is in the time and not in the FLOPs."""

from perf.harness import latent
from perf.harness.readers import registry_count


def read(record):
    shape = latent.sizes(record)
    got = latent.kernel_seconds(record, latent.PREFILL_PROGRAMS,
                                latent.PREFILL_MODULE, latent.PREFILL_KERNEL)
    pairs = registry_count(record, latent.PAIRS_COUNTER)
    if not shape or not got or not pairs:
        return None
    layers, heads, _, _, qk, v = shape
    return (100.0 * latent.prefill_flops(pairs, layers, heads, qk, v)
            / got[0] / record["peaks"]["bf16_flops_per_s"])
