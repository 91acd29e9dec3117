"""Latent attention: the FLOPs of absorbed attention over the live
latent rows of the window's decode steps (live rows x layers x heads x
((rank + rope) + rank) x 2) over the device time of the
``latent_paged_attention`` kernel, as a share of the chip's bf16 peak.
Bound: FLOP/s.  With 32 query rows a stored row the MXU's tiles are a
quarter full, so the kernel's own ceiling is well under 100."""

from perf.harness import latent


def read(record):
    got = latent.step_kernel(record)
    if not got:
        return None
    (layers, heads, rank, rope, _, _), seconds, rows = got
    return (100.0 * latent.step_flops(rows, layers, heads, rank, rope)
            / seconds / record["peaks"]["bf16_flops_per_s"])
