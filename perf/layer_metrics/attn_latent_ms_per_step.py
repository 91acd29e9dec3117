"""Latent attention: device time of the decode step's instructions
under ``attn_latent`` (the query and down projections, the norm and the
rotation, the row's write, the two absorbed products, the
``latent_paged_attention`` kernel and the output projection), all
layers, per decode step, in ms."""

from perf.harness import latent
from perf.harness.readers import registry_count


def read(record):
    got = latent.scope_seconds(record, latent.DECODE_PROGRAM,
                               latent.DECODE_MODULE, latent.ANY_SCOPE)
    steps = registry_count(record, "decode_steps_total")
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
