"""Latent attention in a hybrid: the bytes of the live latent rows the
window's decode steps had to read, counted for the configuration's
LATENT layers alone (``layer_types``; ``attn_latent_roofline`` counts
every layer), over the device time of the ``latent_paged_attention``
kernel in the decode step, as a share of the chip's HBM bandwidth.
Bound: bytes/s; stored at 640 lanes for the algorithm's 576 the
kernel's ceiling is 90%."""

from perf.harness import ling_hybrid


def read(record):
    return ling_hybrid.latent_kernel_share(record)
