"""Latent attention: the bytes of the live latent rows the window's
decode steps had to read (live rows x layers x (rank + rope) x
itemsize: the ALGORITHM's 576 numbers a row, read once as key and
value both; stored at 640 lanes the kernel's ceiling is 90%) over the
device time of the ``latent_paged_attention`` kernel in the decode
step, as a share of the chip's HBM bandwidth.  Bound: bytes/s; read it
beside ``attn_latent_flops_share``: the kernel sits at the ridge."""

from perf.harness import latent


def read(record):
    got = latent.step_kernel(record)
    if not got:
        return None
    (layers, _, rank, rope, _, _), seconds, rows = got
    return (100.0 * latent.step_bytes(rows, layers, rank, rope) / seconds
            / record["peaks"]["hbm_bytes_per_s"])
