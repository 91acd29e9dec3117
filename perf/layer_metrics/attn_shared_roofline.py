"""The one page run: the K and V bytes of the live rows the window's
decode steps had to read (live rows x bytes a stored row x the layers
that read the run a step, ``decode_shared_run_reads_total`` over the
steps) over the device time under ``attn_shared``, as a share of the
chip's HBM bandwidth.  Bound: bytes/s.  The work counted is the
algorithm's, whatever kernel does it."""

from perf.harness import dhd


def read(record):
    got = dhd.scope_seconds(record, dhd.DECODE_PROGRAM, dhd.DECODE_MODULE,
                            dhd.SHARED_SCOPE)
    need = dhd.shared_run_bytes(record)
    if not got or not need:
        return None
    return 100.0 * need / got[0] / record["peaks"]["hbm_bytes_per_s"]
