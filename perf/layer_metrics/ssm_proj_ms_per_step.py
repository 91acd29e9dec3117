"""State-space layers' projections: device time of the decode step's
instructions under ``ssm_proj`` (a Mamba-2 layer's in- and
out-projection, which ``ssm`` leaves out), all mamba layers, per decode
step, in ms.  None from a program without the scope."""

from perf.harness import nemotron, ssm


def read(record):
    got = ssm.scope_seconds(record, ssm.DECODE_PROGRAM, ssm.DECODE_MODULE,
                            nemotron.PROJ_SCOPE)
    steps = nemotron.steps(record)
    if not got or not steps:
        return None
    return got[0] / steps * 1e3
