"""Expert layer, a chip's share: the live rows' assignments to held
experts (``moe_assignments_total``, phase "decode") over held experts x
routed layers x decode steps, the window's mean: the rows ONE held
expert sees a step.  The deployment's other chips' slots would send it
8 times as many; even routing of 32 slots x 6 over 128 experts reads
1.5."""

from perf.harness import nemotron


def read(record):
    shape = nemotron.sizes(record)
    held = nemotron.held_assignments(record)
    steps = nemotron.steps(record)
    if not shape or not held or not steps:
        return None
    _, _, experts, layers, _, _, _, _ = shape
    return held / (experts * layers * steps)
