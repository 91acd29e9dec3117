"""Full layers over a page run: the cached rows ONE full layer read a
decode step, the window's mean (``decode_full_rows_read_total``: the
sum over the seated slots of their lengths, the step's own row counted,
over ``decode_steps_total``).  What the traffic makes the walk kernel
read; x K/V heads x 320 numbers x itemsize x full layers it is a step's
full-layer K/V bytes."""

from perf.harness import mimo
from perf.harness.readers import registry_count


def read(record):
    rows = mimo.counted(record, mimo.FULL_ROWS)
    steps = registry_count(record, mimo.STEPS)
    if not rows or not steps:
        return None
    return rows / steps
