"""Expert layer: how uneven the routing of the window's decode steps
was: the busiest expert's rows over the mean rows an expert got, per
(layer, step), as a ratio of sums: ``moe_expert_load_max_total`` over
``moe_assignments_total`` / experts.  1 is perfectly even."""

from perf.harness import moe


def read(record):
    top = moe.phase_delta(record, "moe_expert_load_max_total", "decode")
    total = moe.phase_delta(record, "moe_assignments_total", "decode")
    if not top or not total:
        return None
    return top / (total / moe.model_sizes(record)[3])
