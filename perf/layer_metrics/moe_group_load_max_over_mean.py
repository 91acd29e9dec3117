"""Expert layer: how uneven the router's group step was over the
window's decode steps: the live rows that kept the most-kept group over
the mean rows a group was kept by (``moe_groups_chosen_total{group}`` at
phase "decode"), as ``moe_load_max_over_mean`` is of the experts.  1 is
perfectly even."""

from perf.harness import ling_hybrid


def read(record):
    return ling_hybrid.group_load_max_over_mean(record)
