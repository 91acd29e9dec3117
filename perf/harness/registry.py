"""Deltas of the program's observability registry over a window.

The registry's percentiles are interpolated inside fixed buckets, so
the benchmark takes only sums and counts from it and computes every
percentile from its own client-side timestamps.
"""


def totals(snapshot, name):
    """(sum, count) of a histogram family, or (value, value) of a
    counter family, summed over all label sets."""
    fam = snapshot.get(name)
    if not fam:
        return 0.0, 0.0
    s = c = 0.0
    for v in fam["values"]:
        if "count" in v:
            s += v["sum"]
            c += v["count"]
        else:
            s += v["value"]
            c += v["value"]
    return s, c


def delta(before, after, name):
    """(sum delta, count delta) of ``name`` between two snapshots."""
    s0, c0 = totals(before, name)
    s1, c1 = totals(after, name)
    return s1 - s0, c1 - c0


def mean_ms(before, after, name):
    """Mean of a histogram's observations inside the window, in ms;
    None when it saw none."""
    s, c = delta(before, after, name)
    return None if c <= 0 else s / c * 1e3
