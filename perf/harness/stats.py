"""Percentiles, spread and window arithmetic.  Pure Python: the load
generator's parent and the tests use it without jax.  The one clock is
``time.perf_counter`` everywhere; a single reading is off by some
tenth of a millisecond, so no reported span rests on one reading of
less than 250 ms."""


def percentile(values, q):
    """The ``q`` quantile (0..1) with linear interpolation between the
    two nearest order statistics (numpy's default rule).  Raises on an
    empty list: a metric with no sample is left out, never reported 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 0.5)


def quartile_spread(values):
    """(Q3 - Q1) / median with ``statistics.quantiles(values, n=4)``,
    the spread the contract's bounds are set from."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def rate(work, t_open, t_close):
    """Work completed inside the window over the window's length."""
    if t_close <= t_open:
        raise ValueError("window closed before it opened")
    return work / (t_close - t_open)


def in_window(stamps, t_open, t_close):
    """How many timestamps fall inside [t_open, t_close)."""
    return sum(1 for t in stamps if t_open <= t < t_close)
