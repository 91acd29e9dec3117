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


def interquartile_mean(values):
    """The mean of the middle half: the sorted samples from index
    ``n // 4`` to ``n - n // 4``.  Where the samples are a mixture of
    populations with a gap at the 50th percentile, a few percent of
    them crossing the gap move the median by the gap's width and this
    by about twice their share of it; a stall in 1% of the samples is
    outside the half it takes.  Raises on fewer than four samples, as
    ``percentile`` raises on none."""
    xs = sorted(values)
    n = len(xs)
    if n < 4:
        raise ValueError("interquartile mean of fewer than four samples")
    mid = xs[n // 4:n - n // 4]
    return sum(mid) / len(mid)


def follower_share(stamps, within):
    """The share of the timestamps that come at most ``within`` after
    another one: of a closed loop's sends, the requests that arrive in
    a convoy behind another client's."""
    xs = sorted(stamps)
    if not xs:
        raise ValueError("follower share of no samples")
    return sum(1 for a, b in zip(xs, xs[1:]) if b - a <= within) / len(xs)


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
