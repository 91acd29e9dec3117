"""Decode engine: requests seated by one tick that seated any, mean
over the window: ``decode_tick_admissions_total{n}``, counted where the
admission happens (``4+`` counts as 4: a lower bound from there on).
1 when no two requests queue behind one another's prefill; the depth
of a convoy otherwise."""

from perf.harness import tick_account as ta

WEIGHTS = {"1": 1, "2": 2, "3": 3, "4+": 4}


def read(record):
    by_n = {n: ta.delta(record, "decode_tick_admissions_total", n=n)
            for n in WEIGHTS}
    if None in by_n.values() or not sum(by_n.values()):
        return None
    return (sum(WEIGHTS[n] * c for n, c in by_n.items())
            / sum(by_n.values()))
