"""Parallel: collective time on device 0 during which no other op runs
there, per step, from the trace."""

from perf.harness import trace as tr


def read(record):
    if not record.get("trace") or not record.get("steps"):
        return None
    return (tr.exposed_collective_seconds(record["trace"])
            / record["steps"] * 1e3)
