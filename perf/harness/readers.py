"""Shared arithmetic of the per-layer metric readers.

A reader (``perf/layer_metrics/<metric>.py``) is one function
``read(record) -> number or None`` over a run's record: the runner's
spans (``span_seconds``), the registry snapshots (``registry``), the
clients' timestamps (``client``), the reduced trace (``trace``) and the
driver's facts.  A reader that finds nothing to read returns None and
the harness leaves the metric out of the line.
"""

from perf.harness import registry, trace as tr


def span_mean_ms(record, name):
    xs = record.get("span_seconds", {}).get(name)
    return None if not xs else sum(xs) / len(xs) * 1e3


def registry_mean_ms(record, name):
    reg = record.get("registry")
    if not reg:
        return None
    return registry.mean_ms(reg["before"], reg["after"], name)


def registry_count(record, name):
    reg = record.get("registry")
    if not reg:
        return None
    return registry.delta(reg["before"], reg["after"], name)[1]


def busy_per_step_s(record):
    """Device-busy seconds of one step, the mean over the chips."""
    if not record.get("trace") or not record.get("steps"):
        return None
    b = tr.busy(record["trace"])
    if not b:
        return None
    return sum(b.values()) / len(b) / record["steps"]


def step_flops_share(record):
    """Model FLOPs of one step on one chip over the device-busy time
    of one step, as a share of the chip's published bf16 peak."""
    busy = busy_per_step_s(record)
    if not busy or not record.get("train_flops_per_step"):
        return None
    per_chip = record["train_flops_per_step"] / len(record["devices"])
    return 100.0 * per_chip / busy / record["peaks"]["bf16_flops_per_s"]


def kernel_seconds(record, program, pattern):
    """(seconds, events) on the first device of the Pallas custom calls
    of the compiled ``program`` whose op_name matches ``pattern``, or
    None when the text or the trace holds none."""
    from perf.harness import hlo

    text = record.get("compiled_text", {}).get(program)
    if not record.get("trace") or not text:
        return None
    names = hlo.kernel_instructions(text, pattern)
    if not names:
        return None
    secs, n = tr.kernel_seconds(record["trace"], names)
    return (secs, n) if n else None
