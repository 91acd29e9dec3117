"""The program's own spans in a reduced trace, as numbers.

``paddle_tpu.observability.span`` writes every span of the program as a
``TraceAnnotation``; in a traced run they land in the profile's host
plane, on the device ops' clock, and ``trace.load`` keeps them in
``trace["host"]`` as ``(thread, name, start_ns, dur_ns)`` beside the
runner's ``perf.*`` spans.  Pure functions over those tuples.

Nesting is by name, not by thread: the profile names every Python
thread's line after the process, so a line's name does not tell the
threads apart.  That is sound for what is read here: every ``decode.*``
span comes from the one stepper thread, and every ``executor.*`` span
of a training cell from the loop's thread.

A program that writes no such span (a parent commit) gives empty lists
here, and the readers built on them return None.
"""

from perf.harness import trace as tr


def intervals(trace, names, lo=None, hi=None):
    """Disjoint sorted (start, end) union of the host spans whose name
    is in ``names``, clipped to the window."""
    if lo is None:
        lo, hi = tr.window(trace)
    names = set(names)
    return tr.clip(tr.union((s, s + d) for _, n, s, d in trace["host"]
                            if n in names), lo, hi)


def intersect(a, b):
    """Parts of the disjoint sorted intervals ``a`` that ``b`` covers."""
    return tr.subtract(a, tr.subtract(a, b))


def durations(trace, name):
    """Seconds of each span of that name that starts inside the
    window."""
    lo, hi = tr.window(trace)
    return [d / 1e9 for _, n, s, d in trace["host"]
            if n == name and lo <= s < hi]


def count(trace, name):
    return len(durations(trace, name))


def mean_ms(trace, name):
    """Mean duration, in ms, of the spans of that name that start
    inside the window; None when there is none."""
    xs = durations(trace, name)
    return sum(xs) / len(xs) * 1e3 if xs else None


def self_seconds(trace, name, children=()):
    """Seconds inside the window covered by spans named ``name`` and by
    none of the spans named in ``children``."""
    own = intervals(trace, [name])
    return tr.measure(tr.subtract(own, intervals(trace, children))) / 1e9


def idle_under(trace, names, outside=(), plane=None):
    """Seconds inside the window in which no op ran on one device (the
    first, by name, unless given) while a span named in ``names`` was
    open and none named in ``outside`` was."""
    lo, hi = tr.window(trace)
    plane = plane or sorted(trace["devices"])[0]
    ran = tr.clip(tr.union((ev[1], ev[1] + ev[2])
                           for ev in trace["devices"][plane]), lo, hi)
    idle = tr.subtract([(lo, hi)], ran)
    under = tr.subtract(intervals(trace, names, lo, hi),
                        intervals(trace, outside, lo, hi))
    return tr.measure(intersect(idle, under)) / 1e9


def idle_share(trace, names, outside=(), witness="decode.tick"):
    """``idle_under`` as a percentage of the window.  None where the
    trace holds no ``witness`` span: a program that writes no spans, as
    against one in which no span of ``names`` happened to open (0)."""
    if not trace or not trace.get("devices"):
        return None
    lo, hi = tr.window(trace)
    if not intervals(trace, [witness], lo, hi):
        return None
    return 100.0 * idle_under(trace, names, outside) / ((hi - lo) / 1e9)


# -- over a run's record: what several readers share ------------------------


def trace_span_mean_ms(record, name):
    """Mean of the program's span ``name`` in the traced window, ms
    (``readers.span_mean_ms`` reads the runner's own spans)."""
    trace = record.get("trace")
    return mean_ms(trace, name) if trace else None


def kernel_ms_per_step(record, program, pattern):
    """Device ms in one training step of the Pallas kernels of the
    compiled ``program`` whose op_name matches ``pattern``."""
    from perf.harness.readers import kernel_seconds

    got = kernel_seconds(record, program, pattern)
    if not got or not record.get("steps"):
        return None
    return got[0] / record["steps"] * 1e3


def exec_prepare_ms(record):
    """Executor: what one ``exe.run`` spends on the host before it can
    dispatch — feed conversion, the program key with the feed signature
    and the cache lookup, and gathering the state from the scope — mean
    per ``executor.run`` span of the traced window, in ms."""
    trace = record.get("trace")
    runs = count(trace, "executor.run") if trace else 0
    if not runs:
        return None
    parts = ("executor.feed", "executor.lookup", "executor.gather_state")
    return sum(sum(durations(trace, p)) for p in parts) / runs * 1e3


def exec_dispatch_ms(record):
    """Executor: the jitted step's dispatch (``executor.step``: the
    call returns before the device is done), mean in ms."""
    return trace_span_mean_ms(record, "executor.step")
