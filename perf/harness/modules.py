"""Which compiled program a device event belongs to.

Instruction names (``fusion.12``) repeat from one compiled program to
the next, so the device time of a program's instructions has to be
taken inside that program's runs: the events of the device plane's
``XLA Modules`` line (``jit__decode_step(<fingerprint>)``), which
``perf/harness/trace.py:load`` does not keep.  On the CPU backend of a
rehearsal there is no such line; its op events carry an ``hlo_module``
stat instead.
"""

import bisect
import re

from perf.harness import trace as tr

MODULES_LINE = "XLA Modules"


def load(source):
    """{device plane: [(module name, start_ns, dur_ns)]} from an xplane
    file or the directory that holds one."""
    from jax.profiler import ProfileData

    path = source if source.endswith(".pb") else tr.find_xplane(source)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[plane.name] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events]
    return out


def seconds_in(trace, modules, program_pattern, names, plane=None):
    """(seconds, events, runs) on one device (the first by name unless
    given), inside the window: the device ops named in ``names`` that
    ran inside a run of a program whose module name matches
    ``program_pattern``; ``runs`` counts those runs.  None when the
    trace holds no such run."""
    lo, hi = tr.window(trace)
    plane = plane or sorted(trace["devices"])[0]
    rx = re.compile(program_pattern)
    evs = tr.in_window(trace["devices"][plane], lo, hi)
    runs = sorted((s, s + d) for n, s, d in (modules or {}).get(plane, ())
                  if rx.search(n) and s + d > lo and s < hi)
    if runs:
        starts = [s for s, _ in runs]

        def inside(ev):
            i = bisect.bisect_right(starts, ev[1]) - 1
            return i >= 0 and ev[1] < runs[i][1]
        n_runs = len(runs)
    else:
        # a rehearsal on the CPU backend: the op's own stat
        def inside(ev):
            return bool(rx.search(str(ev[3].get("hlo_module", ""))))
        n_runs = None
    mine = [ev for ev in evs if inside(ev)]
    if not mine:
        return None
    hit = [ev for ev in mine if tr.bare(ev[0]) in names]
    return sum(ev[2] for ev in hit) / 1e9, len(hit), n_runs
