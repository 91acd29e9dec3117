"""Reduction of a jax profiler trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone (``benchmark/xprof.py``,
which PR 28 deleted, had the right idea and needed TensorFlow's
protobufs); the category rules are copied from it.  Everything after
``load`` works on plain tuples, so the arithmetic is tested on small
hand-made traces.

A trace is reduced inside a *window*: the host span named
``WINDOW_SPAN`` that the driver writes round the traced loop.  Device
and host events share the profile's clock.
"""

import glob
import os
import re

WINDOW_SPAN = "perf.window"
OPS_LINE = "XLA Ops"

_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective"
                         r"|permute|all-to-all")
_CATEGORY_RULES = [
    (re.compile(r"convolution|conv(\.|$|\d)"), "conv"),
    (re.compile(r"dot(\.|$|\d|_general)|matmul"), "matmul"),
    (_COLLECTIVE, "collective"),
    (re.compile(r"custom-call|custom_call|pallas|mosaic"), "kernel"),
    (re.compile(r"copy|transpose|bitcast"), "copy/transpose"),
    (re.compile(r"reduce-window|select-and-scatter"), "pooling"),
    (re.compile(r"reduce"), "reduce"),
    (re.compile(r"fusion|fused"), "fusion(elementwise)"),
    (re.compile(r"infeed|outfeed|send|recv"), "io"),
]


def categorize(name, stats=None):
    """Category of a device op: the profiler's own ``hlo_category``
    where the event carries one, else the rules on the instruction
    name (only the part left of " = ")."""
    cat = (stats or {}).get("hlo_category")
    if cat:
        return str(cat)
    low = name.split(" = ")[0].lower()
    for rx, cat in _CATEGORY_RULES:
        if rx.search(low):
            return cat
    return "other"


def is_collective(name, stats=None):
    text = (name + " " + str((stats or {}).get("hlo_category", ""))).lower()
    return bool(_COLLECTIVE.search(text))


# -- loading ---------------------------------------------------------------


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(source):
    """``{"devices": {plane name: [(name, start_ns, dur_ns, stats)]},
    "host": [(thread, name, start_ns, dur_ns)]}`` from an xplane file,
    a directory holding one, or a ``ProfileData``.  Only the device
    planes' ``XLA Ops`` lines and the host plane's named spans are
    kept."""
    from jax.profiler import ProfileData

    if isinstance(source, str):
        path = source if source.endswith(".pb") else find_xplane(source)
        source = ProfileData.from_file(path)
    devices, host, cpu_ops = {}, [], []
    for plane in source.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[plane.name] = [
                    (ev.name, float(ev.start_ns), float(ev.duration_ns),
                     {k: v for k, v in ev.stats
                      if isinstance(v, (str, int, float))})
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0 or ev.name.startswith(
                            ("ThreadpoolListener", "$")):
                        continue
                    stats = {k: v for k, v in ev.stats
                             if isinstance(v, (str, int, float))}
                    if "hlo_op" in stats:   # XLA:CPU runs ops on the host
                        cpu_ops.append((ev.name, float(ev.start_ns),
                                        float(ev.duration_ns), stats))
                    else:
                        host.append((line.name, ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    if not devices and cpu_ops:
        # a rehearsal on the CPU backend: its ops stand in for a device
        # so that the reduction's control flow runs; never a result
        devices["/host:CPU (rehearsal)"] = cpu_ops
    return {"devices": devices, "host": host}


# -- interval arithmetic ---------------------------------------------------


def union(intervals):
    """Disjoint sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """[(name, self_ns, stats)]: each event's duration minus the part
    its nested children cover (a ``while`` or ``call`` op spans its
    body's ops on the same line)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out, stack = [], []   # stack of [end, index into out]
    for name, start, dur, stats in evs:
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(dur, stack[-1][0] - start)
        out.append([name, dur, stats])
        stack.append([start + dur, len(out) - 1])
    return [(n, max(d, 0.0), st) for n, d, st in out]


# -- the reduction ---------------------------------------------------------


def window(trace):
    """(start_ns, end_ns) of the traced window: the WINDOW_SPAN host
    span, else the extent of all device events."""
    spans = [(s, s + d) for _, n, s, d in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    evs = [ev for d in trace["devices"].values() for ev in d]
    if not evs:
        raise ValueError("the trace holds no device event and no window")
    return (min(ev[1] for ev in evs), max(ev[1] + ev[2] for ev in evs))


def in_window(events, lo, hi):
    return [ev for ev in events if ev[1] + ev[2] > lo and ev[1] < hi]


def busy(trace, lo=None, hi=None):
    """{plane: busy seconds}: the union of the intervals in which an
    operation ran on that device, inside the window."""
    if lo is None:
        lo, hi = window(trace)
    return {p: measure(clip(union((ev[1], ev[1] + ev[2]) for ev in evs),
                            lo, hi)) / 1e9
            for p, evs in trace["devices"].items()}


def bare(name):
    """An event's instruction name as the compiled text has it."""
    return name.split(" = ")[0].strip().lstrip("%")


def kernel_seconds(trace, names, plane=None):
    """(seconds, events) of the device ops inside the window on one
    device (the first, by name, unless given) whose instruction name is
    in ``names`` (device events carry no stats on this setup, so a
    kernel is known by the instruction the compiled text says it is:
    ``perf/harness/hlo.py``)."""
    lo, hi = window(trace)
    plane = plane or sorted(trace["devices"])[0]
    evs = [ev for ev in in_window(trace["devices"][plane], lo, hi)
           if bare(ev[0]) in names]
    return sum(ev[2] for ev in evs) / 1e9, len(evs)


def exposed_collective_seconds(trace, plane=None):
    """Collective time on one device during which no other op runs
    there, inside the window."""
    lo, hi = window(trace)
    plane = plane or sorted(trace["devices"])[0]
    evs = in_window(trace["devices"][plane], lo, hi)
    coll = union((ev[1], ev[1] + ev[2]) for ev in evs
                 if is_collective(ev[0], ev[3]))
    rest = union((ev[1], ev[1] + ev[2]) for ev in evs
                 if not is_collective(ev[0], ev[3]))
    return measure(clip(subtract(coll, rest), lo, hi)) / 1e9


def top_ops(trace, n=10, plane=None, categories=None):
    """[[label, seconds]]: the categories with most self time first
    (``[category]``), then single operations, ``n`` entries in all."""
    lo, hi = window(trace)
    plane = plane or sorted(trace["devices"])[0]
    by_cat, by_op = {}, {}
    for name, dur, stats in self_times(
            in_window(trace["devices"][plane], lo, hi)):
        cat = (categories or {}).get(bare(name)) or categorize(name, stats)
        by_cat[cat] = by_cat.get(cat, 0.0) + dur
        key = f"{bare(name)[:48]} ({cat})"
        by_op[key] = by_op.get(key, 0.0) + dur
    cats = sorted(by_cat.items(), key=lambda kv: -kv[1])[:n // 2]
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:n - len(cats)]
    return ([[f"[{c}]", t / 1e9] for c, t in cats]
            + [[k, t / 1e9] for k, t in ops])


SPAN_PREFIXES = ("perf.", "decode.", "executor.", "serving.")


def idle_gaps(trace, n=5, plane=None, span_prefixes=SPAN_PREFIXES):
    """[[what the host was doing, seconds]] for the ``n`` longest gaps
    between device ops inside the window: each gap is named after the
    innermost host span (name starting with one of ``span_prefixes``:
    the runner's and the program's own, so a gap inside a tick reads
    ``decode.logits_to_host`` or ``decode.admit`` and not the tick
    round it; the window span excepted) that covers at least half of
    it."""
    lo, hi = window(trace)
    plane = plane or sorted(trace["devices"])[0]
    ran = clip(union((ev[1], ev[1] + ev[2])
                     for ev in trace["devices"][plane]), lo, hi)
    gaps = sorted(subtract([(lo, hi)], ran), key=lambda g: g[0] - g[1])[:n]
    spans = [(nm, s, s + d) for _, nm, s, d in trace["host"]
             if nm.startswith(tuple(span_prefixes)) and nm != WINDOW_SPAN]
    out = []
    for gs, ge in gaps:
        over = [(nm, min(e, ge) - max(s, gs), e - s) for nm, s, e in spans
                if min(e, ge) > max(s, gs)]
        # the innermost span that covers at least half of the gap,
        # else the one that covers most of it
        inner = sorted((w, nm) for nm, c, w in over if c >= (ge - gs) / 2)
        if inner:
            best = inner[0][1]
        elif over:
            best = max(over, key=lambda o: o[1])[0]
        else:
            best = "host: no span"
        out.append([best, (ge - gs) / 1e9])
    return out


def summary(trace, categories=None):
    """What every traced run reports: ``busy_s`` (mean over the chips),
    ``window_s``, and the breakdown.  ``categories`` ({instruction:
    category}, from the compiled programs' text) names what the
    instruction names alone cannot."""
    lo, hi = window(trace)
    b = busy(trace, lo, hi)
    if not b:
        raise ValueError("the trace holds no device plane")
    return {"busy_s": sum(b.values()) / len(b),
            "busy_s_per_device": b,
            "window_s": (hi - lo) / 1e9,
            "breakdown": {"device_ops": top_ops(trace, categories=categories),
                          "idle_gaps": idle_gaps(trace)}}
