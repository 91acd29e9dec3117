"""The decode tick's own account (``paddle_tpu/decode/session.py``:
``TickAccount``, PR 37) as numbers over a run's record.

The account is a handful of registry counter families with labels:
``decode_tick_seconds_total{phase, admitting}``,
``decode_ticks_total{admitting}``, ``decode_tick_admissions_total{n}``,
``decode_step_inputs_total{source}``.  ``registry.totals`` sums a family
over its label sets; a reader here wants some of them, so ``delta``
takes the labels to hold to.  A program that keeps no account (a parent
commit) has no ``decode_ticks_total`` in its snapshots: every function
then returns None and the reader leaves its metric out of the line.

``reconcile`` lays the account beside the spans of a traced window: the
counter twin of a span has to tell the same seconds.
"""

from perf.harness import program_spans as ps
from perf.harness import trace as tr

# label -> the span whose statement charges it (session.py: PHASE_SPANS)
PHASE_SPANS = {
    "between": "decode.between", "collect": "decode.logits_to_host",
    "decide": "decode.sample", "sweep": "decode.sweep",
    "admit": "decode.admit", "prefill": "decode.prefill",
    "first_token": "decode.first_token", "cow": "decode.cow",
    "upload": "decode.upload", "dispatch": "decode.dispatch",
    "deliver": "decode.deliver"}
# a tick's seconds are these, added (`prefill` and `first_token` lie
# inside `admit`; `between` lies outside the tick)
IN_TICK = ("collect", "decide", "sweep", "admit", "cow", "upload",
           "dispatch", "deliver", "other")
# the host's work of a tick that no step in flight covers, admissions
# apart: between the ids' arrival and the next dispatch, and between ticks
EXPOSED = ("decide", "sweep", "cow", "upload", "dispatch", "other",
           "between")
WITNESS = "decode_ticks_total"


def total(snapshot, name, **labels):
    """Sum of a counter family's children whose labels hold
    ``labels``; 0.0 for a family or a child that is not there."""
    fam = snapshot.get(name)
    if not fam:
        return 0.0
    return sum(v["value"] for v in fam["values"]
               if all(v["labels"].get(k) == str(want)
                      for k, want in labels.items()))


def delta(record, name, **labels):
    """The window's delta of ``name`` over the children that hold
    ``labels``; None where the program keeps no tick account."""
    reg = record.get("registry")
    if not reg or WITNESS not in reg["after"]:
        return None
    return (total(reg["after"], name, **labels)
            - total(reg["before"], name, **labels))


def seconds(record, phases, **labels):
    """Seconds of the window's ticks under the ``phases`` named."""
    parts = [delta(record, "decode_tick_seconds_total", phase=p, **labels)
             for p in phases]
    return None if None in parts else sum(parts)


def ms_per_tick(record, phases, **labels):
    """Mean seconds a tick under ``phases``, in ms; None without a
    tick in the window."""
    secs = seconds(record, phases, **labels)
    ticks = delta(record, "decode_ticks_total", **labels)
    return None if not ticks else secs / ticks * 1e3


def share(part, whole):
    """``part`` of ``whole`` in percent; None where either was not
    read or nothing was there to share."""
    return None if part is None or not whole else 100.0 * part / whole


# -- the account beside the spans of a traced window -------------------------

IDLE_RESIDUAL = ("decode.sample", "decode.sweep", "decode.cow",
                 "decode.deliver")
TICK_CHILDREN = ("decode.logits_to_host", "decode.sample", "decode.sweep",
                 "decode.admit", "decode.cow", "decode.step",
                 "decode.deliver")


def reconcile(record):
    """The three reconciliations of a traced run, as one dict of plain
    numbers (seconds, or points of the window for the idle shares):

    - ``phases``: for every phase ``[counter delta, summed spans]``.
      The registry is read before the profile starts and the spans
      exist only inside it, so the counters run a little longer:
      ``counted_s`` (all phases and ``between``) over ``spanned_s``
      (the ``decode.tick`` and ``decode.between`` spans) is by how much.
    - ``spanned_s`` against ``window_less_idle_wait_s``: ticks and the
      time between them tile the window, but for the idle waits.
    - ``idle``: the two lumps the ledger has against their parts.

    ``ticks`` and ``longest_s`` (the window's longest tick, admission
    and wait between ticks) say what "one tick's worth" is and how far
    a sound tick lies under the slow-tick floor.

    None without the account or without a trace."""
    trace = record.get("trace")
    if not trace or seconds(record, IN_TICK) is None:
        return None
    lo, hi = tr.window(trace)

    def spanned(names):
        return tr.measure(ps.intervals(trace, names, lo, hi)) / 1e9

    phases = {label: [seconds(record, [label]), spanned([name])]
              for label, name in PHASE_SPANS.items()}
    phases["other"] = [seconds(record, ["other"]), ps.self_seconds(
        trace, "decode.tick",
        [PHASE_SPANS[label] for label in IN_TICK if label != "other"])]
    window = (hi - lo) / 1e9
    # `program_spans.idle_under`, with the device's idle intervals (a
    # union over every op of the window) made once for the dozen shares
    ran = tr.clip(tr.union(
        (ev[1], ev[1] + ev[2])
        for ev in trace["devices"][sorted(trace["devices"])[0]]), lo, hi)
    not_ran = tr.subtract([(lo, hi)], ran)

    def idle(names, outside=()):
        under = tr.subtract(ps.intervals(trace, names, lo, hi),
                            ps.intervals(trace, outside, lo, hi))
        return 100.0 * tr.measure(ps.intersect(not_ran, under)) / 1e9 / window

    parts = {"ids_arrival": idle(["decode.logits_to_host"]),
             "dispatch": idle(["decode.step"], ["decode.logits_to_host"])}
    parts.update({n.split(".")[1]: idle([n], ["decode.admit"])
                  for n in IDLE_RESIDUAL})
    parts["no_child"] = idle(["decode.tick"], TICK_CHILDREN)
    return {
        "ticks": delta(record, "decode_ticks_total"),
        "longest_s": {name: max(ps.durations(trace, name), default=0.0)
                      for name in ("decode.tick", "decode.admit",
                                   "decode.between")},
        "phases": phases,
        "counted_s": seconds(record, IN_TICK + ("between",)),
        "spanned_s": spanned(["decode.tick"]) + spanned(["decode.between"]),
        "window_less_idle_wait_s": window - spanned(["decode.idle_wait"]),
        "idle": {
            "tick_share": idle(["decode.tick", "decode.sweep", "decode.cow",
                                "decode.step", "decode.sample"],
                               ["decode.admit"]),
            "tick_parts": parts,
            "prefill_share": idle(["decode.admit"]),
            "prefill_parts": {
                "seat": idle(["decode.admit"], ["decode.prefill"]),
                "prefill": idle(["decode.prefill"])},
            "between": idle(["decode.between"])}}
