"""A program of the paged skeleton split by the skeleton's own parts,
and an admission's account on the host, for the readers PR 51 adds.

``paddle_tpu/decode/model.py`` puts five ``jax.named_scope``s round its
own call sites, outermost, in every program that runs a block:
``blk_embed``, ``blk_mixer`` (every layer's token mixer: the mechanisms'
scopes lie under it), ``blk_mlp`` (the ``moe_*`` scopes lie under it),
``blk_head`` (with the greedy choice) and, in a bucket's prefill,
``blk_store``.  ``split`` charges every device event inside the runs of
a program to the part its instruction's ``op_name`` lies under, or to
``unscoped`` (an instruction with no ``op_name``, an async ``-done``,
the few the skeleton reckons between its parts), so the parts add up to
the runs' device time: what is not split is a number, not a remainder.

Instruction names repeat from one compiled program to the next
(``fusion.12`` is another fusion in the next bucket's program), so the
runs are grouped by their module event's name, which carries the
program's fingerprint, and each group is read against the compiled text
that knows the most of its events.  A fusion carries its root's
``op_name``: one that the compiler built across two parts counts under
its root's.  An instruction that holds others (``while``) spans their
events and is left out, as ``moe_prefill_ms`` leaves it.

A program without the scopes (a parent commit; an executable that a
compile cache kept from one: the cache's key leaves ``op_name`` out)
gives None everywhere, and the readers leave their metrics out.
"""

import bisect
import re

from perf.harness import hlo_ops
from perf.harness import tick_account as ta
from perf.harness import trace as tr
from perf.harness.moe import (DECODE_MODULE, DECODE_PROGRAM,  # noqa: F401
                              PREFILL_MODULE, PREFILL_PROGRAMS)
from perf.harness.readers import registry_count

# part -> where its instructions are in a compiled text
PARTS = {"embed": re.compile(r"/blk_embed/"),
         "mixer": re.compile(r"/blk_mixer/"),
         "mlp": re.compile(r"/blk_mlp/"),
         "head": re.compile(r"/blk_head/"),
         "store": re.compile(r"/blk_store/")}
UNSCOPED = "unscoped"
# instructions whose time is that of the instructions they hold
HOLDS_OTHERS = re.compile(r"^(while|conditional|call)\b")

# the part of ``decode.prefill`` a device program covers
# (``PagedDecoderLM.prefill``: the wait for the logits' row): label of
# ``decode_tick_seconds_total`` -> its span
PREFILL_PHASES = {"prefill_wait": "decode.prefill_wait"}


def part_of(op_name):
    """The skeleton's part an ``op_name`` lies under, or ``unscoped``."""
    for part, rx in PARTS.items():
        if rx.search(op_name):
            return part
    return UNSCOPED


def _runs(trace, modules, module_pattern, plane):
    """({group: [device events]}, runs) inside the window: the events
    that ran inside a run of a program whose module name matches, by
    that name (one group a compiled program); ``runs`` counts the runs,
    None for a rehearsal's trace, which has no line of module runs and
    whose events say their module's name themselves."""
    lo, hi = tr.window(trace)
    rx = re.compile(module_pattern)
    evs = tr.in_window(trace["devices"][plane], lo, hi)
    runs = sorted((s, s + d, n) for n, s, d in (modules or {}).get(plane, ())
                  if rx.search(n) and s + d > lo and s < hi)
    groups = {}
    if not runs:
        mine = [ev for ev in evs
                if rx.search(str(ev[3].get("hlo_module", "")))]
        return ({"": mine} if mine else {}), None
    starts = [s for s, _, _ in runs]
    for ev in evs:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < runs[i][1]:
            groups.setdefault(runs[i][2], []).append(ev)
    return groups, len(runs)


def split(record, program_prefix, module_pattern, plane=None):
    """({part: seconds, "unscoped": seconds}, runs) of the device events
    inside the window's runs of the programs whose compiled text's key
    starts with ``program_prefix``.  None without a trace, without such
    a text, where no text holds one of the skeleton's scopes, or where
    the window holds no such run.  Kept on the record: four readers a
    program ask for it."""
    memo = record.setdefault("skeleton_split", {})
    if program_prefix not in memo:
        memo[program_prefix] = _split(record, program_prefix, module_pattern,
                                      plane)
    return memo[program_prefix]


def _split(record, program_prefix, module_pattern, plane):
    trace = record.get("trace")
    tables = [hlo_ops.op_names(t)
              for k, t in record.get("compiled_text", {}).items()
              if k.startswith(program_prefix)]
    if not trace or not trace.get("devices") or not tables:
        return None
    if not any(part_of(op) != UNSCOPED
               for table in tables for op in table.values()):
        return None
    plane = plane or sorted(trace["devices"])[0]
    groups, runs = _runs(trace, record.get("trace_modules"), module_pattern,
                         plane)
    if not groups:
        return None
    out = dict.fromkeys((*PARTS, UNSCOPED), 0.0)
    for evs in groups.values():
        names = [tr.bare(ev[0]) for ev in evs]
        table = max(tables, key=lambda t: sum(n in t for n in names))
        for name, ev in zip(names, evs):
            if not HOLDS_OTHERS.match(name):
                out[part_of(table.get(name, ""))] += ev[2] / 1e9
    return out, runs


def part_ms(record, program_prefix, module_pattern, parts, per=None):
    """Device ms under the ``parts`` named, per ``per``: a decode
    step's by the program's count of steps; a prefill's by the runs the
    trace counts, or, where it has no line of module runs (a
    rehearsal), by the program's own count of prefills."""
    got = split(record, program_prefix, module_pattern)
    if got is None:
        return None
    per = per or got[1] or registry_count(record, "decode_prefill_seconds")
    return sum(got[0][p] for p in parts) / per * 1e3 if per else None


def unscoped_share(record, program_prefix, module_pattern):
    """Share of the runs' device time under none of the five, %."""
    got = split(record, program_prefix, module_pattern)
    if got is None:
        return None
    total = sum(got[0].values())
    return 100.0 * got[0][UNSCOPED] / total if total else None


# a family of the by-bucket account that has a child as soon as one
# request was seated (a snapshot leaves out a family without children)
ACCOUNT_WITNESS = "decode_admissions_total"


def family_delta(record, name, **labels):
    """The window's delta of a counter family the tick's account has
    kept since PR 51, over the children that hold ``labels``: 0.0 for a
    family that has no child yet (no padding on a ladder the prompts sit
    on); None where the program keeps no such account (a parent
    commit)."""
    reg = record.get("registry")
    if not reg or ACCOUNT_WITNESS not in reg["after"]:
        return None
    return ta.delta(record, name, **labels)
