"""PR 51's thirteen readers on hand-made records: a compiled text with
scoped and unscoped instructions and a ``while`` (two bucket programs
whose instruction names repeat), registry snapshots with two buckets, a
trace with an idle gap under ``decode.prefill_wait``; and None on the
record of a parent that writes none of it."""

import json
import os
import subprocess
import sys

import pytest

from perf.harness import skeleton as sk
from perf.harness import trace as tr
from perf.run import load_reader

DEVICE = ["prefill_mixer_ms", "prefill_mlp_ms", "prefill_ends_ms",
          "prefill_unscoped_share", "step_mixer_ms", "step_mlp_ms",
          "step_head_ms", "step_unscoped_share"]
COUNTED = ["decode_admit_host_ms", "decode_admit_pad_stall_share",
           "decode_admit_packed_rows_share"]
IDLE = ["gen_idle_prefill_wait_share", "gen_idle_prefill_host_share"]


def _line(name, op=None, kind="fusion"):
    meta = f', metadata={{op_name="{op}"}}' if op else ""
    return f"  %{name} = f32[4]{{0}} {kind}(%p){meta}\n"


P = "jit(_prefill_bucket)/"
# the 128-row bucket: ``fusion.2`` is a mixer's
BUCKET_128 = "%body {\n" + _line(
    "fusion.7", P + "blk_mlp/while/body/moe_experts/dot_general"
) + "}\nENTRY %main {\n" + "".join([
    _line("fusion.1", P + "blk_embed/gather"),
    _line("fusion.2", P + "blk_mixer/attn_full/dot_general"),
    _line("while.3", P + "blk_mlp/moe_combine/while", kind="while"),
    _line("fusion.4", P + "blk_store/scatter"),
    _line("fusion.5", P + "blk_head/dot_general"),
    _line("fusion.6", P + "lt"),            # between the parts
    _line("copy-done.8"),                   # no op_name at all
]) + "}\n"
# the 256-row bucket: the same names are other instructions
BUCKET_256 = "ENTRY %main {\n" + "".join([
    _line("fusion.1", P + "blk_mixer/ssm/ssm_scan/mul"),
    _line("fusion.2", P + "blk_mlp/dot_general"),
    _line("fusion.9", P + "blk_head/dot_general"),
    _line("fusion.10", P + "blk_mixer/add"),
]) + "}\n"
D = "jit(_decode_step)/"
STEP = "ENTRY %main {\n" + "".join([
    _line("fusion.1", D + "blk_embed/gather"),
    _line("fusion.2", D + "blk_mixer/attn_full/jit(rpa)/rpa/pallas_call"),
    _line("fusion.3", D + "blk_mlp/dot_general"),
    _line("fusion.4", D + "blk_head/argmax"),
    _line("fusion.5", D + "add"),
]) + "}\n"


def _device_record():
    def ev(name, at, dur):
        return (f"%{name} = ...", at, dur, {})

    ops = [
        # a run of the 128-row bucket's program
        ev("fusion.1", 101.0, 2.0), ev("fusion.2", 104.0, 30.0),
        ev("while.3", 135.0, 42.0), ev("fusion.7", 136.0, 40.0),
        ev("fusion.4", 178.0, 5.0), ev("fusion.5", 184.0, 8.0),
        ev("fusion.6", 193.0, 1.0), ev("copy-done.8", 195.0, 4.0),
        # two runs of the 256-row bucket's
        ev("fusion.1", 301.0, 50.0), ev("fusion.2", 352.0, 20.0),
        ev("fusion.9", 373.0, 8.0), ev("fusion.10", 382.0, 2.0),
        ev("fusion.1", 401.0, 50.0), ev("fusion.2", 452.0, 20.0),
        ev("fusion.9", 473.0, 8.0), ev("fusion.10", 482.0, 2.0),
        # two decode steps
        ev("fusion.1", 601.0, 1.0), ev("fusion.2", 603.0, 10.0),
        ev("fusion.3", 614.0, 20.0), ev("fusion.4", 635.0, 3.0),
        ev("fusion.5", 639.0, 1.0),
        ev("fusion.1", 701.0, 1.0), ev("fusion.2", 703.0, 10.0),
        ev("fusion.3", 714.0, 20.0), ev("fusion.4", 735.0, 3.0),
        ev("fusion.5", 739.0, 1.0),
        # outside every run
        ev("fusion.2", 900.0, 77.0)]
    mods = [("jit__prefill_bucket(11)", 100.0, 100.0),
            ("jit__prefill_bucket(22)", 300.0, 90.0),
            ("jit__prefill_bucket(22)", 400.0, 90.0),
            ("jit__decode_step(5)", 600.0, 50.0),
            ("jit__decode_step(5)", 700.0, 50.0)]
    steps = {"type": "counter", "values": [{"labels": {}, "value": 0}]}
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"prefill_bucket_128": BUCKET_128,
                          "prefill_bucket_256": BUCKET_256,
                          "decode_step": STEP},
        "registry": {"before": {"decode_steps_total": steps},
                     "after": {"decode_steps_total": {
                         "type": "counter",
                         "values": [{"labels": {}, "value": 2}]}}}}


def test_a_program_is_split_by_the_skeletons_parts():
    rec = _device_record()
    got = {m: load_reader(m)(rec) for m in DEVICE}
    ns = 1e-9 * 1e3                 # the events are in ns, the metrics ms
    # each run read against its own bucket's text: mixer 30 in the first
    # run, 50 + 2 in each of the other two; the while is left out, its
    # body's 40 counted
    assert got["prefill_mixer_ms"] == pytest.approx((30 + 2 * 52) / 3 * ns)
    assert got["prefill_mlp_ms"] == pytest.approx((40 + 2 * 20) / 3 * ns)
    assert got["prefill_ends_ms"] == pytest.approx(
        (2 + 5 + 8 + 2 * 8) / 3 * ns)
    total = (2 + 30 + 40 + 5 + 8 + 1 + 4) + 2 * (50 + 20 + 8 + 2)
    assert got["prefill_unscoped_share"] == pytest.approx(
        100.0 * (1 + 4) / total)
    # the parts and what is under none add up to the runs' device time
    parts, runs = sk.split(rec, sk.PREFILL_PROGRAMS, sk.PREFILL_MODULE)
    assert runs == 3 and sum(parts.values()) == pytest.approx(total * 1e-9)
    assert got["step_mixer_ms"] == pytest.approx(10 * ns)
    assert got["step_mlp_ms"] == pytest.approx(20 * ns)
    assert got["step_head_ms"] == pytest.approx((1 + 3) * ns)
    assert got["step_unscoped_share"] == pytest.approx(100.0 * 1 / 35)


def test_a_rehearsals_trace_is_split_by_the_events_own_module():
    rec = _device_record()
    rec["trace_modules"] = None
    rec["compiled_text"].pop("prefill_bucket_256")
    rec["trace"]["devices"]["/device:TPU:0"] = [
        (n, s, d, {"hlo_module": "jit__prefill_bucket" if s < 200
                   else "jit__decode_step"})
        for n, s, d, _ in rec["trace"]["devices"]["/device:TPU:0"]
        if s < 200 or 600 <= s < 800]
    rec["registry"]["after"]["decode_prefill_seconds"] = {
        "type": "histogram", "values": [{"labels": {}, "count": 1,
                                         "sum": 0.1}]}
    assert load_reader("prefill_mixer_ms")(rec) == pytest.approx(30e-6)
    assert load_reader("step_mlp_ms")(rec) == pytest.approx(20e-6)


def _family(values):
    return {"type": "counter",
            "values": [{"labels": dict(k), "value": v}
                       for k, v in values.items()]}


def _counted_record():
    def snap(scale):
        secs = {(("phase", "admit"), ("admitting", "1")): 2.0 * scale,
                (("phase", "prefill"), ("admitting", "1")): 1.9 * scale,
                (("phase", "prefill_wait"), ("admitting", "1")): 1.5 * scale,
                (("phase", "collect"), ("admitting", "0")): 5.0 * scale}
        return {
            "decode_ticks_total": _family({(("admitting", "1"),): 4 * scale,
                                           (("admitting", "0"),): 9 * scale}),
            "decode_tick_seconds_total": _family(secs),
            "decode_admissions_total": _family(
                {(("bucket", "128"),): 3 * scale,
                 (("bucket", "256"),): 2 * scale}),
            "decode_admit_stalled_slot_seconds_total": _family(
                {(("bucket", "128"), ("kind", "real")): 4.5 * scale,
                 (("bucket", "128"), ("kind", "pad")): 1.5 * scale,
                 (("bucket", "256"), ("kind", "real")): 9.5 * scale,
                 (("bucket", "256"), ("kind", "pad")): 0.5 * scale}),
            "decode_slot_seconds_total": _family({(): 40.0 * scale}),
            "decode_admit_tick_rows_total": _family(
                {(("kind", "run"),): 1024 * scale,
                 (("kind", "packed"),): 768 * scale})}
    return {"registry": {"before": snap(1), "after": snap(3)}}


def test_the_admissions_account_by_bucket():
    rec = _counted_record()
    # (admit 4.0 - wait 3.0) s over 10 seated admissions of two buckets
    assert load_reader("decode_admit_host_ms")(rec) == pytest.approx(100.0)
    assert load_reader("decode_admit_pad_stall_share")(rec) == \
        pytest.approx(100.0 * 4.0 / 80.0)
    assert load_reader("decode_admit_packed_rows_share")(rec) == \
        pytest.approx(75.0)
    # the stall share reads its family summed over the buckets
    assert load_reader("decode_admit_stall_share")(rec) == pytest.approx(
        100.0 * 32.0 / 80.0)
    # prompts that sit on the ladder pad nothing: the family has no
    # `pad` child, and the share reads 0
    for snap in (flat := _counted_record())["registry"].values():
        fam = snap["decode_admit_stalled_slot_seconds_total"]
        fam["values"] = [v for v in fam["values"]
                         if v["labels"]["kind"] != "pad"]
    assert load_reader("decode_admit_pad_stall_share")(flat) == 0.0
    # a window without an admission: nothing to divide by
    still = {"registry": {"before": rec["registry"]["after"],
                          "after": rec["registry"]["after"]}}
    assert load_reader("decode_admit_host_ms")(still) is None
    assert load_reader("decode_admit_packed_rows_share")(still) is None


def _idle_record(wait=True):
    T = "python3"
    host = [(T, tr.WINDOW_SPAN, 1000.0, 10000.0),
            (T, "decode.tick", 1500.0, 9000.0),
            (T, "decode.admit", 2000.0, 6000.0),
            (T, "decode.prefill", 2500.0, 5000.0)]
    if wait:
        host += [(T, "decode.prefill_wait", 3500.0, 3500.0)]
    # the step in flight ends at 3,400 and the prefill's program runs
    # from 4,500 to 6,000: the device is idle for 1,000 at the head of
    # the wait and 1,000 at its tail, and for 100 + 500 inside the
    # prefill call round it
    return {"trace": {"host": host, "devices": {"/device:TPU:0": [
        ("fusion.0", 2400.0, 1000.0, {}),
        ("fusion.1", 4500.0, 1500.0, {})]}}}


def test_the_idle_under_a_prefill_is_split_at_its_wait():
    rec = _idle_record()
    wait = load_reader("gen_idle_prefill_wait_share")(rec)
    host = load_reader("gen_idle_prefill_host_share")(rec)
    assert wait == pytest.approx(20.0) and host == pytest.approx(6.0)
    assert wait + host + load_reader("gen_idle_seat_share")(rec) == \
        pytest.approx(load_reader("gen_idle_prefill_share")(rec))
    # a gap inside the admission bears the new phase's name
    assert ["decode.prefill_wait", 1.1e-6] in [
        [name, pytest.approx(s)] for name, s in tr.idle_gaps(rec["trace"])]


@pytest.mark.parametrize("metric", DEVICE + COUNTED + IDLE)
def test_a_parents_record_reads_nothing(metric):
    """The driver lays this PR's files over the parent's checkout: its
    programs have no ``blk_`` scope, its account no by-bucket family and
    no ``prefill_wait`` label, its trace no ``decode.prefill_wait``."""
    read = load_reader(metric)
    rec = _device_record()
    rec["compiled_text"] = {
        k: t.replace("blk_embed/", "").replace("blk_mixer/", "")
        .replace("blk_mlp/", "").replace("blk_head/", "")
        .replace("blk_store/", "")
        for k, t in rec["compiled_text"].items()}
    counted = _counted_record()["registry"]
    for snap in counted.values():
        for name in ("decode_admissions_total",
                     "decode_admit_tick_rows_total"):
            del snap[name]
        stalled = snap["decode_admit_stalled_slot_seconds_total"]
        stalled["values"] = [{"labels": {}, "value": sum(
            v["value"] for v in stalled["values"])}]
    rec["registry"] = counted
    rec["trace"]["host"] = _idle_record(wait=False)["trace"]["host"]
    assert read(rec) is None
    assert read({"trace": None, "registry": None}) is None


def test_the_gpt2_cell_rehearses_traced_and_reads_what_it_lists():
    """The Cerebras generate cell's driver keeps no prefill program's
    text and loads no module runs from its trace, so on the chip nothing
    says which program a device event ran in: the five metrics read from
    the account and from the spans list the cell and are on its traced
    line, the eight read from the device's events do not."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    cell = "cerebras-gpt-1.3b-generate-chat"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 29), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", [])}
    new = set(COUNTED + IDLE)
    assert new <= listed and not set(DEVICE) & listed
    assert set(out["metrics"]) <= listed
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # a rehearsal's traced window is one second: the readers have
    # something to read where it holds an admission
    if "decode_admit_host_ms" in m:         # by the registry's window
        assert set(COUNTED) <= set(m)
        assert m["decode_admit_host_ms"] > 0
        assert m["decode_admit_pad_stall_share"] >= 0
        assert 0 < m["decode_admit_packed_rows_share"] <= 100
    if "gen_idle_prefill_wait_share" in m:  # by the profile's
        assert m["gen_idle_prefill_wait_share"] + m[
            "gen_idle_prefill_host_share"] + m["gen_idle_seat_share"] == \
            pytest.approx(m["gen_idle_prefill_share"], abs=1e-6)
