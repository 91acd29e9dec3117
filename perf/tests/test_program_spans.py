"""``perf/harness/program_spans.py`` on small hand-made traces: plain
tuples in the shape ``trace.load`` gives, times in ns."""

import pytest

from perf.harness import program_spans as ps

T = "python3"      # every Python thread's line is named after the process


def _trace(host, device=()):
    return {"host": [(T, "perf.window", 1000.0, 10000.0)] + list(host),
            "devices": {"/device:TPU:0": [(n, s, d, {})
                                          for n, s, d in device]}}


def test_self_time_subtracts_nested_children_inside_the_window():
    tr = _trace([
        (T, "decode.tick", 2000.0, 4000.0),      # 2000..6000
        (T, "decode.admit", 2500.0, 1000.0),     # child, 2500..3500
        (T, "decode.step", 4000.0, 1500.0),      # child, 4000..5500
        (T, "decode.tick", 10000.0, 3000.0),     # 10000..13000: 1000 inside
        (T, "decode.step", 10500.0, 2000.0),     # 10500..12500: 500 inside
    ])
    assert ps.self_seconds(tr, "decode.tick") == pytest.approx(5000e-9)
    assert ps.self_seconds(tr, "decode.tick", ["decode.admit"]) == \
        pytest.approx(4000e-9)
    assert ps.self_seconds(
        tr, "decode.tick", ["decode.admit", "decode.step"]) == \
        pytest.approx((4000 - 1000 - 1500 + 1000 - 500) * 1e-9)


def test_self_time_takes_children_by_name_across_threads():
    """Nesting is by name: a child written by another thread's line
    (here a handler thread) still counts against its parent's time, and
    a span of another name on the parent's line does not."""
    tr = _trace([
        ("python3", "serving.generate", 2000.0, 6000.0),
        ("python3 (2)", "serving.first_write", 3000.0, 500.0),
        ("python3", "decode.tick", 2500.0, 2000.0),
    ])
    assert ps.self_seconds(tr, "serving.generate",
                           ["serving.first_write"]) == \
        pytest.approx(5500e-9)
    # overlapping children are counted once (their union)
    assert ps.self_seconds(tr, "serving.generate",
                           ["serving.first_write", "decode.tick"]) == \
        pytest.approx((6000 - 2000) * 1e-9)


def test_count_and_mean_take_spans_that_start_in_the_window():
    tr = _trace([
        (T, "executor.step", 500.0, 1000.0),     # starts before the window
        (T, "executor.step", 2000.0, 1000.0),
        (T, "executor.step", 5000.0, 3000.0),
        (T, "executor.step", 11000.0, 1000.0),   # starts at its close
    ])
    assert ps.count(tr, "executor.step") == 2
    assert ps.mean_ms(tr, "executor.step") == pytest.approx(2000e-6)
    assert ps.count(tr, "executor.fetch") == 0
    assert ps.mean_ms(tr, "executor.fetch") is None


def test_idle_under_takes_the_part_of_a_gap_inside_the_span():
    # the device runs 1000..3000 and 7000..11000: one gap, 3000..7000
    tr = _trace([
        (T, "decode.tick", 2000.0, 3000.0),      # 2000..5000: half the gap
        (T, "decode.admit", 4000.0, 500.0),      # 4000..4500, in the gap
        (T, "decode.idle_wait", 6000.0, 3000.0),  # 6000..9000: 1000 idle
    ], device=[("fusion.1", 1000.0, 2000.0), ("fusion.2", 7000.0, 4000.0)])
    assert ps.idle_under(tr, ["decode.tick"]) == pytest.approx(2000e-9)
    assert ps.idle_under(tr, ["decode.admit"]) == pytest.approx(500e-9)
    assert ps.idle_under(tr, ["decode.tick"],
                         outside=["decode.admit"]) == pytest.approx(1500e-9)
    assert ps.idle_under(tr, ["decode.idle_wait"]) == pytest.approx(1000e-9)
    # what the three leave over of the 4000 ns gap is unattributed
    assert ps.idle_under(tr, ["decode.tick", "decode.idle_wait"]) == \
        pytest.approx(3000e-9)
    assert ps.idle_share(tr, ["decode.idle_wait"]) == pytest.approx(10.0)


def test_idle_share_tells_no_spans_from_no_idle_wait():
    device = [("fusion.1", 1000.0, 2000.0)]
    # a program that writes no span (the parent commit): nothing to read
    assert ps.idle_share(_trace([], device), ["decode.idle_wait"]) is None
    assert ps.idle_share(None, ["decode.idle_wait"]) is None
    # ticks but never an idle wait: a busy engine, a number
    busy = _trace([(T, "decode.tick", 2000.0, 1000.0)], device)
    assert ps.idle_share(busy, ["decode.idle_wait"]) == 0.0


def test_record_level_readers_return_none_without_the_programs_spans():
    old = {"trace": _trace([(T, "perf.exe_run", 2000.0, 1000.0)],
                           [("fusion.1", 1000.0, 2000.0)])}
    assert ps.exec_prepare_ms(old) is None
    assert ps.exec_dispatch_ms(old) is None
    assert ps.exec_prepare_ms({"trace": None}) is None
    new = {"trace": _trace([
        (T, "executor.run", 2000.0, 2000.0),
        (T, "executor.feed", 2000.0, 100.0),
        (T, "executor.lookup", 2100.0, 200.0),
        (T, "executor.gather_state", 2300.0, 300.0),
        (T, "executor.step", 2600.0, 1000.0),
        (T, "executor.run", 5000.0, 2000.0),
        (T, "executor.feed", 5000.0, 300.0),
        (T, "executor.lookup", 5300.0, 200.0),
        (T, "executor.gather_state", 5500.0, 100.0),
        (T, "executor.step", 5600.0, 1200.0),
    ])}
    assert ps.exec_prepare_ms(new) == pytest.approx(600e-6)
    assert ps.exec_dispatch_ms(new) == pytest.approx(1100e-6)


@pytest.mark.parametrize("metric", [
    "exec_prepare_ms.img", "exec_prepare_ms.tokens", "exec_dispatch_ms.img",
    "exec_dispatch_ms.tokens", "flash_attn_fwd_ms_per_step",
    "flash_attn_bwd_ms_per_step", "decode_queue_wait_ms",
    "serve_first_write_lag_ms", "decode_slot_occupancy", "decode_sample_ms",
    "decode_logits_to_host_ms", "gen_idle_prefill_share",
    "gen_idle_tick_share", "gen_idle_no_request_share"])
def test_new_readers_return_none_on_a_record_of_the_parent(metric):
    """The driver lays this PR's benchmark files over the parent's
    checkout: there the program writes no span and has no such counter,
    and a reader returns None (the line leaves the metric out) and does
    not raise — traced or not."""
    from perf.run import load_reader

    read = load_reader(metric)
    registry = {"before": {"decode_steps_total": {"values": [{"value": 1}]}},
                "after": {"decode_steps_total": {"values": [{"value": 9}]}}}
    traced = {"trace": _trace([(T, "perf.engine_step", 2000.0, 1000.0)],
                              [("fusion.1", 1000.0, 2000.0)]),
              "registry": registry, "steps": 4, "span_seconds": {},
              "compiled_text": {"step": "", "decode_step": ""},
              "traffic": {"gen_slots": 16}}
    assert read(traced) is None
    assert read({"trace": None, "registry": None}) is None
