"""The trace reduction on a small hand-made trace checked in beside it
(``data/two_chip_steps.xspace.txt``, read through the same
``ProfileData`` as a chip's ``.xplane.pb``) and, where one is checked
in, on a trace recorded on the chip."""

import glob
import os

import pytest

from perf.harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "two_chip_steps.xspace.txt")) as f:
        text = "\n".join(ln for ln in f if not ln.startswith("#"))
    return tr.load(ProfileData.from_text_proto(text))


def test_load_keeps_ops_lines_and_host_spans(small):
    assert sorted(small["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(small["devices"]["/device:TPU:0"]) == 6  # not the module
    assert {n for _, n, _, _ in small["host"]} == {
        "perf.window", "perf.exe_run", "perf.loss_read"}


def test_window_is_the_host_span(small):
    assert tr.window(small) == (1000.0, 11000.0)


def test_busy_is_the_union_clipped_to_the_window(small):
    b = tr.busy(small)
    # device 0: 2000..6000, 6500..8000, 10500..11000 (clipped)
    assert b["/device:TPU:0"] == pytest.approx(6000e-9)
    assert b["/device:TPU:1"] == pytest.approx(5000e-9)
    s = tr.summary(small)
    assert s["busy_s"] == pytest.approx(5500e-9)
    assert s["window_s"] == pytest.approx(10000e-9)
    assert 0 < 1 - s["busy_s"] / s["window_s"] < 1   # the idle share


def test_kernel_sum_takes_the_named_instructions(small):
    secs, n = tr.kernel_seconds(small, {"custom-call.4"})
    assert (n, secs) == (1, pytest.approx(1500e-9))
    assert tr.kernel_seconds(small, {"custom-call.5"}) == (0.0, 0)


def test_exposed_collective_time(small):
    # all-reduce.3 runs 6500..8000; fusion.2 covers 7000..7500 of it
    assert tr.exposed_collective_seconds(small) == pytest.approx(1000e-9)


def test_self_time_subtracts_nested_ops(small):
    ops = dict((k, v) for k, v in tr.top_ops(small, n=20))
    # the while spans 4000 ns, its two children 2500 of them
    assert ops["while.9 (other)"] == pytest.approx(1500e-9)
    assert ops["custom-call.4 (kernel)"] == pytest.approx(1500e-9)
    assert ops["[collective]"] == pytest.approx(1000e-9)  # less fusion.2


def test_idle_gaps_are_named_after_host_spans(small):
    gaps = tr.idle_gaps(small, n=2)
    # 8000..10500 is the longest gap, covered by perf.loss_read
    assert gaps[0] == ["perf.loss_read", pytest.approx(2500e-9)]
    assert gaps[1] == ["perf.exe_run", pytest.approx(1000e-9)]


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (3, 4)]) == [
        (0, 1), (2, 3), (4, 10)]
    assert tr.measure(tr.clip([(0, 10)], 2, 5)) == 3


def test_categories():
    assert tr.categorize("convolution.5") == "conv"
    assert tr.categorize("fusion.1", {"hlo_category": "convolution fusion"}
                         ) == "convolution fusion"
    assert tr.categorize("all-reduce-start.2") == "collective"
    assert tr.is_collective("all-gather.1")
    assert not tr.is_collective("fusion.7")


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(DATA, "*.xplane.pb"))) or [None])
def test_recorded_chip_trace(path):
    """Invariants on a trace recorded on the chip."""
    if path is None:
        pytest.skip("no recorded .xplane.pb is checked in")
    t = tr.load(path)
    assert t["devices"], "no device plane"
    s = tr.summary(t)
    assert 0 < s["busy_s"] <= s["window_s"]
    for p, evs in t["devices"].items():
        lo, hi = tr.window(t)
        total = sum(d for _, d, _ in tr.self_times(tr.in_window(evs, lo, hi)))
        assert total >= s["busy_s_per_device"][p] * 1e9 * 0.999 - 1
    coll = tr.exposed_collective_seconds(t)
    assert 0 <= coll <= s["busy_s_per_device"][sorted(t["devices"])[0]]
    assert len(s["breakdown"]["device_ops"]) <= 10
    assert len(s["breakdown"]["idle_gaps"]) <= 10


def test_hlo_text_names_kernels_and_heavy_fusions():
    from perf.harness import hlo

    text = """
%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8] parameter(0)
  ROOT %convolution.2 = bf16[8,8] convolution(%p, %p), dim_labels=bf_io->bf
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %add.1 = f32[8] add(%p, %p)
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8] parameter(0)
  %fusion.435 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fused_computation.1
  %fusion.7 = f32[8] fusion(%a), kind=kLoop, calls=%fused_computation.2
  %custom-call.61 = bf16[8,8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(jit(_flash_fwd_impl))/pallas_call" stack_frame_id=6}
  ROOT %custom-call.9 = bf16[8,8] custom-call(%a), custom_call_target="Sharding"
}
"""
    assert hlo.custom_calls(text) == {
        "custom-call.61": "jit(step)/jvp(jit(_flash_fwd_impl))/pallas_call"}
    assert hlo.kernel_instructions(text, r"_flash_(fwd|bwd)_impl") == {
        "custom-call.61"}
    assert hlo.kernel_instructions(text, r"_decode_step") == set()
    cats = hlo.categories(text)
    assert cats == {"custom-call.61": "kernel",
                    "fusion.435": "conv/matmul fusion"}
    assert tr.bare("%fusion.435 = bf16[8,8] fusion(...)") == "fusion.435"


def test_idle_gaps_take_the_programs_own_spans():
    """A gap inside a tick is named after the innermost span of any of
    the prefixes, the program's own included, not after the runner's
    span round the tick."""
    trace = {
        "devices": {"/device:TPU:0": [("fusion.1", 1000.0, 1000.0, {}),
                                      ("fusion.2", 5000.0, 1000.0, {}),
                                      ("fusion.3", 8000.0, 2000.0, {})]},
        "host": [("main", "perf.window", 1000.0, 9000.0),
                 ("stepper", "perf.engine_step", 1500.0, 8000.0),
                 ("stepper", "decode.tick", 1600.0, 7800.0),
                 ("stepper", "decode.logits_to_host", 1900.0, 3200.0),
                 ("stepper", "decode.admit", 6100.0, 1800.0),
                 ("stepper", "decode.prefill", 7500.0, 300.0),
                 ("handler", "serving.generate", 0.0, 20000.0),
                 ("other", "python.gc", 2000.0, 3000.0)]}
    # gaps: 2000..5000 (3000 ns) and 6000..8000 (2000 ns)
    assert tr.idle_gaps(trace, n=2) == [
        ["decode.logits_to_host", pytest.approx(3000e-9)],
        ["decode.admit", pytest.approx(2000e-9)]]
    # the runner's spans alone: what the label was before
    assert [g[0] for g in tr.idle_gaps(trace, n=2, span_prefixes=("perf.",))
            ] == ["perf.engine_step", "perf.engine_step"]
    assert tr.idle_gaps(trace, n=1, span_prefixes=("none.",)) == [
        ["host: no span", pytest.approx(3000e-9)]]
