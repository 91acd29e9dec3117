"""The expert layer's readers on a hand-made compiled text, trace and
registry: instructions by scope and by name, events taken only inside
one program's runs (instruction names repeat across programs), counter
deltas by phase."""

from perf.harness import hlo_ops, modules, moe, trace as tr
from perf.layer_metrics import (moe_experts_roofline, moe_ms_per_step,
                                moe_prefill_flops_share)

TEXT = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/moe_router/div"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/moe_experts/mul"}
  %ragged-dot-none.3 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/scatter"}
}
'''


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%ragged-dot-none.3 = ...", 120.0, 40.0, {}),
        ("%fusion.9 = ...", 170.0, 20.0, {}),
        ("%fusion.2 = ...", 320.0, 7.0, {}),             # a prefill run:
        ("%ragged-dot-none.3 = ...", 330.0, 30.0, {}),   # same names
        ("%fusion.2 = ...", 520.0, 5.0, {}),             # decode run 2
        ("%ragged-dot-none.3 = ...", 530.0, 40.0, {}),
    ]
    mods = [("jit__prefill_bucket(7)", 300.0, 100.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    counter = lambda d, p: {"type": "counter", "values": [  # noqa: E731
        {"labels": {"phase": "decode"}, "value": d},
        {"labels": {"phase": "prefill"}, "value": p}]}
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": TEXT, "prefill_bucket_64": TEXT},
        "registry": {
            "before": {"moe_experts_hit_total": counter(10, 5),
                       "moe_assignments_total": counter(100, 50),
                       "decode_steps_total": {"values": [
                           {"labels": {}, "value": 3}]}},
            "after": {"moe_experts_hit_total": counter(14, 9),
                      "moe_assignments_total": counter(116, 114),
                      "decode_steps_total": {"values": [
                          {"labels": {}, "value": 5}]}}},
        "config": {"hidden_size": 8, "intermediate_size": 4,
                   "num_hidden_layers": 1, "num_experts": 4,
                   "generate": {"dtype": "bfloat16"}},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_instructions_by_scope_and_by_name():
    assert hlo_ops.op_names(TEXT)["fusion.9"].endswith("/scatter")
    assert hlo_ops.instructions(TEXT, moe.ANY_SCOPE, moe.RAGGED_DOT) == {
        "fusion.1", "fusion.2", "ragged-dot-none.3"}
    assert hlo_ops.instructions(TEXT, moe.EXPERTS_SCOPE) == {"fusion.2"}


def test_events_count_only_inside_their_programs_runs():
    rec = _record()
    names = {"fusion.2", "ragged-dot-none.3"}
    secs, events, runs = modules.seconds_in(
        rec["trace"], rec["trace_modules"], r"_decode_step", names)
    assert (events, runs) == (3, 2) and abs(secs - 85e-9) < 1e-15
    secs, events, runs = modules.seconds_in(
        rec["trace"], rec["trace_modules"], r"_prefill_bucket", names)
    assert (events, runs) == (2, 1) and abs(secs - 37e-9) < 1e-15
    assert modules.seconds_in(rec["trace"], rec["trace_modules"],
                              r"_verify_step", names) is None


def test_the_readers_arithmetic():
    rec = _record()
    # moe scopes + ragged dots in the two decode runs: 10+40+5+40 ns, 2 steps
    assert abs(moe_ms_per_step.read(rec) - 95e-9 / 2 * 1e3) < 1e-12
    # 4 experts hit x 3 x 8 x 4 x 2 B over 85 ns, of 1e9 B/s
    want = 100.0 * (3 * 4 * 8 * 4 * 2) / 85e-9 / 1e9
    assert abs(moe_experts_roofline.read(rec) - want) < 1e-6 * want
    # 64 prefill assignments x 6 x 8 x 4 FLOP over 37 ns, of 1e12 FLOP/s
    want = 100.0 * (64 * 6 * 8 * 4) / 37e-9 / 1e12
    assert abs(moe_prefill_flops_share.read(rec) - want) < 1e-6 * want


def test_a_program_without_the_layer_reads_nothing():
    rec = _record()
    rec["registry"] = {"before": {}, "after": {}}
    rec["compiled_text"] = {"decode_step": "ENTRY %m {\n  %a.1 = f32[] add()\n}"}
    for reader in (moe_ms_per_step, moe_experts_roofline,
                   moe_prefill_flops_share):
        assert reader.read(rec) is None


def test_module_runs_of_a_recorded_chip_trace():
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small_tpu_4.xplane.pb")
    mods = modules.load(path)
    assert len(mods) == 4
    for runs in mods.values():
        assert any(name.startswith("jit_step(") for name, _, _ in runs)
        assert all(dur > 0 for _, _, dur in runs)
