"""The state-space layer's four readers on a hand-made compiled text,
trace and registry: instructions by scope, events taken only inside one
program's runs, a loop's instruction left to its body's, live
slot-steps (not slots) in the roofline's bytes, and nothing read from a
program without the scopes."""

from perf import run
from perf.harness import ssm
from perf.harness import trace as tr

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/ssm/ssm_conv/mul"}
  %ssd_step.2 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/ssm/ssm_state/ssd_step/pallas_call"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/ssm/mul"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/dot_general"}
}
'''
BUCKET = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/exp"}
  %while.5 = f32[4]{0} while(%p), metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/while"}
  %fusion.6 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/while/body/dot_general"}
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_conv/mul"}
}
'''
CONFIG = {"layer_types": ["mamba", "mamba", "attention", "mamba"],
          "num_hidden_layers": 4, "mamba_n_heads": 2, "mamba_d_head": 8,
          "mamba_d_state": 16}
READERS = ("ssm_ms_per_step", "ssm_state_roofline", "ssm_scan_ms_per_krow",
           "ssm_scan_flops_share")


def _counter(value):
    return {"values": [{"labels": {}, "value": value}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%ssd_step.2 = ...", 120.0, 40.0, {}),
        ("%fusion.3 = ...", 165.0, 5.0, {}),
        ("%fusion.9 = ...", 175.0, 20.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # a prefill run:
        ("%while.5 = ...", 320.0, 70.0, {}),             # the same names,
        ("%fusion.6 = ...", 325.0, 30.0, {}),            # its own text
        ("%fusion.6 = ...", 360.0, 30.0, {}),
        ("%fusion.7 = ...", 392.0, 3.0, {}),
        ("%ssd_step.2 = ...", 520.0, 60.0, {}),          # decode run 2
    ]
    mods = [("jit__prefill_bucket(7)", 300.0, 100.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP, "prefill_bucket_64": BUCKET},
        "registry": {
            "before": {"decode_steps_total": _counter(3),
                       "decode_active_slot_steps_total": _counter(10),
                       "decode_prefill_tokens_total": _counter(100),
                       "decode_prefill_padded_tokens_total": _counter(128)},
            "after": {"decode_steps_total": _counter(5),
                      "decode_active_slot_steps_total": _counter(15),
                      "decode_prefill_tokens_total": _counter(140),
                      "decode_prefill_padded_tokens_total": _counter(192)}},
        "config": CONFIG, "traffic": {"gen_slots": 64},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    assert ssm.sizes({"config": CONFIG}) == (3, 2, 8, 16)
    assert ssm.sizes({"config": {"linear_key_head_dim": 96}}) is None
    assert ssm.state_bytes(3, 2, 8, 16) == 3 * 2 * 8 * 16 * 4
    assert ssm.step_state_bytes(5, 3, 2, 8, 16) == 2 * 5 * 3 * 2 * 8 * 16 * 4
    assert ssm.scan_flops(40, 3, 2, 8, 16) == 6 * 40 * 3 * 2 * 8 * 16


def test_the_four_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in READERS}
    # under ssm in the two decode runs: 10 + 40 + 5 + 60 ns over 2 steps
    assert abs(got["ssm_ms_per_step"] - 115e-9 / 2 * 1e3) < 1e-12
    # 5 LIVE slot-steps (not 2 steps x 64 slots) x 3 layers x 2 x 8 x 16
    # x 4 B, once each way, over the kernel's 40 + 60 ns, of 1e9 B/s
    want = 100.0 * (2 * 5 * 3 * 2 * 8 * 16 * 4) / 100e-9 / 1e9
    assert abs(got["ssm_state_roofline"] - want) < 1e-6 * want
    # under ssm_scan in the prefill run, the loop's body and not the
    # loop: 8 + 30 + 30 ns over 64 bucket rows
    assert abs(got["ssm_scan_ms_per_krow"] - 68e-9 * 1e3 / 0.064) < 1e-9
    # 40 real rows x 6 x 3 x 2 x 8 x 16 FLOP over 68 ns, of 1e12 FLOP/s
    want = 100.0 * (40 * 6 * 3 * 2 * 8 * 16) / 68e-9 / 1e12
    assert abs(got["ssm_scan_flops_share"] - want) < 1e-6 * want


def test_a_program_without_the_scopes_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/lin_attn/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare,
                                      "prefill_bucket_64": bare}},
                   {"trace": None}, {"compiled_text": {}},
                   {"registry": {"before": {}, "after": {}}}):
        rec = {**_record(), **change}
        for name in READERS:
            assert run.load_reader(name)(rec) is None, (name, change)
    rec = {**_record(), "config": {"linear_key_head_dim": 96}}
    assert run.load_reader("ssm_state_roofline")(rec) is None
    assert run.load_reader("ssm_scan_flops_share")(rec) is None
