"""The latent layer's four readers on a hand-made compiled text, trace
and registry: the kernel's events taken only inside the decode step's
runs and the flash kernel's only inside a prefill's, the instructions
under ``attn_latent`` by scope, the algorithm's bytes and FLOPs (576
numbers a row whatever is stored), and nothing read from a program
without the scopes or the kernel."""

from perf import run
from perf.harness import latent
from perf.harness import trace as tr

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/attn_latent/attn_latent_down/mul"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/attn_latent/attn_latent_absorb/dot_general"}
  %latent.3 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/attn_latent/jit(latent_paged_attention)/pallas_call"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/moe_experts/dot_general"}
}
'''
BUCKET = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/attn_latent/attn_latent_expand/dot_general"}
  %flash.5 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_bucket)/attn_latent/jit(_flash_fwd_impl)/pallas_call"}
  %latent.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/moe_experts/mul"}
}
'''
CONFIG = {"num_hidden_layers": 3, "num_attention_heads": 4,
          "kv_lora_rank": 16, "qk_rope_head_dim": 8, "qk_nope_head_dim": 12,
          "v_head_dim": 10}
READERS = ("attn_latent_ms_per_step", "attn_latent_roofline",
           "attn_latent_flops_share", "attn_latent_prefill_flops_share")


def _counter(value):
    return {"values": [{"labels": {}, "value": value}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%fusion.2 = ...", 112.0, 6.0, {}),
        ("%latent.3 = ...", 120.0, 40.0, {}),
        ("%fusion.9 = ...", 175.0, 20.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # a prefill run:
        ("%flash.5 = ...", 320.0, 50.0, {}),             # the same names,
        ("%latent.3 = ...", 375.0, 9.0, {}),             # its own text
        ("%latent.3 = ...", 520.0, 60.0, {}),            # decode run 2
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
                       latent.PAIRS_COUNTER: _counter(1000)},
            "after": {"decode_steps_total": _counter(5),
                      latent.PAIRS_COUNTER: _counter(1820)}},
        "latent_rows": 700,
        "config": CONFIG, "traffic": {"gen_slots": 64},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    assert latent.sizes({"config": CONFIG}) == (3, 4, 16, 8, 20, 10)
    assert latent.sizes({"config": {"mamba_d_state": 128}}) is None
    assert latent.row_bytes(512, 64) == 1152          # not the stored 1,280
    assert latent.step_bytes(700, 3, 16, 8) == 700 * 3 * 24 * 2
    assert latent.step_flops(700, 3, 4, 16, 8) == 2 * 700 * 3 * 4 * (24 + 16)
    # the published layer: 60 FLOP a byte of cache
    assert (latent.step_flops(1, 1, 32, 512, 64)
            / latent.step_bytes(1, 1, 512, 64)) == 32 * (576 + 512) * 2 / 1152
    assert latent.prefill_flops(820, 3, 4, 20, 10) == 2 * 820 * 3 * 4 * 30


def test_the_four_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in READERS}
    # under attn_latent in the two decode runs: 10 + 6 + 40 + 60 ns, the
    # kernel included, the experts' fusion not; over 2 steps
    assert abs(got["attn_latent_ms_per_step"] - 116e-9 / 2 * 1e3) < 1e-12
    # the kernel's 40 + 60 ns in the decode runs (its namesake in the
    # prefill run is another program's instruction)
    want = 100.0 * (700 * 3 * 24 * 2) / 100e-9 / 1e9
    assert abs(got["attn_latent_roofline"] - want) < 1e-6 * want
    want = 100.0 * (2 * 700 * 3 * 4 * 40) / 100e-9 / 1e12
    assert abs(got["attn_latent_flops_share"] - want) < 1e-6 * want
    # 820 pairs x 3 layers x 4 heads x (20 + 10) x 2 over the flash
    # kernel's 50 ns inside the prefill run
    want = 100.0 * (2 * 820 * 3 * 4 * 30) / 50e-9 / 1e12
    assert abs(got["attn_latent_prefill_flops_share"] - want) < 1e-6 * want


def test_a_program_without_the_scopes_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/ssm/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare,
                                      "prefill_bucket_64": bare}},
                   {"trace": None}, {"compiled_text": {}},
                   {"registry": {"before": {}, "after": {}},
                    "latent_rows": None}):
        rec = {**_record(), **change}
        for name in READERS:
            assert run.load_reader(name)(rec) is None, (name, change)
    rec = {**_record(), "config": {"mamba_d_state": 128}}
    for name in READERS[1:]:
        assert run.load_reader(name)(rec) is None, name
