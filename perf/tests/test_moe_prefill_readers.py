"""``moe_prefill_ms`` and ``moe_grouped_fill`` on a hand-made compiled
text, trace and registry: a share's loop over blocks is an instruction
whose event spans its body's, so the body's instructions are counted
and the loop's own is not; per run of the prefill programs; a program
without the counters (the parent of PR 38) reads no fill."""

from perf.layer_metrics import moe_grouped_fill, moe_prefill_ms
from perf.harness import trace as tr

TEXT = '''
%body {
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/while/body/moe_dispatch/gather"}
  %ragged-dot-none.3 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/while/body/moe_combine/scatter-add"}
}
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/moe_router/dot_general"}
  %while.5 = (s32[], f32[4]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(_prefill_bucket)/moe_combine/while"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/attn_window/add"}
}
'''


def _record(counters=True):
    ops = [("%fusion.1 = ...", 110.0, 10.0, {}),          # prefill run 1
           ("%while.5 = ...", 125.0, 60.0, {}),           # spans its body
           ("%fusion.2 = ...", 126.0, 5.0, {}),
           ("%ragged-dot-none.3 = ...", 132.0, 40.0, {}),
           ("%fusion.4 = ...", 173.0, 8.0, {}),
           ("%fusion.9 = ...", 190.0, 5.0, {}),
           ("%fusion.1 = ...", 310.0, 10.0, {}),          # run 2: no block
           ("%fusion.1 = ...", 510.0, 99.0, {})]          # a decode step's
    mods = [("jit__prefill_bucket(7)", 100.0, 100.0),
            ("jit__prefill_bucket(9)", 300.0, 100.0),
            ("jit__decode_step(1)", 500.0, 100.0)]

    def rows(assigned, computed):
        return {"type": "counter", "values": [
            {"labels": {"rows": "assigned", "phase": "prefill"},
             "value": assigned},
            {"labels": {"rows": "computed", "phase": "prefill"},
             "value": computed}]}
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"prefill_bucket_512": TEXT, "decode_step": TEXT},
        "registry": {
            "before": {"moe_grouped_rows_total": rows(100, 1024)}
            if counters else {},
            "after": {"moe_grouped_rows_total": rows(100 + 390, 1024 + 1024)}
            if counters else {}}}


def test_moe_prefill_ms_counts_the_loops_body_and_not_the_loop():
    # 10 + 5 + 40 + 8 in the first run, 10 in the second: ns over 2 runs
    assert abs(moe_prefill_ms.read(_record()) - 73e-9 / 2 * 1e3) < 1e-12


def test_moe_prefill_ms_reads_nothing_without_a_trace_or_the_layer():
    rec = _record()
    rec["compiled_text"] = {"prefill_bucket_512": "ENTRY %m {\n}"}
    assert moe_prefill_ms.read(rec) is None
    assert moe_prefill_ms.read(dict(_record(), trace=None)) is None


def test_moe_grouped_fill_is_assigned_over_computed():
    assert abs(moe_grouped_fill.read(_record()) - 100.0 * 390 / 1024) < 1e-9
    assert moe_grouped_fill.read(_record(counters=False)) is None
    still = _record()
    still["registry"]["after"] = still["registry"]["before"]
    assert moe_grouped_fill.read(still) is None
