"""The ``phi-4-mini-flash-reasoning`` entries of ``BENCHMARK.json`` and
their files: the traffic as ISSUE 48 names it (one deal of 32 requests,
eight prompt lengths off the bucket ladder, eight prime answer budgets,
the five long prompts spread, every budget to short and long prompts
alike), the configuration uncut from the catalog's row with what the
row lacks under ``assumed``, the lists the cell was appended to, every
ablation known to the reference, the cell's five new readers (and the
state-space layer's step time, Granite's reader unedited) on a
hand-made compiled text, trace and registry, and the cell rehearsed end
to end.  (Cases a later PR would add to ``test_traffic.py``,
``test_benchmark_json.py`` and ``test_rehearse.py``: a PR that adds a
cell edits no file the benchmark has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import dhd, loadgen
from perf.harness import trace as tr
from perf.reference import phi4_flash_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi-4-mini-flash-reasoning-generate-longchain"
CONFIG = "phi-4-mini-flash-reasoning"
TRAFFIC = "generate-longchain-s64"
NEW_METRICS = ("attn_shared_ms_per_step", "attn_shared_roofline",
               "ssm_s6_state_roofline", "ssm_s6_scan_ms_per_krow",
               "prefill_cross_rows_share")
# accepted metrics of one layer whose readers hold here unedited
SHARED_METRICS = ("attn_window_ms_per_step", "ssm_ms_per_step")
ABLATIONS = ("no_diff_term", "lam0_constant", "no_subln",
             "no_one_minus_lam0", "plain_gqa_pairing", "window_off",
             "cross_reads_window", "cross_rows_zero", "gmu_no_memory",
             "gmu_memory_after_gate", "no_decay", "no_dt_on_input",
             "no_conv", "no_skip_D", "no_gate", "rmsnorm_for_layernorm",
             "rope_on_attention")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 64, 64, 128)
    assert (t["ramp_seconds"], t["stagger_tokens"], t["trace_seconds"]) == (
        10, 3, 10)
    assert t["prompt_lengths"] == [[500, 4], [900, 4], [1500, 5], [2300, 5],
                                   [3400, 5], [5000, 4], [7200, 3],
                                   [10500, 2]]
    assert t["max_tokens"] == [[307, 3], [401, 4], [503, 5], [613, 5],
                               [719, 5], [827, 4], [929, 3], [1021, 3]]
    loadgen.check_deal(t)                       # the deal holds the cards
    deal = t["deal"]
    assert len(deal) == 32
    assert sum(p for p, _ in deal) / 32 == 3256.25
    assert round(sum(b for _, b in deal) / 32, 1) == 651.8
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # off the ladder 128, 256 .. 8,192, 12,288
    ladder = {2 ** k for k in range(7, 14)} | {12288}
    assert not ladder & {p for p, _ in deal}


def test_the_long_prompts_are_spread_and_the_budgets_dealt_alike():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    deal = t["deal"]
    long_at = [i for i, (p, _) in enumerate(deal) if p >= 7200]
    assert [deal[i][0] for i in long_at] == [10500, 7200, 7200, 10500, 7200]
    # four requests apart at least, round the end of the deal too
    gaps = [b - a for a, b in zip(long_at, long_at[1:] + [long_at[0] + 32])]
    assert min(gaps) >= 4, gaps
    by_budget = {}
    for p, b in deal:
        by_budget.setdefault(b, []).append(p)
    for b, prompts in by_budget.items():
        assert min(prompts) <= 1500 and max(prompts) >= 3400, (b, prompts)
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 12288 and g["slots"] == t["gen_slots"] == 64
    assert g["state_entries"] == g["slots"] + 1
    longest = max(deal, key=sum)
    assert longest == [10500, 1021] and sum(longest) == 11521 <= rows
    # what the deal reserves a seated sequence in the mean: 3,908 rows
    assert round(sum(p + b for p, b in deal) / 32) == 3908


# -- the configuration --------------------------------------------------------


def test_the_configuration_is_the_catalogs_row_uncut():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == []
    # the row of the model-configs guide's catalog, copied beside the
    # tests' data: every published key held equal to it
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "Phi-4-mini-flash-reasoning"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert {k: cfg[k] for k in row["config"]} == row["config"]
    assert (cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"],
            cfg["intermediate_size"], cfg["sliding_window"],
            cfg["mb_per_layer"]) == (32, 2560, 200064, 10240, 512, 2)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (
        40, 20)
    assert cfg["tie_word_embeddings"] is True
    assert cfg["mlp_bias"] is False and cfg["lm_head_bias"] is False
    # what the row lacks is assumed, each with the issue's sentence
    sizes = cfg["assumed_sizes"]
    assert sizes == {"mamba_d_state": 16, "mamba_d_conv": 4,
                     "mamba_expand": 2, "mamba_dt_rank": 160,
                     "mamba_conv_bias": True, "mamba_proj_bias": False,
                     "head_dim": 64}
    assert sizes["mamba_dt_rank"] == -(-cfg["hidden_size"] // 16)
    assert sizes["head_dim"] * cfg["num_attention_heads"] == 2560
    reason = "the catalog's config lacks the mamba_* keys"
    for key in ("mamba_sizes", "head_dim", "positions", "biases",
                "differential_attention", "gmu", "layers"):
        assert reason in cfg["assumed"][key], key
    assert "QK_ROW_STD" in cfg["assumed"]["weights"]
    assert "log-uniform" in cfg["assumed"]["weights"]
    said = " ".join(cfg["departures"])
    for word in ("random weights", "12,288", "packed pages", "refused"):
        assert word in said, word
    assert "64 concurrent sequences of up to 12,288 rows" in cfg["stands_for"]
    g = cfg["generate"]
    assert (g["dtype"], g["slots"], g["page_size"], g["pages_per_seq"],
            g["ring_pages"], g["state_entries"]) == (
        "bfloat16", 64, 128, 96, 5, 65)
    # below every slot at capacity, above what the deal reserves
    at_capacity = 64 * (96 + 8 * 5) + 1
    assert 64 * (31 + 8 * 5) < g["num_pages"] == 7041 < at_capacity
    assert g["planned_bytes"] < 15.0e9


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-1] == CELL and len(cells) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert BENCH["workloads"][-1]["chips"] == 1
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["gen_tokens_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(per) == 78
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(NEW_METRICS)
    layers = {"attn_shared": "Pallas kernels", "ssm_s6": "state-space layer",
              "prefill_": "decode engine"}
    for name in NEW_METRICS:
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "gen_tokens_per_s"
        assert per[name]["layer"] == [v for k, v in layers.items()
                                      if name.startswith(k)][0]
        assert os.path.exists(os.path.join(
            ROOT, "perf", "layer_metrics", name + ".py"))
    # every decode_*, gen_idle_* and .rate metric the six generate cells
    # (the four rate cells) report, the rings' time and the state-space
    # layer's (the scope, the program and the step count are Granite's:
    # its reader holds unedited, so the layer has ONE name); not the two
    # cache metrics whose arithmetic counts two kinds of cache where
    # this model has three, nor another model's kernels
    granite = "granite-4.0-h-micro-generate-longanswer"
    for name, m in per.items():
        mine = CELL in m.get("workloads", [])
        if name in NEW_METRICS + SHARED_METRICS:
            assert mine and m["workloads"][-1] == CELL, name
        elif name.startswith(("decode_", "gen_idle_", "serve_")) and \
                granite in m["workloads"]:
            assert mine and m["workloads"][-1] == CELL, name
        else:
            assert not mine, name
    for name in ("cache_state_bytes_share", "cache_bytes_per_live_row",
                 "attn_full_roofline", "ssm_state_roofline"):
        assert CELL not in per[name]["workloads"]


def test_correct_holds_every_ablation_and_the_state():
    """Every ablation ISSUE 48 names is known to the reference and held
    by a stated factor; the reference in float8 must read over the
    limit; the state entry has a limit of its own, under which the
    reference's bfloat16 state has to fail."""
    wl = _json("perf", "workloads", CELL + ".json")
    assert wl["driver"] == "generate_dhd"
    assert wl["verify"]["reference"] == "phi4_flash_block"
    assert wl["verify"]["prompt_lens"] == [300, 1100, 5000]
    assert wl["verify"]["tokens"] == 16
    # the ablations are held against a prompt that wraps the rings
    assert wl["verify"]["prompt_lens"][wl["verify"]["ablation_prompt"]] > 640
    for v in (wl["verify"], wl["rehearse"]["verify"]):
        assert sorted(v["ablations"]) == sorted(ABLATIONS)
        assert set(v["ablations"]) < set(ref.ABLATIONS)
        # the two of the cross layers move the logits least: 1.2, 1.3
        assert all(v["ablation_factor"][a] >= 1.2 for a in v["ablations"])
        assert 0 < v["state_rel_rms"] < v["logits_rel_rms"]
    v = wl["verify"]
    assert (v["precision_below"], v["state_precision_below"]) == (
        "fp8", "state_bf16")
    assert {"fp8", "state_bf16"} < set(ref.ABLATIONS)
    assert v["state_precision_factor"] >= 2
    for word in ABLATIONS + ("float8", "state_rel_rms"):
        assert word in v["why"], word


# -- the new readers ----------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/ssm/ssm_conv/mul"}
  %s6_step.2 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/ssm/ssm_state/s6_step/pallas_call"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/ssm/dot_general"}
  %scatter.4 = f32[4]{0} scatter(%p), metadata={op_name="jit(_decode_step)/attn_shared/scatter"}
  %gqa.5 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/attn_shared/jit(ragged_paged_attention_gqa)/ragged_paged_attention_gqa/pallas_call"}
  %fusion.8 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/attn_window/mul"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/dot_general"}
}
'''
BUCKET = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/exp"}
  %while.5 = f32[4]{0} while(%p), metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/while"}
  %fusion.6 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_scan/while/body/mul"}
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/ssm/ssm_conv/mul"}
  %gqa.5 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/attn_shared/dot_general"}
}
'''
SMALL = {"model_type": "phi4flash", "num_hidden_layers": 8,
         "hidden_size": 32,
         "assumed_sizes": {"mamba_d_state": 8, "mamba_expand": 4}}


def _counter(value, **labels):
    return {"labels": labels, "value": value}


def _registry(steps, slot_steps, tokens, own, cross, reads):
    return {"decode_ticks_total": {"values": [_counter(steps)]},
            "decode_steps_total": {"values": [_counter(steps)]},
            "decode_active_slot_steps_total": {"values": [
                _counter(slot_steps)]},
            "decode_prefill_tokens_total": {"values": [_counter(tokens)]},
            "decode_prefill_rows_total": {"values": [
                _counter(own, part="self"), _counter(cross, part="cross")]},
            "decode_shared_run_reads_total": {"values": [_counter(reads)]}}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%s6_step.2 = ...", 112.0, 20.0, {}),
        ("%fusion.3 = ...", 133.0, 5.0, {}),
        ("%scatter.4 = ...", 140.0, 2.0, {}),
        ("%gqa.5 = ...", 143.0, 30.0, {}),
        ("%fusion.8 = ...", 175.0, 7.0, {}),
        ("%fusion.9 = ...", 183.0, 9.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # a prefill run:
        ("%while.5 = ...", 320.0, 70.0, {}),             # the same names,
        ("%fusion.6 = ...", 325.0, 30.0, {}),            # its own text
        ("%fusion.6 = ...", 360.0, 30.0, {}),
        ("%gqa.5 = ...", 392.0, 3.0, {}),
        ("%gqa.5 = ...", 520.0, 50.0, {}),               # decode run 2
        ("%s6_step.2 = ...", 575.0, 20.0, {}),
    ]
    mods = [("jit__prefill_bucket(7)", 300.0, 100.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP, "prefill_bucket_64": BUCKET},
        "registry": {"before": _registry(3, 10, 100, 128, 2, 6),
                     "after": _registry(5, 15, 140, 192, 3, 10)},
        # the live rows' K and V as stored, ONE layer's: the driver's
        "kv_bytes": 300.0 * 64, "config": SMALL,
        "traffic": {"gen_slots": 64},
        "peaks": {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    cfg = _json("perf", "configs", CONFIG + ".json")
    assert dhd.sizes({"config": cfg}) == (9, 5120, 16)
    assert dhd.sizes({"config": SMALL}) == (3, 128, 8)
    assert dhd.sizes({"config": {"mamba_d_state": 128}}) is None
    assert dhd.state_bytes(9, 5120, 16) == 9 * 327680
    assert dhd.step_state_bytes(5, 9, 5120, 16) == 2 * 5 * 9 * 327680


def test_the_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec)
           for name in NEW_METRICS + SHARED_METRICS[1:]}
    # under attn_shared in the two decode runs: the scatter and the
    # kernel, 2 + 30 + 50 ns over 2 steps; the prefill's 3 ns are not
    assert abs(got["attn_shared_ms_per_step"] - 82e-9 / 2 * 1e3) < 1e-12
    # the rows' bytes x (4 reads over 2 steps = 2 readers) over 82 ns
    want = 100.0 * (300 * 64 * 2) / 82e-9 / 1e12
    assert abs(got["attn_shared_roofline"] - want) < 1e-6 * want
    # under ssm: 10 + 20 + 5 + 20 ns over 2 steps
    assert abs(got["ssm_ms_per_step"] - 55e-9 / 2 * 1e3) < 1e-12
    # 5 LIVE slot-steps x 3 layers x 128 x 8 x 4 B, once each way, over
    # the kernel's 20 + 20 ns
    want = 100.0 * (2 * 5 * 3 * 128 * 8 * 4) / 40e-9 / 1e12
    assert abs(got["ssm_s6_state_roofline"] - want) < 1e-6 * want
    # under ssm_scan in the prefill run, the loop's body and not the
    # loop: 8 + 30 + 30 ns over 40 LIVE prompt rows
    assert abs(got["ssm_s6_scan_ms_per_krow"] - 68e-9 * 1e3 / 0.040) < 1e-9
    # one cross row over 64 self rows
    assert got["prefill_cross_rows_share"] == 100.0 / 64


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/lin_attn/mul"}\\n}')
    granite = {"mamba_d_state": 128, "mamba_n_heads": 64,
               "num_hidden_layers": 40, "layer_types": ["mamba"] * 40}
    parents = {k: v for k, v in _registry(5, 15, 140, 0, 0, 0).items()
               if k not in (dhd.PREFILL_ROWS, dhd.SHARED_READS)}
    for change in ({"compiled_text": {"decode_step": bare,
                                      "prefill_bucket_64": bare},
                    "registry": {"before": parents, "after": parents}},
                   {"trace": None, "registry": None},
                   {"compiled_text": {},
                    "registry": {"before": {}, "after": {}}}):
        rec = {**_record(), **change}
        for name in NEW_METRICS:
            assert run.load_reader(name)(rec) is None, (name, change)
    # another hybrid's traced run: the ssm scopes are there, the sizes
    # are not this model's
    rec = {**_record(), "config": granite}
    for name in ("ssm_s6_state_roofline", "ssm_s6_scan_ms_per_krow"):
        assert run.load_reader(name)(rec) is None, name


# -- the cell, end to end -----------------------------------------------------


def test_the_cell_rehearses_traced_and_reads_every_new_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 17), "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    allowed = {m["name"] for m in BENCH["per_layer"]
               if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS + SHARED_METRICS) \
        <= set(out["metrics"]) <= allowed
    # the toy's one bucket is 64 rows: one cross row a prompt
    assert out["metrics"]["prefill_cross_rows_share"]["value"] == 100.0 / 64
