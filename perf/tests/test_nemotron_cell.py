"""The ``nemotron-3-nano-30b-a3b`` entries of ``BENCHMARK.json`` and
their files: the traffic as ISSUE 64 names it (one deal of 32 requests,
six prompt lengths of 300-7,500 rows off the bucket ladder and none over
its top bucket, four prime answer budgets, the longest sequence 8,521 of
8,576 rows), the configuration uncut from the catalog's row but for the
experts held and the vocabulary, the lists the cell was appended to and
the one it was left off, every ablation known to the reference, the
three new readers on a hand-made compiled text, trace and registry, and
the cell rehearsed end to end.  (Cases a later PR would add to
``test_traffic.py``, ``test_benchmark_json.py`` and ``test_rehearse.py``:
a PR that adds a cell edits no file the benchmark has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import loadgen, nemotron, ssm
from perf.harness import trace as tr
from perf.reference import nemotron_h_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron-3-nano-30b-a3b-generate-subagent"
CONFIG = "nemotron-3-nano-30b-a3b"
TRAFFIC = "generate-subagent-s32"
NEW_METRICS = ("moe_plain_experts_roofline", "ssm_proj_ms_per_step",
               "moe_held_rows_per_expert")
CELLS_BEFORE, METRICS_BEFORE = 14, 110
# ``moe_held_experts_roofline`` counts THREE matrices an expert and would
# read 1.5 times this cell's bytes; the rest know another model's layers
NOT_LISTED = ("moe_held_experts_roofline", "moe_experts_roofline",
              # perf/tests/test_ling_cell.py holds its list to Ling's cell
              "moe_route_ms_per_step",
              "moe_prefill_flops_share", "rpa_ms_per_step", "rpa_roofline",
              "cache_bytes_per_live_row", "attn_window_ms_per_step",
              "lin_attn_ms_per_step", "short_conv_ms_per_step",
              "prefill_chunk_rows_share")
LISTED = ("decode_step_ms", "decode_tick_ms", "gen_idle_tick_share",
          "gen_idle_prefill_share", "prefill_mixer_ms", "prefill_mlp_ms",
          "step_mixer_ms", "step_mlp_ms", "serve_ttft_p95_ms.rate",
          "decode_prefill_ms.rate", "moe_ms_per_step", "moe_prefill_ms",
          "moe_load_max_over_mean", "moe_held_assignment_share",
          "moe_grouped_fill", "moe_shared_ms_per_step", "ssm_ms_per_step",
          "ssm_state_roofline", "ssm_scan_ms_per_krow",
          "ssm_scan_flops_share", "cache_state_bytes_share",
          "attn_full_roofline")
DEAL = [[700, 613], [3000, 1021], [300, 307], [5000, 2039], [1500, 613],
        [7500, 1021], [700, 307], [3000, 613], [1500, 1021], [300, 613],
        [5000, 1021], [700, 2039], [3000, 307], [1500, 613], [7500, 613],
        [300, 307], [5000, 2039], [700, 613], [3000, 1021], [1500, 2039],
        [300, 613], [7500, 1021], [1500, 307], [3000, 613], [5000, 1021],
        [700, 2039], [300, 307], [3000, 2039], [1500, 613], [5000, 613],
        [700, 307], [1500, 1021]]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 32, 32, 64)
    assert (t["stagger_tokens"], t["trace_seconds"]) == (3, 10)
    assert t["deal"] == DEAL
    assert t["prompt_lengths"] == [[300, 5], [700, 6], [1500, 7], [3000, 6],
                                   [5000, 5], [7500, 3]]
    assert t["max_tokens"] == [[307, 7], [613, 11], [1021, 8], [2039, 6]]
    loadgen.check_deal(t)
    assert round(sum(p for p, _ in DEAL) / 32) == 2553
    assert round(sum(b for _, b in DEAL) / 32) == 915
    assert sum(b for _, b in DEAL) == 29294
    ladder = {128 << i for i in range(7)}
    assert not ladder & {p for p, _ in DEAL}
    # the three longest prompts never side by side
    long = [i for i, (p, _) in enumerate(DEAL) if p == 7500]
    assert all(b - a > 1 for a, b in zip(long, long[1:]))
    assert t["ramp_seconds"] == t["trace_ramp_seconds"] >= 20
    assert t["ramp_why"] and "TO BE" not in json.dumps(t)


def test_the_longest_sequence_fits_and_no_prompt_is_over_the_top_bucket():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 8576 and g["slots"] == t["gen_slots"] == 32
    assert max(p + b for p, b in DEAL) == 8521 <= rows
    assert max(p for p, _ in DEAL) <= g["prefill_rows"] == 8192
    assert g["state_entries"] == g["slots"] + 1
    # a deal seated at once (32 slots), a page rounded up a sequence
    pages = sum(-(-(p + b) // g["page_size"]) for p, b in DEAL)
    assert pages < 0.65 * g["num_pages"] and g["num_pages"] >= 1000


# -- the configuration --------------------------------------------------------


def test_every_catalog_key_is_uncut_but_the_two_in_reduced():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    reduced = ["n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert set(reduced) <= set(cfg["reduced_why"])
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    kept = {"n_routed_experts": (16, 128), "vocab_size": (16384, 131072)}
    for key, published in row["config"].items():
        if key in kept:
            assert (cfg[key], published) == kept[key]
            assert cfg[key + "_published"] == published
        else:
            assert cfg[key] == published, key
    # the widths and the depth, as published
    assert (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2688, 52, 32, 2, 128, 64, 64, 128, 8, 4, 1856, 3712, 6, 2.5)
    assert cfg["mlp_hidden_act"] == "relu2" and cfg["use_conv_bias"] is True
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == 52
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert [i for i, c in enumerate(pattern) if c == "*"] == [
        5, 12, 19, 26, 33, 42]
    # the keys the harness's readers take under other models' names
    assert cfg["layer_types"] == [
        {"M": "mamba", "E": "experts", "*": "attention"}[c] for c in pattern]
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"]) == (64, 64, 128, 8)
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert set(cfg["derived"]) >= {"layer_types", "mamba_n_heads",
                                   "mamba_d_head", "mamba_d_state",
                                   "num_experts"}
    assert (cfg["ep_size"], cfg["ep_rank"]) == (8, 0)
    assert cfg["n_routed_experts"] * cfg["ep_size"] == 128
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["n_routed_experts"] >= 8
    assert ssm.sizes({"config": cfg}) == (23, 64, 64, 128)
    assert nemotron.sizes({"config": cfg}) == (2688, 1856, 16, 23, 6, 2,
                                               128, 2)
    for said in ("stands_for", "assumed", "departures", "rehearse"):
        assert cfg[said], said
    for reading in ("block", "no_rotation", "attention", "grouped_norm",
                    "projection_order", "selection_bias", "router_eps",
                    "expert_form", "chunk_size", "time_step_limit",
                    "weights"):
        assert cfg["assumed"][reading], reading
    assert "QK_ROW_STD" in cfg["assumed"]["weights"]
    assert "v5e-8" in cfg["stands_for"]
    g = cfg["generate"]
    assert "stored against needed" in g["expert_store"]
    assert 0 < g["planned_bytes"] <= 15.0e9
    for count in ("5,258,420,544", "5,385,036,096", "38,744,896",
                  "23,399,040", "9,977,856", "179,948,288"):
        assert count in cfg["reduced_why"]["n_routed_experts"], count
    # stored weights + pools: over 75% of the chip
    pools = (g["num_pages"] * 786_432 + g["state_entries"] * 49_082_368)
    assert 2 * 5_385_036_096 + pools >= 0.75 * 16e9
    assert "TO BE" not in json.dumps(cfg)


# -- the benchmark's lists ----------------------------------------------------


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 12
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    assert tuple(names[METRICS_BEFORE:METRICS_BEFORE + 3]) == NEW_METRICS
    for name in NEW_METRICS:
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "gen_tokens_per_s"
    assert per["moe_plain_experts_roofline"]["layer"] == \
        per["moe_held_experts_roofline"]["layer"]
    assert per["ssm_proj_ms_per_step"]["layer"] == \
        per["ssm_ms_per_step"]["layer"]
    for name in LISTED:
        assert appended(per[name]["workloads"]), name
    for name in NOT_LISTED:
        assert CELL not in per[name]["workloads"], name
    # every list the other Mamba-2 cell is on, but for the ungrouped
    # walk's: both Mamba-2 cells stand on one metric
    granite = "granite-4.0-h-micro-generate-longanswer"
    for name, m in per.items():
        if granite in m.get("workloads", []):
            assert CELL in m["workloads"], name


def test_every_listed_reader_loads():
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert callable(run.load_reader(m["name"])), m["name"]


def test_correct_holds_every_ablation_and_the_precision_below():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_ssm_moe", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "nemotron_h_block"
    chunk, ablations, bucket, top = v["prompt_lens"]
    # one chunk of the scan; the ablations' (several chunks, a state that
    # has settled); one bucket; the top bucket
    assert chunk <= 128 < ablations <= 2048 < bucket <= 4096 < top
    assert top + v["tokens"] <= 8192 and v["ablation_prompt"] == 1
    assert (v["tokens"], v["streams"]) == (16, 2)
    # all the reference knows but the one the bf16 floor hides, the
    # state's and the weights' precision below by their own keys
    assert set(v["ablations"]) == set(ref.ABLATIONS) - {
        "bias_in_weights", "state_bf16", "fp8"}
    assert v["reported"] == ["bias_in_weights"]
    assert (v["precision_below"], v["state_precision_below"]) == (
        "fp8", "state_bf16")
    assert all(v["ablation_factor"][a] >= 1 for a in v["ablations"])
    # two limits over all the check's rows, each between two readings of
    # the harness's own: the lower-quartile row's under float8 (by 2x)
    # and every ablation, the worst row's under the faults planted in
    # SOME rows; nothing is held over all rows together (that number
    # stood above its upper reading, REVIEW of PR 64)
    assert "logits_rel_rms" not in v and "logits_rel_rms_median_row" not in v
    assert 0 < v["logits_rel_rms_quartile_row"] <= 0.13
    assert v["logits_rel_rms_quartile_row"] < v["logits_rel_rms_worst_row"] < 1
    assert v["precision_below_factor"] >= 2
    assert min(v["ablation_factor"].values()) >= 1.75
    assert set(v["planted"]) == {"another_token", "null_entry"}
    assert all(f >= 1.15 for f in v["planted"].values())
    # the state entry at the median head, over the one-row rounding
    # event's reading (3e-4) and under a bfloat16 state's (3.2e-3)
    assert 3e-4 < v["state_rel_rms"] < 3.2e-3 / v["state_precision_factor"]
    assert v["state_precision_factor"] >= 2
    r = wl["rehearse"]["verify"]
    assert set(r["ablations"]) | set(r["reported"]) == set(
        v["ablations"]) | {"bias_in_weights"}
    assert len(wl["why"]) > 500 and len(v["why"]) > 500
    assert "TO BE" not in json.dumps(wl)


# -- the readers --------------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/ssm_proj/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/ssm/ssm_state/mul"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/ssm_proj/add"}
  %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mlp/while/body/moe_experts/mul"}
  %ragged-dot.5 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call"
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mlp/moe_shared/dot_general"}
}
'''
CONFIG_KEYS = {"hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
               "layer_types": ["mamba", "experts", "mamba", "attention",
                               "experts"],
               "hidden_size": 2688, "moe_intermediate_size": 1856,
               "n_routed_experts": 16, "num_key_value_heads": 2,
               "head_dim": 128, "mamba_n_heads": 64, "mamba_d_head": 64,
               "mamba_d_state": 128,
               "generate": {"dtype": "bfloat16", "page_size": 128}}


def _counter(value, **labels):
    return {"values": [{"labels": labels, "value": value}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%fusion.2 = ...", 115.0, 20.0, {}),
        ("%fusion.3 = ...", 140.0, 5.0, {}),
        ("%fusion.4 = ...", 150.0, 10.0, {}),
        ("%ragged-dot.5 = ...", 165.0, 30.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # outside a step
        ("%fusion.1 = ...", 520.0, 25.0, {}),            # decode run 2
        ("%ragged-dot.5 = ...", 550.0, 40.0, {}),
    ]
    mods = [("jit__prefill_bucket(7)", 300.0, 90.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP},
        "registry": {
            "before": {"decode_steps_total": _counter(3),
                       "moe_experts_hit_total": _counter(100,
                                                         phase="decode"),
                       "moe_assignments_total": _counter(
                           1000, phase="decode")},
            "after": {"decode_steps_total": _counter(5),
                      "moe_experts_hit_total": _counter(150,
                                                        phase="decode"),
                      "moe_assignments_total": _counter(
                          1096, phase="decode")}},
        "config": CONFIG_KEYS, "traffic": {"gen_slots": 32},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    assert nemotron.sizes({"config": CONFIG_KEYS}) == (
        2688, 1856, 16, 2, 1, 2, 128, 2)
    assert nemotron.sizes({"config": {"conv_L_cache": 3}}) is None
    # the issue's figures: an expert's two matrices 9,977,856 parameters
    assert nemotron.plain_expert_bytes(1, 2688, 1856, 2) == 2 * 9_977_856
    # a token's K and V in the six attention layers: 6,144 B
    assert nemotron.kv_bytes(1, 6, 2, 128, 2) == 6144
    # a slot's 23 states, read and written: 2 x 23 x 2,097,152 B
    rec = {"config": {**CONFIG_KEYS, "layer_types": ["mamba"] * 23,
                      "num_hidden_layers": 23}}
    assert nemotron.state_bytes(rec, 1) == 2 * 23 * 2_097_152
    # 32 slots x 6 assignments over 128 experts: 1.5 rows an expert
    assert 32 * 6 / 128 == 1.5


def test_the_three_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in NEW_METRICS}
    # 50 held experts hit x 2 matrices x 2,688 x 1,856 x 2 B over the
    # 10 + 30 + 40 ns under moe_experts (the ragged-dots are its) in the
    # two runs
    want = 100.0 * (50 * 2 * 2688 * 1856 * 2) / 80e-9 / 1e9
    assert abs(got["moe_plain_experts_roofline"] - want) < 1e-6 * want
    # 10 + 5 + 25 ns under ssm_proj over 2 steps
    assert abs(got["ssm_proj_ms_per_step"] - 40e-9 / 2 * 1e3) < 1e-12
    # 96 assignments over 16 held experts x 2 routed layers x 2 steps
    assert got["moe_held_rows_per_expert"] == 1.5
    # three matrices an expert would read 1.5 times the bytes
    rec["config"] = {**CONFIG_KEYS, "generate": {"dtype": "bfloat16"}}
    held = run.load_reader("moe_held_experts_roofline")(rec)
    assert abs(held / got["moe_plain_experts_roofline"] - 1.5) < 1e-9


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/blk_mixer/ssm/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare}},
                   {"trace": None}, {"compiled_text": {}},
                   {"registry": {"before": {}, "after": {}}},
                   {"config": {"conv_L_cache": 3, "generate": {}}}):
        rec = {**_record(), **change}
        for name in NEW_METRICS:
            counters_alone = name == "moe_held_rows_per_expert"
            if counters_alone and not ({"registry", "config"} & set(change)):
                continue                # no text, no trace: still reads
            if name == "ssm_proj_ms_per_step" and "config" in change:
                continue                # the scope alone: any model's
            assert run.load_reader(name)(rec) is None, (name, change)


# -- the cell, rehearsed ------------------------------------------------------


def test_the_cell_rehearses_traced_and_reads_what_it_lists():
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
    # the grouped walk is interpreted off the chip: no custom call of
    # its name, so its roofline finds nothing to read in a rehearsal
    assert (allowed - {"attn_full_roofline"}
            <= set(out["metrics"]) <= allowed)
    for name in NEW_METRICS + ("ssm_ms_per_step", "ssm_state_roofline",
                               "moe_shared_ms_per_step", "step_mlp_ms",
                               "step_mixer_ms"):
        assert out["metrics"][name]["value"] > 0, name
    assert 0 < out["metrics"]["cache_state_bytes_share"]["value"] < 100


def test_the_cell_rehearses_untraced():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", CELL, "--seed", "5",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"gen_tokens_per_s", "setup_s"}
