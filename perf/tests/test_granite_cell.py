"""The ``granite-4.0-h-micro`` entries of ``BENCHMARK.json`` and their
files: the traffic as ISSUE 41 names it (one deal of 32 requests, nine
prompt lengths off the bucket ladder, eight prime answer budgets, the
longest request within the rows a sequence holds), the configuration
uncut from the catalog's row, and the lists the cell was appended to.
(Cases a later PR would add to ``test_traffic.py`` and
``test_benchmark_json.py``: a PR that adds a cell edits no file the
benchmark has.)"""

import json
import os

from perf.harness import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite-4.0-h-micro-generate-longanswer"
CONFIG = "granite-4.0-h-micro"
TRAFFIC = "generate-longanswer-s64"
NEW_METRICS = ("ssm_ms_per_step", "ssm_state_roofline",
               "ssm_scan_ms_per_krow", "ssm_scan_flops_share")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 64, 64, 128)
    assert (t["ramp_seconds"], t["stagger_tokens"], t["trace_seconds"]) == (
        10, 3, 10)
    assert t["prompt_lengths"] == [[64, 5], [96, 5], [128, 4], [200, 5],
                                   [300, 4], [420, 3], [600, 3], [850, 2],
                                   [1200, 1]]
    assert t["max_tokens"] == [[131, 3], [179, 4], [223, 5], [277, 5],
                               [347, 5], [431, 4], [509, 3], [613, 3]]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 32
    assert sum(p for p, _ in deal) / 32 == 296.0
    assert round(sum(b for _, b in deal) / 32) == 326
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # off the ladder 128, 256, 512, 1,024, 1,920 but for the one bucket
    # a 128-row page forces
    ladder = {256, 512, 1024, 1920}
    assert not ladder & {p for p, _ in deal}


def test_the_longest_request_fits_the_rows_a_sequence_holds():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 1920 and g["slots"] == t["gen_slots"]
    assert g["state_entries"] == g["slots"] + 1
    longest = max(t["deal"], key=sum)
    assert longest == [1200, 613] and sum(longest) == 1813 <= rows


def test_the_configuration_is_the_catalogs_row_uncut():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 40
    assert [i for i, t in enumerate(cfg["layer_types"])
            if t == "attention"] == [5, 15, 25, 35]
    assert (cfg["hidden_size"], cfg["vocab_size"],
            cfg["shared_intermediate_size"]) == (2048, 100352, 8192)
    assert (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_n_groups"]) == (64, 64, 128, 4, 1)
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (
        12, 0.22, 0.015625, 8)
    assert cfg["tie_word_embeddings"] is True
    assert cfg["position_embedding_type"] == "nope"
    # the row of the model-configs guide's catalog, copied beside the
    # tests' data
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == CONFIG
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert {k: cfg[k] for k in row["config"]} == row["config"]


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-1] == CELL and len(cells) == 8
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["gen_tokens_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == list(NEW_METRICS)
    for name in NEW_METRICS:
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "gen_tokens_per_s"
        assert per[name]["layer"] == "state-space layer"
    hybrid = "olmo-hybrid-7b-generate-mixed"
    for name, m in per.items():
        mine = CELL in m.get("workloads", [])
        if name.startswith(("rpa_", "lin_attn_")) or name in NEW_METRICS:
            assert mine == (name in NEW_METRICS), name
        elif hybrid in m.get("workloads", []):
            # every decode_*, gen_idle_*, .rate and cache metric the
            # other hybrid's cell lists
            assert mine and m["workloads"][-1] == CELL, name
    assert CELL in per["attn_full_roofline"]["workloads"]


def test_correct_holds_the_attention_layers_and_the_state():
    """Every ablation ISSUE 41 names is held by a factor, the two of
    the attention layers among them (the q and k projections are drawn
    so that the softmax is not flat), and the state entries have a
    limit of their own, under which the reference's bfloat16 state has
    to fail; the pool is what 64 sequences can fill."""
    wl = _json("perf", "workloads", CELL + ".json")
    assert wl["driver"] == "generate_ssm"
    for v in (wl["verify"], wl["rehearse"]["verify"]):
        assert sorted(v["ablations"]) == sorted(
            ["no_decay", "no_dt_on_input", "no_conv", "no_conv_bias",
             "no_skip_D", "no_gate", "norm_before_gate",
             "softmax_scale_rsqrt", "rope_on_attention",
             "no_residual_multiplier", "post_norm"])
        assert all(v["ablation_factor"][a] >= 2 for a in v["ablations"])
        assert "reported" not in v
        assert 0 < v["state_rel_rms"] < v["logits_rel_rms"]
    v = wl["verify"]
    assert (v["precision_below"], v["state_precision_below"]) == (
        "fp8", "state_bf16")
    assert v["state_precision_factor"] >= 2
    cfg = _json("perf", "configs", CONFIG + ".json")
    g = cfg["generate"]
    assert g["num_pages"] == g["slots"] * g["pages_per_seq"] + 1 == 961
    assert "QK_ROW_STD" in cfg["assumed"]["weights"]
