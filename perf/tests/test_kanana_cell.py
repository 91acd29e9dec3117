"""The ``kanana-2-30b-a3b`` entries of ``BENCHMARK.json`` and their
files: the traffic as ISSUE 45 names it (one deal of 32 requests, eight
prompt lengths off the bucket ladder, eight prime answer budgets, the
longest request within the rows a sequence holds, the long prompts
spread through the deal), the configuration uncut from the catalog's
row but for the three keys in ``reduced``, and the lists the cell was
appended to.  Written so that a later PR's appended cell or metric
breaks nothing here: positions are counted from the front.  (Cases a
later PR would add to ``test_traffic.py`` and ``test_benchmark_json.py``:
a PR that adds a cell edits no file the benchmark has.)"""

import json
import os

from perf.harness import loadgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kanana-2-30b-a3b-generate-longdoc"
CONFIG = "kanana-2-30b-a3b"
TRAFFIC = "generate-longdoc-s64"
NEW_METRICS = ("attn_latent_ms_per_step", "attn_latent_roofline",
               "attn_latent_flops_share", "attn_latent_prefill_flops_share")
CELLS_BEFORE, METRICS_BEFORE = 8, 69
REDUCED = {"num_hidden_layers": (16, 48), "n_routed_experts": (16, 128),
           "vocab_size": (16032, 128256)}
ABLATIONS = ["no_kv_norm", "rope_rotate_half", "rope_on_nope",
             "scale_rsqrt128", "k_rope_per_head", "softmax_router",
             "no_renorm", "scale_1", "top_k5", "shared_off",
             "dense_layer0_off"]


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
    assert t["prompt_lengths"] == [[300, 6], [700, 6], [1100, 5], [1800, 5],
                                   [2600, 4], [3600, 3], [5000, 2],
                                   [7000, 1]]
    assert t["max_tokens"] == [[251, 4], [347, 5], [431, 5], [509, 5],
                               [613, 5], [757, 4], [877, 2], [1021, 2]]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 32
    assert sum(p for p, _ in deal) == 58700          # mean 1,834
    assert sum(b for _, b in deal) / 32 == 541.5
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # off the ladder 128, 256 .. 8,192, and 82,944 bucket rows a deal
    ladder = [128 << i for i in range(7)]
    assert not set(ladder) & {p for p, _ in deal}
    assert sum(min(b for b in ladder if b >= p) for p, _ in deal) == 82944


def test_the_long_prompts_are_spread_and_the_longest_request_fits():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 8192 and g["slots"] == t["gen_slots"] == 64
    deal = t["deal"]
    # the 7,000- and the 5,000-row prompts answer in at most 613
    assert all(b <= 613 for p, b in deal if p >= 5000)
    assert [7000, 613] in deal
    assert max(sum(r) for r in deal) == 7613 <= rows
    # the ten prompts of 2,600 rows and more: every third place or so
    at = [i for i, (p, _) in enumerate(deal) if p >= 2600]
    assert len(at) == 10
    gaps = [b - a for a, b in zip(at, at[1:] + [at[0] + 32])]
    assert set(gaps) <= {3, 4}


def test_every_catalog_key_is_uncut_but_the_three_in_reduced():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert sorted(cfg["reduced_why"]) == sorted(REDUCED)
    # the row of the model-configs guide's catalog, copied beside the
    # tests' data
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "kanana-2-30b-a3b-instruct-2601"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    for key, published in row["config"].items():
        if key in REDUCED:
            assert (cfg[key], published) == REDUCED[key], key
        else:
            assert cfg[key] == published, key
    assert (cfg["num_hidden_layers_published"],
            cfg["n_routed_experts_published"],
            cfg["vocab_size_published"]) == (48, 128, 128256)
    assert (cfg["ep_size"], cfg["ep_rank"]) == (8, 0)
    assert cfg["vocab_size"] * cfg["ep_size"] == 128256
    # the widths, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"]) == (
        2048, 6144, 768, 32)
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["q_lora_rank"]) == (
        128, 64, 128, 512, None)
    assert (cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"], cfg["first_k_dense_replace"]) == (
        6, 2, 2.448, 1)
    assert cfg["rope_interleave"] is True and cfg["rope_scaling"] is None
    for said in ("stands_for", "assumed", "departures"):
        assert cfg[said], said
    assert "QK_ROW_STD" in cfg["assumed"]["weights"]
    g = cfg["generate"]
    assert (g["row_lanes_algorithm"], g["row_lanes_stored"]) == (576, 640)
    assert g["num_pages"] <= g["slots"] * g["pages_per_seq"] + 1
    assert g["planned_bytes"] <= 15.0e9
    # weights + pool: at least 10 GB of the chip
    pool = (cfg["num_hidden_layers"] * g["num_pages"] * g["page_size"]
            * g["row_lanes_stored"] * 2)
    assert pool + 2 * 1_802_973_056 >= 10e9


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:9]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 6
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    per = {m["name"]: m for m in BENCH["per_layer"]}
    mine = BENCH["per_layer"][METRICS_BEFORE:METRICS_BEFORE + 4]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["layer"], m["source"]) == (
            "gen_tokens_per_s", "latent attention", "device_trace")
    exaone = "k-exaone-236b-a23b-generate-mixed"
    moe_by_the_record = {
        "moe_ms_per_step", "moe_load_max_over_mean",
        "moe_held_experts_roofline", "moe_held_assignment_share",
        "moe_shared_ms_per_step", "moe_prefill_ms", "moe_grouped_fill"}
    for name, m in per.items():
        listed = m.get("workloads", [])
        if name in NEW_METRICS:
            continue
        if name.startswith(("rpa_", "lin_attn_", "ssm_", "attn_full",
                            "attn_window")):
            assert CELL not in listed, name
        elif (name.startswith(("decode_", "gen_idle_"))
              or name.endswith(".rate")) and exaone in listed:
            assert appended(listed), name
        elif name in moe_by_the_record or name == "cache_bytes_per_live_row":
            assert appended(listed), name
        else:
            assert CELL not in listed, name


def test_correct_holds_every_ablation_and_both_precisions():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_latent", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "kanana_mla_block"
    assert (v["prompt_lens"], v["tokens"], v["cached_len"], v["streams"]) == (
        [100, 1100, 5000], 16, 1024, 2)
    # the ablation prompt is longer than the cached part
    assert v["prompt_lens"][v["ablation_prompt"]] > v["cached_len"]
    assert v["ablations"] == ABLATIONS
    assert all(v["ablation_factor"][a] >= 2 for a in ABLATIONS)
    assert v["precisions_below"] == ["fp8", "latent_fp8"]
    assert 0 < v["logits_rel_rms_median_row"] < v["logits_rel_rms"]
    r = wl["rehearse"]["verify"]
    assert sorted(r["ablation_factor"]) == sorted(ABLATIONS)
