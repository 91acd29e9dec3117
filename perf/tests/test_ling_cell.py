"""The ``ling-3.0-flash`` entries of ``BENCHMARK.json`` and their files:
the traffic as ISSUE 55 names it (one deal of 48 requests, eight prompt
lengths of 200-6,000 rows off the bucket ladder, eight prime answer
budgets six requests each, the longest sequence 8,039 of 8,192 rows),
the configuration uncut from the catalog's row but for the three keys in
``reduced``, the lists the cell was appended to, every ablation known to
the reference, the four new readers on a hand-made compiled text, trace
and registry, and the cell rehearsed end to end.  (Cases a later PR
would add to ``test_traffic.py``, ``test_benchmark_json.py`` and
``test_rehearse.py``: a PR that adds a cell edits no file the benchmark
has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import ling_hybrid as lg
from perf.harness import linear_attn, loadgen
from perf.harness import trace as tr
from perf.reference import ling_hybrid_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ling-3.0-flash-generate-reasoning"
CONFIG = "ling-3.0-flash"
TRAFFIC = "generate-reasoning-s128"
NEW_METRICS = ("attn_latent_layers_roofline", "lin_attn_gate_ms_per_step",
               "moe_route_ms_per_step", "moe_group_load_max_over_mean")
CELLS_BEFORE, METRICS_BEFORE = 11, 98
REDUCED = {"num_hidden_layers": (6, 42), "num_experts": (128, 512),
           "vocab_size": (39296, 157184)}
# accepted metrics whose readers would be wrong here: the latent
# layer's count every layer of the configuration as latent
NOT_LISTED = ("attn_latent_roofline", "attn_latent_flops_share",
              "attn_latent_prefill_flops_share", "cache_bytes_per_live_row")
LISTED_FAMILIES = ("lin_attn_ms_per_step", "lin_attn_state_roofline",
                   "lin_attn_scan_ms_per_krow", "lin_attn_scan_flops_share",
                   "cache_state_bytes_share", "attn_latent_ms_per_step",
                   "moe_ms_per_step", "moe_load_max_over_mean",
                   "moe_held_experts_roofline", "moe_held_assignment_share",
                   "moe_shared_ms_per_step", "moe_prefill_ms",
                   "moe_grouped_fill", "step_mixer_ms", "prefill_mixer_ms",
                   "decode_tick_ms", "gen_idle_tick_share",
                   "serve_ttft_p95_ms.rate")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 128, 128, 256)
    assert (t["stagger_tokens"], t["trace_seconds"]) == (3, 10)
    assert t["prompt_lengths"] == [[200, 8], [400, 8], [700, 8], [1100, 7],
                                   [1700, 6], [2600, 5], [4000, 4],
                                   [6000, 2]]
    assert t["max_tokens"] == [[b, 6] for b in (509, 613, 751, 1021, 1279,
                                                1531, 1789, 2039)]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 48
    assert sum(p for p, _ in deal) == 69300          # mean 1,443.75
    assert sum(b for _, b in deal) / 48 == 1191.5
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # off the ladder 128, 256 .. 8,192
    assert not {p for p, _ in deal} & {128 << i for i in range(7)}


def test_the_longest_sequence_fits_and_long_prompts_are_spread():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    cfg = _json("perf", "configs", CONFIG + ".json")
    g = cfg["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 8192 and g["slots"] == t["gen_slots"] == 128
    # an entry a slot and the null entry; every slot's run at its limit
    assert g["state_entries"] == g["slots"] + 1
    assert g["num_pages"] == g["slots"] * g["pages_per_seq"] + 1
    deal = t["deal"]
    assert [6000, 2039] in deal
    assert max(sum(r) for r in deal) == 8039 <= rows
    # the seventeen prompts of 1,700 rows and more: never two in a row
    at = [i for i, (p, _) in enumerate(deal) if p >= 1700]
    assert len(at) == 17
    assert all((b - a) % 48 > 1 for a, b in zip(at, at[1:] + [at[0] + 48]))
    # answers outweigh prompts: most of a window is decode
    assert sum(b for _, b in deal) > 0.8 * sum(p for p, _ in deal)


# -- the configuration --------------------------------------------------------


def test_every_catalog_key_is_uncut_but_the_three_in_reduced():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert sorted(cfg["reduced_why"]) == sorted(REDUCED)
    # the row of the model-configs guide's catalog, copied beside the
    # tests' data
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "Ling-3.0-flash"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    for key, published in row["config"].items():
        if key in REDUCED:
            assert (cfg[key], published) == REDUCED[key], key
            assert cfg[key + "_published"] == published, key
        else:
            assert cfg[key] == published, key
    # the widths, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_attention_heads"], cfg["head_dim"]) == (
        2560, 6144, 768, 768, 32, 128)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (
        None, 512, 128, 64, 128)
    assert (cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"]) == (8, 8, 4, 2.5)
    assert (cfg["kda_lower_bound"], cfg["kda_safe_gate"],
            cfg["short_conv_kernel_size"], cfg["layer_group_size"]) == (
        -5, True, 4, 6)
    # the deployment: 28 chips, seven stages of a period, 4 a stage
    assert (cfg["deployment_chips"], cfg["deployment_pipeline_stages"],
            cfg["deployment_ep_size"], cfg["deployment_ep_rank"]) == (
        28, 7, 4, 0)
    assert cfg["num_hidden_layers"] * cfg["deployment_pipeline_stages"] == 42
    assert cfg["num_experts"] * cfg["deployment_ep_size"] == 512
    assert cfg["vocab_size"] * cfg["deployment_vocab_shards"] == 157184
    # the floors: a whole period, four layers after the leading dense
    # ones, 8 experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == cfg["layer_group_size"]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"]
    # no clamped SwiGLU on a layer that is kept
    kept = cfg["num_hidden_layers"]
    assert not any(cfg["expert_swiglu_limit_list"][:kept])
    assert not any(cfg["share_expert_swiglu_limit_list"][:kept])
    assert any(cfg["expert_swiglu_limit_list"])      # kept whole
    # what the accepted linear-attention readers take the sizes by
    assert cfg["layer_types"] == ["linear_attention"] * 5 + [
        "latent_attention"]
    assert linear_attn.sizes({"config": cfg}) == (5, 32, 128, 128)
    for said in ("stands_for", "assumed", "departures", "rehearse",
                 "derived_why"):
        assert cfg[said], said
    told = " ".join(cfg["departures"])
    for word in ("multi-token-prediction", "clamped SwiGLU",
                 "UnsupportedOverState", "final norm"):
        assert word in told, word
    for reading in ("use_qk_norm", "kda_gate",
                    "gated_attention_proj_granularity_type", "router",
                    "weights"):
        assert cfg["assumed"][reading], reading
    g = cfg["generate"]
    assert (g["row_lanes_algorithm"], g["row_lanes_stored"]) == (576, 640)
    assert 0 < g["planned_bytes"] <= 15.0e9
    # weights + states + pages: over 60% of the chip
    states = g["state_entries"] * 5 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    pages = g["num_pages"] * g["page_size"] * 640 * 2
    assert 2 * 3_639_533_344 + states + pages >= 0.6 * 16e9


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:12]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 9
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    mine = BENCH["per_layer"][METRICS_BEFORE:METRICS_BEFORE + 4]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    layers = {m["layer"] for m in BENCH["per_layer"][:METRICS_BEFORE]}
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "gen_tokens_per_s" and m["layer"] in layers
        assert m["source"] == ("program_counter" if m["name"]
                               == "moe_group_load_max_over_mean"
                               else "device_trace")
        assert m["name"].endswith("_roofline") == (
            m["unit"] == "%" and m["better"] == "higher")
    listed = {m["name"] for m in BENCH["per_layer"][:METRICS_BEFORE]
              if CELL in m.get("workloads", [])}
    assert set(LISTED_FAMILIES) <= listed
    assert not set(NOT_LISTED) & listed
    for m in BENCH["per_layer"][:METRICS_BEFORE]:
        if m["name"] in listed:
            assert appended(m["workloads"]), m["name"]
            assert m["moves"] == "gen_tokens_per_s", m["name"]


def test_every_listed_reader_loads():
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert callable(run.load_reader(m["name"])), m["name"]


def test_correct_holds_every_ablation_and_the_precisions():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_hybrid_latent", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "ling_hybrid_block"
    short, middle, long_ = v["prompt_lens"]
    # about 300, 2,200 and 6,000 rows: the 512-, the 4,096- and the
    # 8,192-row buckets, none on the ladder
    assert 256 < short <= 512 and 2048 < middle <= 4096 < long_ <= 8192
    assert long_ + v["tokens"] <= 8192
    assert (v["tokens"], v["streams"], v["ablation_prompt"]) == (16, 2, 1)
    # the latent layer's scale is read and written down, not held: the
    # softmax of a random layer over thousands of rows is flat
    assert (tuple(v["ablations"]) + tuple(v["ablations_reported"])
            == ref.ABLATIONS)
    assert v["ablations_reported"] == ["latent_scale_rsqrt128"]
    assert all(v["ablation_factor"][a] >= 1.5 for a in v["ablations"])
    # float8 weights, float8 latent rows and bfloat16 states each fail
    assert tuple(v["precisions_below"]) == ref.PRECISIONS == (
        "fp8", "latent_fp8", "state_bf16")
    assert 0 < v["logits_rel_rms_quartile_row"] <= v["logits_rel_rms"]
    # the group step on the router's sets, the states on a state entry,
    # the latent rows on the rows the pages hold: the logits of a model
    # with ONE latent layer of six cannot see the last two
    assert set(v["judged_on_router_sets"]) == {"no_group", "topk_group3"}
    assert v["judged_on_state"] == ["state_bf16"]
    assert v["judged_on_latent_rows"] == ["latent_fp8"]
    assert 0 < v["top_k_set_differs_share"] < 0.5
    assert 0 < v["state_rel_rms"] <= 1e-3
    assert 0 < v["latent_rows_rel_rms"] <= 0.05
    assert 0 < v["latent_rows_float8_gap"] <= 0.02
    r = wl["rehearse"]["verify"]
    assert sorted(r["ablation_factor"]) == sorted(v["ablations"])
    assert len(wl["why"]) > 500 and len(v["why"]) > 500


def test_the_row_held_is_one_the_routers_flipped_choices_leave_clean():
    """``--seed 119680864``'s 6,000-row prompt as the chip read it (my
    chip run, PR 55, ``chiprun_out/pr55/i_diag_119680864.log``): ten of
    the 17 rows hold an expert the float32 router did not choose, the
    median row reads over the limit and the row at the first quartile
    is one of the seven clean ones."""
    import numpy as np

    from perf.drivers import generate_hybrid_latent as drv

    rows = [0.0505, 0.042, 0.069, 0.0582, 0.0573, 0.0188, 0.0655, 0.0196,
            0.0546, 0.0229, 0.0199, 0.0198, 0.0166, 0.0639, 0.0176, 0.074,
            0.0622]
    limit = _json("perf", "workloads", CELL + ".json")["verify"][
        "logits_rel_rms_quartile_row"]
    assert np.median(rows) > limit
    assert drv.CLEAN_ROW == 0.25
    assert np.quantile(rows, drv.CLEAN_ROW) == sorted(rows)[4] < limit / 2


# -- the readers --------------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/lin_attn_gate/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/lin_attn_gate/logistic"}
  %kda.3 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/lin_attn/lin_attn_state/jit(kda_step)/pallas_call"}
  %latent.4 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/jit(latent_paged_attention)/pallas_call"}
  %fusion.5 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mlp/moe_router/dot_general"}
  %topk.6 = f32[4]{0} custom-call(%p), custom_call_target="TopK", metadata={op_name="jit(_decode_step)/blk_mlp/moe_dispatch/moe_group/top_k"}
  %topk.7 = f32[4]{0} custom-call(%p), custom_call_target="TopK", metadata={op_name="jit(_decode_step)/blk_mlp/moe_dispatch/top_k"}
  %fusion.8 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mlp/moe_experts/dot_general"}
}
'''
CONFIG_AS_RUN = {"num_hidden_layers": 6, "kv_lora_rank": 512,
                 "qk_rope_head_dim": 64, "n_group": 8,
                 "layer_types": ["linear_attention"] * 5
                 + ["latent_attention"],
                 "generate": {"dtype": "bfloat16"}}


def _counter(value, **labels):
    return {"labels": labels, "value": value}


def _groups(rows):
    return {"values": [_counter(n, group=str(g), phase=phase)
                       for phase, by in rows.items()
                       for g, n in enumerate(by)]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%fusion.2 = ...", 111.0, 4.0, {}),
        ("%kda.3 = ...", 116.0, 30.0, {}),
        ("%latent.4 = ...", 147.0, 20.0, {}),
        ("%fusion.5 = ...", 168.0, 6.0, {}),
        ("%topk.6 = ...", 175.0, 5.0, {}),
        ("%topk.7 = ...", 181.0, 3.0, {}),
        ("%fusion.8 = ...", 185.0, 9.0, {}),
        ("%latent.4 = ...", 520.0, 30.0, {}),            # decode run 2
        ("%fusion.1 = ...", 700.0, 50.0, {}),            # in no decode run
    ]
    mods = [("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    steps = {"values": [_counter(3)]}, {"values": [_counter(5)]}
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP},
        "registry": {
            "before": {"decode_steps_total": steps[0],
                       lg.GROUPS_COUNTER: _groups(
                           {"decode": [10] * 8, "prefill": [5] * 8})},
            "after": {"decode_steps_total": steps[1],
                      lg.GROUPS_COUNTER: _groups(
                          {"decode": [110, 70, 60, 50, 40, 30, 20, 100],
                           "prefill": [905] * 8})}},
        "latent_rows": 50000,
        "config": CONFIG_AS_RUN, "traffic": {"gen_slots": 128},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_the_latent_layers_are_counted_from_layer_types():
    assert lg.latent_layers({"config": CONFIG_AS_RUN}) == 1
    # Kanana's file names no layer_types: nothing to count by
    assert lg.latent_layers({"config": {"kv_lora_rank": 512,
                                        "num_hidden_layers": 16}}) is None
    # Olmo-Hybrid's has layer_types and no latent layer
    assert lg.latent_layers({"config": {
        "layer_types": ["linear_attention"], "num_hidden_layers": 1}}) is None


def test_the_four_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in NEW_METRICS}
    # the kernel's 20 + 30 ns for 50,000 rows x ONE latent layer x the
    # algorithm's 1,152 B (six layers would read six times this)
    want = 100.0 * 50000 * 1 * 1152 / 50e-9 / 1e9
    assert abs(got["attn_latent_layers_roofline"] - want) < 1e-6 * want
    # by scope inside the decode runs, over 2 steps
    assert abs(got["lin_attn_gate_ms_per_step"] - 14e-9 / 2 * 1e3) < 1e-12
    # router + dispatch, the group step inside the latter
    assert abs(got["moe_route_ms_per_step"] - 14e-9 / 2 * 1e3) < 1e-12
    # decode: 100 of 400 rows on the busiest of 8 groups
    assert got["moe_group_load_max_over_mean"] == 100 / (400 / 8)


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/blk_mixer/attn_latent/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare},
                    "registry": {"before": {}, "after": {}}},
                   {"trace": None, "registry": None},
                   {"compiled_text": {},
                    "registry": {"before": {}, "after": {}}}):
        rec = {**_record(), **change}
        for name in NEW_METRICS:
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
    # the latent kernel is interpreted off the chip: no custom call of
    # its name, so its roofline finds nothing to read in a rehearsal
    assert (allowed - {"attn_latent_layers_roofline"}
            <= set(out["metrics"]) <= allowed)
    # a state entry of four toy KDA layers beside the latent rows
    assert 50 < out["metrics"]["cache_state_bytes_share"]["value"] < 100
    assert 1 <= out["metrics"]["moe_group_load_max_over_mean"]["value"] <= 2
    assert 0 < out["metrics"]["moe_held_assignment_share"]["value"] < 100


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
