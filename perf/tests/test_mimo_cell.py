"""The ``mimo-v2.5`` entries of ``BENCHMARK.json`` and their files: the
traffic as ISSUE 62 names it (one deal of 24 requests, six prompt lengths
of 2,000-32,000 rows off the bucket ladder, four prime answer budgets,
the longest sequence 32,613 of 32,768 rows), the configuration uncut
from the catalog's row but for the depth, the experts held and the
vocabulary, the lists the cell was appended to and the ones it was left
off, every ablation known to the reference, the three new readers on a
hand-made compiled text, trace and registry, and the cell rehearsed end
to end.  (Cases a later PR would add to ``test_traffic.py``,
``test_benchmark_json.py`` and ``test_rehearse.py``: a PR that adds a
cell edits no file the benchmark has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import loadgen, mimo
from perf.harness import trace as tr
from perf.reference import mimo_v2_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mimo-v2.5-generate-agent"
CONFIG = "mimo-v2.5"
TRAFFIC = "generate-agent-s48"
NEW_METRICS = ("attn_window_roofline", "attn_full_rows_per_step",
               "cache_run_fill")
CELLS_BEFORE, METRICS_BEFORE = 13, 107
# accepted metrics whose readers would be wrong or silent here:
# ``cache_bytes_per_live_row`` takes ONE row size for both kinds of
# cache (a full layer's row is 2,560 B, a ring's 5,120);
# ``attn_chunk_flops_share`` counts 4 x heads x head size a pair (K and
# V of one width) and knows its model by ``conv_L_cache``; there is no
# shared expert, no state, no latent row; ``rpa_*`` read the ungrouped
# step's kernel
NOT_LISTED = ("cache_bytes_per_live_row", "attn_chunk_flops_share",
              "moe_shared_ms_per_step", "cache_state_bytes_share",
              "rpa_ms_per_step", "rpa_roofline", "moe_experts_roofline",
              "moe_prefill_flops_share", "short_conv_ms_per_step")
LISTED_FAMILIES = ("decode_step_ms", "decode_tick_ms", "gen_idle_tick_share",
                   "gen_idle_prefill_share", "prefill_mixer_ms",
                   "prefill_mlp_ms", "step_mixer_ms", "step_mlp_ms",
                   "serve_ttft_p95_ms.rate", "decode_prefill_ms.rate",
                   "moe_ms_per_step", "moe_load_max_over_mean",
                   "moe_prefill_ms", "moe_held_experts_roofline",
                   "moe_held_assignment_share", "moe_grouped_fill",
                   "attn_full_roofline", "attn_window_ms_per_step",
                   "prefill_chunk_ms_per_krow", "prefill_chunk_rows_share")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 48, 48, 96)
    assert (t["stagger_tokens"], t["trace_seconds"]) == (3, 10)
    assert t["prompt_lengths"] == [[2000, 4], [4000, 5], [8000, 6],
                                   [12000, 4], [20000, 3], [32000, 2]]
    assert t["max_tokens"] == [[307, 6], [613, 8], [1021, 6], [2039, 4]]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 24
    assert sum(p for p, _ in deal) == 248_000
    assert round(sum(p for p, _ in deal) / 24) == 10_333
    assert round(sum(b for _, b in deal) / 24) == 876
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # nine prompts run past the 8,192-row bucket: 69% of the prompt rows
    # are theirs, and 40% of all rows go through chunks over the rings
    long = [p for p, _ in deal if p > 8192]
    assert len(long) == 9 and round(100 * sum(long) / 248_000) == 69
    assert round(100 * sum(p - 8192 for p in long) / 248_000) == 40
    ladder = {2 ** k for k in range(7, 14)}
    assert not ladder & {p for p, _ in deal}
    # the longer reasoning on the larger task
    by = {}
    for p, b in deal:
        by.setdefault(p, []).append(b)
    assert by[12000] == [2039] * 4 and by[20000] == [1021] * 3
    assert by[32000] == [613] * 2 and by[2000] == [307] * 4
    assert sorted(by[8000]) == [613] * 3 + [1021] * 3
    assert sorted(by[4000]) == [307] * 2 + [613] * 3
    # the nine long prompts spread evenly: never two side by side
    at = [i for i, (p, _) in enumerate(deal) if p > 8192]
    assert all(b - a >= 2 for a, b in zip(at, at[1:]))


def test_the_longest_sequence_fits_and_the_pool_holds_the_deal():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 32768 and g["slots"] == t["gen_slots"] == 48
    assert max(p + b for p, b in t["deal"]) == 32613 <= rows
    assert g["ring_entries"] == t["gen_slots"] + 1
    # two deals seated at once (48 slots), a page rounded up a sequence
    pages = 2 * sum(-(-(p + b) // g["page_size"]) for p, b in t["deal"])
    assert pages < 0.6 * g["num_pages"]
    assert t["ramp_seconds"] >= 30 and t["trace_ramp_seconds"] >= 30
    assert t["ramp_why"] and "TO BE" not in t["ramp_why"]


# -- the configuration --------------------------------------------------------


def test_every_catalog_key_is_uncut_but_the_three_in_reduced():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert sorted(cfg["reduced_why"]) == sorted(reduced)
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "MiMo-V2.5"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    kept = {"num_hidden_layers": (7, 48), "n_routed_experts": (16, 256),
            "vocab_size": (19072, 152576)}
    for key, published in row["config"].items():
        if key in kept:
            assert (cfg[key], published) == kept[key]
            assert cfg[key + "_published"] == published
        else:
            assert cfg[key] == published, key
    # the widths, as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"],
            cfg["v_head_dim"], cfg["num_key_value_heads"],
            cfg["swa_num_key_value_heads"], cfg["sliding_window"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (4096, 64, 192, 128, 4, 8, 128,
                                           16384, 2048, 8)
    assert (cfg["rope_theta"], cfg["swa_rope_theta"],
            cfg["attention_value_scale"], cfg["partial_rotary_factor"]) == (
        10_000_000, 10_000, 0.707, 0.334)
    assert cfg["add_swa_attention_sink_bias"] is True
    assert cfg["add_full_attention_sink_bias"] is False
    assert cfg["n_shared_experts"] is None
    # F(dense) S S S S F S: a whole period and the leading layer
    L = cfg["num_hidden_layers"]
    assert cfg["hybrid_layer_pattern"][:L] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"][:L] == [0, 1, 1, 1, 1, 1, 1]
    assert L - 1 >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert (cfg["ep_size"], cfg["ep_rank"]) == (16, 0)
    assert cfg["n_routed_experts"] * cfg["ep_size"] == 256
    assert cfg["num_experts"] == cfg["n_routed_experts"]
    assert mimo.sizes({"config": cfg}) == (2, 5, 4, 8, 320, 256, 2)
    for said in ("stands_for", "assumed", "departures", "rehearse",
                 "kept_layers"):
        assert cfg[said], said
    for reading in ("pre_norm", "rotated_channels", "rope_per_kind",
                    "value_scale", "attention_chunk_size", "sink",
                    "selection_bias", "score_scale", "moe_layer_freq"):
        assert cfg["assumed"][reading], reading
    told = " ".join(cfg["departures"])
    for word in ("multi-token-prediction", "vision tower", "audio encoder",
                 "256 lanes", "240 experts"):
        assert word in told, word
    g = cfg["generate"]
    assert (g["prefill_rows"], g["chunk_rows"], g["pages_per_seq"]) == (
        8192, 4096, 256)
    assert 0 < g["planned_bytes"] <= 15.0e9
    # weights + run pools (K at 256 lanes, V at 128, two layers): over
    # 75% of the chip
    pools = g["num_pages"] * g["page_size"] * 4 * (256 + 128) * 2 * 2
    assert 2 * 3_429_955_392 + pools >= 0.75 * 16e9
    assert "3,429,955,392" in cfg["reduced_why"]["num_hidden_layers"]
    assert "TO BE" not in json.dumps(cfg)


# -- the benchmark's lists ----------------------------------------------------


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 11
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    mine = BENCH["per_layer"][METRICS_BEFORE:METRICS_BEFORE + 3]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "gen_tokens_per_s"
        assert m["layer"] == "decode engine"
        assert m["source"] == ("device_trace" if m["name"]
                               == "attn_window_roofline"
                               else "program_counter")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    listed = {m["name"] for m in BENCH["per_layer"][:METRICS_BEFORE]
              if CELL in m.get("workloads", [])}
    assert set(LISTED_FAMILIES) <= listed and len(listed) == 48
    assert not set(NOT_LISTED) & listed
    for m in BENCH["per_layer"][:METRICS_BEFORE]:
        if m["name"] in listed:
            assert appended(m["workloads"]), m["name"]
            assert m["moves"] == "gen_tokens_per_s", m["name"]
    # every entry's cells report what it moves
    for m in BENCH["per_layer"]:
        for name in m.get("workloads", ()):
            assert name in e2e[m["moves"]].get("workloads", cells), (
                m["name"], name)


def test_every_listed_reader_loads():
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert callable(run.load_reader(m["name"])), m["name"]


def test_correct_holds_every_ablation_and_the_precision_below():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_ring_run", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "mimo_v2_block"
    under, over, bucket, chunked = v["prompt_lens"]
    # under one window; over one (the ablations'); one bucket; the top
    # bucket and three chunks
    assert under < 128 < over <= 512 < 2048 < bucket <= 4096
    assert chunked == 20000 and v["ablation_prompt"] == 1
    assert (v["tokens"], v["streams"]) == (16, 2)
    assert v["prompt_lens"][v["ablation_prompt"]] > 128
    # all the reference knows but the one the bf16 floor hides, and the
    # precision below by its own key
    assert set(v["ablations"]) == set(ref.ABLATIONS) - {"bias_in_weights",
                                                         "fp8"}
    assert v["precision_below"] == "fp8"
    assert all(v["ablation_factor"][a] >= 1.5 for a in v["ablations"])
    assert 0 < v["logits_rel_rms_median_row"] < v["logits_rel_rms"] <= 0.2
    r = wl["rehearse"]["verify"]
    assert sorted(r["ablation_factor"]) == sorted(v["ablations"])
    assert len(wl["why"]) > 500 and len(v["why"]) > 500
    assert "TO BE" not in json.dumps(wl)


# -- the readers --------------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/attn_window/gather"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/attn_window/exp"}
  %walk.3 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/attn_full/ragged_paged_attention_gqa/pallas_call"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/dot_general"}
}
'''
CONFIG_KEYS = {"hybrid_layer_pattern": [0, 1, 1, 0], "num_hidden_layers": 3,
               "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
               "head_dim": 192, "v_head_dim": 128,
               "generate": {"dtype": "bfloat16", "page_size": 128,
                            "num_pages": 101}}


def _counter(value, **labels):
    return {"values": [{"labels": labels, "value": value}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%fusion.2 = ...", 120.0, 30.0, {}),
        ("%walk.3 = ...", 155.0, 25.0, {}),
        ("%fusion.9 = ...", 185.0, 10.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # outside a step
        ("%fusion.2 = ...", 520.0, 60.0, {}),            # decode run 2
        ("%walk.3 = ...", 585.0, 15.0, {}),
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
                       "decode_active_slot_steps_total": _counter(10),
                       mimo.FULL_ROWS: _counter(1000),
                       mimo.RUN_PAGE_STEPS: _counter(50)},
            "after": {"decode_steps_total": _counter(5),
                      "decode_active_slot_steps_total": _counter(16),
                      mimo.FULL_ROWS: _counter(5000),
                      mimo.RUN_PAGE_STEPS: _counter(150)}},
        "config": CONFIG_KEYS, "traffic": {"gen_slots": 48},
        "kv_bytes": 4000 * 2 * 4 * 320 * 2.0,
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    # the first 3 layers of the pattern: F S S
    assert mimo.sizes({"config": CONFIG_KEYS}) == (1, 2, 4, 8, 320, 256, 2)
    assert mimo.sizes({"config": {"conv_L_cache": 3}}) is None
    # the issue's figures: a full layer 2,560 B a token, a ring row 5,120
    assert mimo.full_bytes(1, 1, 4, 320, 2) == 2560
    assert mimo.ring_bytes(1, 1, 1, 8, 320, 2) == 5120
    # 5 layers x 256 rows: 6.55 MB a sequence
    assert mimo.ring_bytes(1, 256, 5, 8, 320, 2) == 6_553_600
    # 600k live rows over 2 full layers: 3.07 GB a step
    assert mimo.full_bytes(600_000, 2, 4, 320, 2) == 3.072e9


def test_the_three_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in NEW_METRICS}
    # 6 seated slot-steps x 256 rows x 8 heads x 320 x 2 B x 2 window
    # layers over the 10 + 30 + 60 ns under attn_window in the two runs
    want = 100.0 * (6 * 256 * 8 * 320 * 2 * 2) / 100e-9 / 1e9
    assert abs(got["attn_window_roofline"] - want) < 1e-6 * want
    # 4,000 rows read over 2 steps
    assert got["attn_full_rows_per_step"] == 2000.0
    # 100 page-steps over 2 steps x 100 usable pages
    assert got["cache_run_fill"] == 50.0
    # the accepted reader finds this cell's kernel: the bytes the driver
    # counted over the walk's 25 + 15 ns
    full = run.load_reader("attn_full_roofline")(rec)
    assert abs(full - 100.0 * rec["kv_bytes"] / 40e-9 / 1e9) < 1e-3


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/ssm/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare}},
                   {"trace": None}, {"compiled_text": {}},
                   {"registry": {"before": {}, "after": {}}},
                   {"config": {"conv_L_cache": 3, "generate": {}}}):
        rec = {**_record(), **change}
        for name in NEW_METRICS:
            if name != "attn_window_roofline" and not (
                    "registry" in change):
                continue                # counters alone: no text, no trace
            assert run.load_reader(name)(rec) is None, (name, change)
    rec = _record()
    rec["registry"]["after"][mimo.FULL_ROWS] = _counter(1000)
    rec["registry"]["after"][mimo.RUN_PAGE_STEPS] = _counter(50)
    assert run.load_reader("attn_full_rows_per_step")(rec) is None
    assert run.load_reader("cache_run_fill")(rec) is None


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
    assert 0 < out["metrics"]["prefill_chunk_rows_share"]["value"] < 100
    assert out["metrics"]["attn_window_ms_per_step"]["value"] > 0
    assert out["metrics"]["attn_window_roofline"]["value"] > 0
    assert out["metrics"]["attn_full_rows_per_step"]["value"] > 0
    assert 0 < out["metrics"]["cache_run_fill"]["value"] < 100


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
