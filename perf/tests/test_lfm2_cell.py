"""The ``lfm2-8b-a1b`` entries of ``BENCHMARK.json`` and their files: the
traffic as ISSUE 59 names it (one deal of 32 requests, eight prompt
lengths of 1,200-24,000 rows off the bucket ladder, eight prime answer
budgets four requests each, the longest sequence 24,353 of 24,576
rows), the configuration uncut from the catalog's row but for the depth,
the lists the cell was appended to, every ablation known to the
reference, the five new readers on a hand-made compiled text, trace and
registry, and the cell rehearsed end to end.  (Cases a later PR would
add to ``test_traffic.py``, ``test_benchmark_json.py`` and
``test_rehearse.py``: a PR that adds a cell edits no file the benchmark
has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import loadgen
from perf.harness import short_conv as sc
from perf.harness import trace as tr
from perf.reference import lfm2_moe_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2-8b-a1b-generate-rag"
CONFIG = "lfm2-8b-a1b"
TRAFFIC = "generate-rag-s64"
NEW_METRICS = ("short_conv_ms_per_step", "short_conv_prefill_ms",
               "prefill_chunk_ms_per_krow", "attn_chunk_flops_share",
               "prefill_chunk_rows_share")
CELLS_BEFORE, METRICS_BEFORE = 12, 102
# accepted metrics whose readers would be wrong or silent here:
# ``harness/moe.py:model_sizes`` takes an expert's width by
# ``intermediate_size``, which is this model's DENSE width (four times
# the experts'); nothing is held in part; there is no shared expert
NOT_LISTED = ("moe_experts_roofline", "moe_prefill_flops_share",
              "moe_held_experts_roofline", "moe_held_assignment_share",
              "moe_shared_ms_per_step", "moe_grouped_fill")
LISTED_FAMILIES = ("decode_step_ms", "decode_tick_ms", "gen_idle_tick_share",
                   "gen_idle_prefill_share", "prefill_mixer_ms",
                   "prefill_mlp_ms", "step_mixer_ms", "step_mlp_ms",
                   "serve_ttft_p95_ms.rate", "decode_prefill_ms.rate",
                   "moe_ms_per_step", "moe_load_max_over_mean",
                   "moe_prefill_ms", "attn_full_roofline",
                   "cache_state_bytes_share")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 64, 64, 128)
    assert (t["stagger_tokens"], t["trace_seconds"]) == (3, 10)
    assert t["prompt_lengths"] == [[1200, 4], [2200, 5], [3600, 5],
                                   [5600, 5], [7800, 4], [11000, 4],
                                   [16000, 3], [24000, 2]]
    assert t["max_tokens"] == [[b, 4] for b in (61, 89, 113, 149, 181, 227,
                                                283, 353)]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 32
    assert sum(p for p, _ in deal) == 233_000
    assert sum(p for p, _ in deal) / 32 == 7281.25
    assert sum(b for _, b in deal) / 32 == 182.0
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # nine prompts run past the 8,192-row bucket: 28% of the rows
    past = [p - 8192 for p, _ in deal if p > 8192]
    assert len(past) == 9 and sum(past) == 66_272
    assert round(100 * sum(past) / 233_000) == 28
    ladder = {2 ** k for k in range(7, 14)}
    assert not ladder & {p for p, _ in deal}
    # no prompt length keeps to one budget
    for length, copies in t["prompt_lengths"]:
        assert len({b for p, b in deal if p == length}) == copies


def test_the_longest_sequence_fits_and_the_ramp_clears_the_first_prefills():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    g = _json("perf", "configs", CONFIG + ".json")["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 24576 and g["slots"] == t["gen_slots"]
    assert max(p + b for p, b in t["deal"]) == 24000 + 181
    assert 24000 + 353 <= rows           # whatever budget meets it
    assert g["state_entries"] == t["gen_slots"] + 1
    # two deals seated at once, a page rounded up a sequence
    pages = 2 * sum(-(-(p + b) // g["page_size"]) for p, b in t["deal"])
    assert pages < 0.6 * g["num_pages"]
    assert t["ramp_seconds"] >= 30


# -- the configuration --------------------------------------------------------


def test_every_catalog_key_is_uncut_but_the_depth():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert sorted(cfg["reduced_why"]) == ["num_hidden_layers"]
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "LFM2-8B-A1B"
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    for key, published in row["config"].items():
        if key == "num_hidden_layers":
            assert (cfg[key], published) == (12, 24)
            assert cfg[key + "_published"] == published
        else:
            assert cfg[key] == published, key
    # the widths, as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["conv_L_cache"],
            cfg["vocab_size"]) == (2048, 7168, 1792, 32, 8, 32, 4, 3, 65536)
    # stage 0 of two: three whole periods, both dense layers, ten routed
    kept = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert kept == ["conv", "conv", "full_attention", "conv"] * 3
    assert cfg["num_hidden_layers"] * 2 == cfg["num_hidden_layers_published"]
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] == 10 >= 4
    assert sc.attention_sizes({"config": cfg}) == (3, 32, 64)
    for said in ("stands_for", "assumed", "departures", "rehearse",
                 "derived_why"):
        assert cfg[said], said
    for reading in ("tied_head", "chunk_order", "route_eps",
                    "qk_norm_placement", "precision", "weights"):
        assert cfg["assumed"][reading], reading
    assert "lfm2_moe" in cfg["assumed"]["chunk_order"]
    told = " ".join(cfg["departures"])
    for word in ("stage 0", "final norm", "host's share", "chunks"):
        assert word in told, word
    g = cfg["generate"]
    assert (g["prefill_rows"], g["chunk_rows"], g["route_eps"]) == (
        8192, 4096, 1e-6)
    assert 0 < g["planned_bytes"] <= 15.0e9
    # weights + pages: over 80% of the chip; an entry 72 KB
    pages = g["num_pages"] * g["page_size"] * 6144
    assert 2 * 3_928_728_256 + pages >= 0.8 * 16e9
    assert "3,928,728,256" in cfg["reduced_why"]["num_hidden_layers"]


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:13]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 10
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    mine = BENCH["per_layer"][METRICS_BEFORE:METRICS_BEFORE + 5]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "gen_tokens_per_s"
        assert m["layer"] == "short conv + chunked prefill"
        assert m["source"] == ("program_counter" if m["name"]
                               == "prefill_chunk_rows_share"
                               else "device_trace")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    listed = {m["name"] for m in BENCH["per_layer"][:METRICS_BEFORE]
              if CELL in m.get("workloads", [])}
    assert set(LISTED_FAMILIES) <= listed and len(listed) == 43
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


def test_correct_holds_every_ablation_and_the_precisions():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_conv_hybrid", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "lfm2_moe_block"
    inside, top, chunked = v["prompt_lens"]
    # inside a bucket, at the top bucket, through the chunk path (the
    # bucket, then a 4,096-row chunk over 8,192 cached rows)
    assert 1024 < inside <= 2048 and 4096 < top <= 8192 < chunked <= 12288
    assert (v["tokens"], v["streams"], v["chunk_first_rows"]) == (16, 2, 4)
    assert tuple(v["ablations"]) == ref.ABLATIONS
    assert all(v["ablation_factor"][a] >= 1.5 for a in v["ablations"])
    assert tuple(v["precisions_below"]) == ref.PRECISIONS == (
        "fp8", "kv_fp8", "tail_fp8")
    assert (0 < v["given_sets_rel_rms_quartile_row"]
            <= v["given_sets_rel_rms"] <= v["logits_rel_rms"])
    assert v["judged_on_router_sets"] == ["bias_off"]
    assert v["judged_on_chunk_rows"] == ["tail_zero_at_chunk"]
    assert 0 < v["top_k_set_differs_share"] < 0.5
    assert 0 < v["entry_rel_rms"] <= 0.02
    assert 0 < v["float8_gap"] <= 0.02
    assert 0 < v["chunk_first_rows_rel_rms"] <= v["logits_rel_rms"]
    r = wl["rehearse"]["verify"]
    assert sorted(r["ablation_factor"]) == sorted(v["ablations"])
    assert len(wl["why"]) > 500 and len(v["why"]) > 500


# -- the readers --------------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/short_conv/mul"}
  %conv_step.2 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/short_conv/short_conv_step/conv_step/pallas_call"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/dot_general"}
}
'''
BUCKET = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/blk_mixer/short_conv/short_conv_scan/mul"}
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket)/blk_mixer/attn_full/flash"}
}
'''
CHUNK = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_state_chunk)/blk_mixer/short_conv/short_conv_scan/mul"}
  %gather.3 = f32[4]{0} gather(%p), metadata={op_name="jit(_prefill_state_chunk)/blk_mixer/attn_full/attn_chunk/gather"}
  %flash.4 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_state_chunk)/blk_mixer/attn_full/attn_chunk/flash_attention_fwd/pallas_call"}
  %while.5 = f32[4]{0} while(%p), metadata={op_name="jit(_prefill_state_chunk)/blk_mlp/while"}
  %fusion.6 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_state_chunk)/blk_mlp/moe_experts/dot"}
}
'''
CONFIG_KEYS = {"conv_L_cache": 3, "layer_types": ["conv", "conv",
                                                  "full_attention", "conv"],
               "num_hidden_layers": 4, "num_attention_heads": 4,
               "hidden_size": 256}


def _counter(value, **labels):
    return {"values": [{"labels": labels, "value": value}]}


def _hist(total, count):
    return {"values": [{"labels": {}, "sum": total, "count": count}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%conv_step.2 = ...", 120.0, 40.0, {}),
        ("%fusion.9 = ...", 175.0, 20.0, {}),
        ("%fusion.1 = ...", 310.0, 8.0, {}),             # a bucket's run
        ("%fusion.7 = ...", 320.0, 50.0, {}),
        ("%fusion.1 = ...", 410.0, 6.0, {}),             # a chunk's run
        ("%gather.3 = ...", 420.0, 4.0, {}),
        ("%flash.4 = ...", 425.0, 30.0, {}),
        ("%while.5 = ...", 456.0, 40.0, {}),             # spans its body
        ("%fusion.6 = ...", 460.0, 20.0, {}),
        ("%conv_step.2 = ...", 520.0, 60.0, {}),         # decode run 2
    ]
    mods = [("jit__prefill_bucket(7)", 300.0, 90.0),
            ("jit__prefill_state_chunk(9)", 400.0, 99.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP, "prefill_bucket_64": BUCKET,
                          "prefill_state_chunk_16_over_32": CHUNK},
        "registry": {
            "before": {"decode_steps_total": _counter(3),
                       "decode_prefill_seconds": _hist(1.0, 4),
                       "decode_prefill_tokens_total": _counter(100),
                       sc.CHUNK_ROWS: _counter(10, over="state"),
                       sc.CHUNK_PAIRS: _counter(1000, over="state")},
            "after": {"decode_steps_total": _counter(5),
                      "decode_prefill_seconds": _hist(3.0, 6),
                      "decode_prefill_tokens_total": _counter(180),
                      sc.CHUNK_ROWS: _counter(30, over="state"),
                      sc.CHUNK_PAIRS: _counter(1800, over="state")}},
        "config": CONFIG_KEYS, "traffic": {"gen_slots": 64},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_the_attention_layers_are_counted_from_layer_types():
    assert sc.attention_sizes({"config": CONFIG_KEYS}) == (1, 4, 64)
    assert sc.attention_sizes({"config": {"mamba_d_state": 128}}) is None
    # q.k and p.v, 2 x 64 each, 4 heads, 1 layer, a pair
    assert sc.chunk_attention_flops(800, 1, 4, 64) == 4 * 800 * 4 * 64
    # the issue's form for whole chunks: rows x (done + rows / 2) x 2 x 2
    # x heads x head size a layer, to the causal part's half row
    rows, done = 4096, 8192
    pairs = rows * done + rows * (rows + 1) // 2
    assert abs(sc.chunk_attention_flops(pairs, 1, 32, 64)
               / (rows * (done + rows / 2) * 2 * 2 * 32 * 64) - 1) < 1e-4


def test_the_five_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in NEW_METRICS}
    # under short_conv in the two decode runs: 10 + 40 + 60 ns, 2 steps
    assert abs(got["short_conv_ms_per_step"] - 110e-9 / 2 * 1e3) < 1e-12
    # under short_conv in the bucket's run (8) and the chunk's (6), over
    # the window's 2 admissions
    assert abs(got["short_conv_prefill_ms"] - 14e-9 / 2 * 1e3) < 1e-12
    # every event of the chunk's run but the loop that spans its body:
    # 6 + 4 + 30 + 20 ns over 20 real chunk rows
    assert abs(got["prefill_chunk_ms_per_krow"] - 60e-9 * 1e3 / 0.020) < 1e-9
    # 800 pairs x 4 x 4 heads x 64 x 1 layer over the 34 ns under
    # attn_chunk (the gather and the kernel), of 1e12 FLOP/s
    want = 100.0 * (800 * 4 * 4 * 64) / 34e-9 / 1e12
    assert abs(got["attn_chunk_flops_share"] - want) < 1e-6 * want
    # 20 of the window's 80 prompt rows
    assert got["prefill_chunk_rows_share"] == 25.0


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run, a window
    without a chunk: every reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/ssm/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare,
                                      "prefill_bucket_64": bare}},
                   {"trace": None}, {"compiled_text": {}},
                   {"registry": {"before": {}, "after": {}}}):
        rec = {**_record(), **change}
        for name in NEW_METRICS:
            if name == "prefill_chunk_rows_share" and "registry" not in change:
                continue                 # two counters: no text, no trace
            assert run.load_reader(name)(rec) is None, (name, change)
    rec = {**_record(), "config": {"mamba_d_state": 128}}
    assert run.load_reader("attn_chunk_flops_share")(rec) is None
    rec = _record()
    rec["registry"]["after"][sc.CHUNK_ROWS] = _counter(10, over="state")
    assert run.load_reader("prefill_chunk_rows_share")(rec) is None
    assert run.load_reader("prefill_chunk_ms_per_krow")(rec) is None


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
    # a toy entry of six tails beside four-row pages of two layers
    assert 0 < out["metrics"]["cache_state_bytes_share"]["value"] < 50
    assert 0 < out["metrics"]["prefill_chunk_rows_share"]["value"] < 100
    assert out["metrics"]["short_conv_ms_per_step"]["value"] > 0
    assert out["metrics"]["prefill_chunk_ms_per_krow"]["value"] > 0


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
