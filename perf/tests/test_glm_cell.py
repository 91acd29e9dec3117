"""The ``glm-5`` entries of ``BENCHMARK.json`` and their files: the
traffic as ISSUE 53 names it (one deal of 24 requests, six prompt
lengths of 3,000-24,000 rows off the bucket ladder, eight prime answer
budgets, the longest sequence 24,613 of 25,600 rows, the long prompts
never two in a row), the configuration uncut from the catalog's row but
for the three keys in ``reduced``, the lists the cell was appended to,
every ablation known to the reference, the seven new readers on a
hand-made compiled text, trace and registry, and the cell rehearsed end
to end.  (Cases a later PR would add to ``test_traffic.py``,
``test_benchmark_json.py`` and ``test_rehearse.py``: a PR that adds a
cell edits no file the benchmark has.)"""

import json
import os
import subprocess
import sys

from perf import run
from perf.harness import loadgen
from perf.harness import sparse_latent as sp
from perf.harness import trace as tr
from perf.reference import glm_dsa_block as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm-5-generate-longctx"
CONFIG = "glm-5"
TRAFFIC = "generate-longctx-s32"
NEW_METRICS = ("attn_index_ms_per_step", "attn_select_ms_per_step",
               "attn_sparse_ms_per_step", "attn_index_roofline",
               "attn_sparse_roofline", "attn_index_selected_share",
               "attn_index_prefill_ms")
CELLS_BEFORE, METRICS_BEFORE = 10, 91
REDUCED = {"num_hidden_layers": (5, 78), "n_routed_experts": (16, 256),
           "vocab_size": (19360, 154880)}
# accepted metrics of the latent layer that find nothing true to read
# here: the kernel reads the selected rows, not the live ones
NOT_LISTED = ("attn_latent_roofline", "attn_latent_flops_share",
              "attn_latent_prefill_flops_share")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _json("BENCHMARK.json")


# -- the traffic --------------------------------------------------------------


def test_the_traffic_is_the_issues_letter_for_letter():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["gen_slots"], t["gen_queue"]) == (
        "closed", 32, 32, 64)
    assert (t["ramp_seconds"], t["stagger_tokens"], t["trace_seconds"]) == (
        10, 3, 10)
    assert t["prompt_lengths"] == [[3000, 5], [5000, 5], [8000, 5],
                                   [12000, 4], [16000, 3], [24000, 2]]
    assert t["max_tokens"] == [[b, 3] for b in (307, 347, 431, 509, 613, 757,
                                                877, 1021)]
    loadgen.check_deal(t)
    deal = t["deal"]
    assert len(deal) == 24
    assert sum(p for p, _ in deal) == 224000         # mean 9,333
    assert sum(b for _, b in deal) / 24 == 607.75
    for b, _ in t["max_tokens"]:                 # primes: no two divide
        assert all(b % d for d in range(2, int(b ** 0.5) + 1)), b
    # off the ladder 128, 256 .. 8,192 and off the chunks' edges
    assert not {p for p, _ in deal} & {128 << i for i in range(7)}
    assert all((p - 8192) % 4096 for p, _ in deal if p > 8192)


def test_every_context_selects_and_the_longest_sequence_fits():
    t = _json("perf", "traffic", TRAFFIC + ".json")
    cfg = _json("perf", "configs", CONFIG + ".json")
    g = cfg["generate"]
    rows = g["page_size"] * g["pages_per_seq"]
    assert rows == 25600 and g["slots"] == t["gen_slots"] == 32
    deal = t["deal"]
    # every context is over index_topk rows: every attention call selects
    assert min(p for p, _ in deal) > cfg["index_topk"] == 2048
    # the 16,000- and the 24,000-row prompts answer in at most 613
    assert all(b <= 613 for p, b in deal if p >= 16000)
    assert [24000, 613] in deal
    assert max(sum(r) for r in deal) == 24613 <= rows
    # the nine prompts of 12,000 rows and more: never two in a row
    at = [i for i, (p, _) in enumerate(deal) if p >= 12000]
    assert len(at) == 9
    assert all((b - a) % 24 > 1 for a, b in zip(at, at[1:] + [at[0] + 24]))
    # what a prompt over the top bucket runs: the bucket, then chunks of
    # 4,096 over what is cached, the chunk shapes set-up warms
    assert (g["prefill_rows"], g["chunk_rows"]) == (8192, 4096)


# -- the configuration --------------------------------------------------------


def test_every_catalog_key_is_uncut_but_the_three_in_reduced():
    cfg = _json("perf", "configs", CONFIG + ".json")
    entry = [c for c in BENCH["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == cfg["reduced"] == list(REDUCED)
    assert sorted(cfg["reduced_why"]) == sorted(REDUCED)
    # the row of the model-configs guide's catalog, copied beside the
    # tests' data
    row = _json("perf", "tests", "data", CONFIG + ".catalog_row.json")
    assert row["name"] == "GLM-5"
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
            cfg["moe_intermediate_size"], cfg["num_attention_heads"]) == (
        6144, 12288, 2048, 64)
    assert (cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == (
        2048, 512, 192, 64, 256)
    assert (cfg["index_n_heads"], cfg["index_head_dim"],
            cfg["index_topk"]) == (32, 128, 2048)
    assert (cfg["num_experts_per_tok"], cfg["n_shared_experts"],
            cfg["routed_scaling_factor"]) == (8, 1, 2.5)
    # kept as published and unused; the deployment under keys of its own
    assert (cfg["ep_size"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (1, 3, 1)
    assert (cfg["deployment_ep_size"], cfg["deployment_ep_rank"],
            cfg["leading_dense_layers"]) == (16, 0, 1)
    assert cfg["n_routed_experts"] * cfg["deployment_ep_size"] == 256
    assert cfg["vocab_size"] * cfg["deployment_vocab_shards"] == 154880
    # the floors: a period of one and four routed layers, 8 experts, 1/8
    assert cfg["num_hidden_layers"] - cfg["leading_dense_layers"] >= 4
    assert cfg["n_routed_experts"] >= 8
    for said in ("stands_for", "assumed", "departures", "rehearse"):
        assert cfg[said], said
    told = " ".join(cfg["departures"])
    for word in ("next-token-prediction", "Hadamard", "bfloat16", "ep_size"):
        assert word in told, word
    g = cfg["generate"]
    assert (g["row_lanes_algorithm"], g["row_lanes_stored"],
            g["index_row_lanes"]) == (576, 640, 128)
    assert 0 < g["planned_bytes"] <= 15.0e9
    page = cfg["num_hidden_layers"] * g["page_size"] * (640 + 128) * 2
    assert page == 983040 and g["planned_bytes"] + page > 15.0e9
    # weights + pools: over half the chip
    assert g["num_pages"] * page + 2 * 3_909_632_768 >= 12.4e9


def test_the_cell_is_appended_where_it_reports():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) == CELLS_BEFORE
    assert sum(w["chips"] == 4 for w in BENCH["workloads"][:11]) == 1
    cell = BENCH["workloads"][CELLS_BEFORE]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert [c["name"] for c in BENCH["configs"]].index(CONFIG) == 8
    before = set(cells[:CELLS_BEFORE])

    def appended(names):
        """Mine comes after every cell that was there before."""
        return set(names[:names.index(CELL)]) == before & set(names)

    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert appended(e2e["gen_tokens_per_s"]["workloads"])
    assert CELL not in e2e["gen_ttft_mid_ms"]["workloads"]
    mine = BENCH["per_layer"][METRICS_BEFORE:METRICS_BEFORE + 7]
    assert [m["name"] for m in mine] == list(NEW_METRICS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert (m["moves"], m["layer"]) == ("gen_tokens_per_s",
                                            "sparse latent attention")
        assert m["source"] == ("program_counter" if m["name"]
                               == "attn_index_selected_share"
                               else "device_trace")
        assert m["name"].endswith("_roofline") == (
            m["unit"] == "%" and m["better"] == "higher")
    kanana = "kanana-2-30b-a3b-generate-longdoc"
    for m in BENCH["per_layer"][:METRICS_BEFORE]:
        listed = m.get("workloads", [])
        if kanana in listed and m["name"] not in NOT_LISTED:
            assert appended(listed), m["name"]
        else:
            assert CELL not in listed, m["name"]


def test_every_listed_reader_loads():
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", []):
            assert callable(run.load_reader(m["name"])), m["name"]


def test_correct_holds_every_ablation_and_the_precisions():
    wl = _json("perf", "workloads", CELL + ".json")
    assert (wl["driver"], wl["config"], wl["traffic"], wl["chips"]) == (
        "generate_sparse_latent", CONFIG, TRAFFIC, 1)
    v = wl["verify"]
    assert v["reference"] == "glm_dsa_block"
    under, bucket, chunks = v["prompt_lens"]
    # one under index_topk (dense), one through the top bucket, one in
    # chunks and not under 12,000 rows
    assert under < 2048 < bucket <= 8192 < 12000 <= chunks
    assert (v["tokens"], v["streams"]) == (16, 2)
    assert v["prompt_lens"][v["ablation_prompt"]] > max(v["cached_len"],
                                                        2048)
    assert v["cached_len"] % 128 == 0
    assert tuple(v["ablations"]) == ref.ABLATIONS
    assert all(v["ablation_factor"][a] >= 1.5 for a in ref.ABLATIONS)
    assert (tuple(v["precisions_below"])
            + tuple(v["precisions_reported"])) == ref.PRECISIONS
    assert 0 < v["logits_rel_rms_median_row"] < v["logits_rel_rms"]
    # the logits GIVEN the system's sets are held tighter than those the
    # reference selects for itself, the sets themselves as sets
    assert 0 < v["given_sets_rel_rms_median_row"] < v["given_sets_rel_rms"] \
        <= v["logits_rel_rms"]
    assert v["given_sets_rel_rms_median_row"] < v["logits_rel_rms_median_row"]
    assert 0 < v["index_members_differ_share"] < 0.1
    assert tuple(v["judged_on_sets"]) == ref.INDEX_ABLATIONS
    assert v["judged_on_router_sets"] == ["top_k7"]
    assert 0 < v["top_k_set_differs_share"] < 0.5
    r = wl["rehearse"]["verify"]
    assert sorted(r["ablation_factor"]) == sorted(ref.ABLATIONS)
    assert len(wl["why"]) > 500 and len(v["why"]) > 500


# -- the readers --------------------------------------------------------------

STEP = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/attn_index/dot_general"}
  %index.2 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond/branch_0_fun/attn_index/jit(paged_index_scores)/pallas_call"}
  %topk.3 = f32[4]{0} custom-call(%p), custom_call_target="TopK", metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond/branch_0_fun/attn_index_select/top_k"}
  %gather.4 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond/branch_0_fun/attn_sparse/gather"}
  %fusion.5 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond/branch_0_fun/attn_sparse/attn_latent_absorb/dot_general"}
  %latent.6 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond/branch_0_fun/attn_sparse/jit(latent_paged_attention)/pallas_call"}
  %conditional.7 = f32[4]{0} conditional(%p), metadata={op_name="jit(_decode_step)/blk_mixer/attn_latent/cond"}
  %fusion.9 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_decode_step)/blk_mlp/moe_experts/dot_general"}
}
'''
CHUNK = '''
ENTRY %main {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket_chunk)/blk_mixer/attn_latent/attn_index/jit(index_scores)/pallas_call"}
  %while.2 = f32[4]{0} while(%p), metadata={op_name="jit(_prefill_bucket_chunk)/blk_mixer/attn_latent/attn_index_select/while"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(_prefill_bucket_chunk)/blk_mixer/attn_latent/attn_index_select/while/body/reduce_sum"}
  %flash.5 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(_prefill_bucket_chunk)/blk_mixer/attn_latent/attn_sparse/jit(selected_flash_attention)/pallas_call"}
}
'''
CONFIG_AS_RUN = {"num_hidden_layers": 5, "index_head_dim": 128,
                 "kv_lora_rank": 512, "qk_rope_head_dim": 64,
                 "generate": {"dtype": "bfloat16"}}


def _counter(value):
    return {"values": [{"labels": {}, "value": value}]}


def _record():
    ops = [  # (name, start_ns, dur_ns, stats)
        ("%fusion.1 = ...", 100.0, 10.0, {}),            # decode run 1
        ("%index.2 = ...", 112.0, 20.0, {}),
        ("%topk.3 = ...", 135.0, 30.0, {}),
        ("%conditional.7 = ...", 111.0, 85.0, {}),       # holds the others
        ("%gather.4 = ...", 166.0, 12.0, {}),
        ("%fusion.5 = ...", 179.0, 3.0, {}),
        ("%latent.6 = ...", 183.0, 8.0, {}),
        ("%fusion.9 = ...", 192.0, 5.0, {}),
        ("%fusion.1 = ...", 310.0, 40.0, {}),            # a chunk's run:
        ("%while.2 = ...", 352.0, 30.0, {}),             # the same names,
        ("%fusion.3 = ...", 353.0, 25.0, {}),            # its own text
        ("%flash.5 = ...", 385.0, 9.0, {}),
        ("%index.2 = ...", 520.0, 30.0, {}),             # decode run 2
    ]
    mods = [("jit__prefill_bucket_chunk(7)", 300.0, 100.0),
            ("jit__decode_step(1)", 500.0, 100.0),
            ("jit__decode_step(1)", 90.0, 110.0)]        # not in time order
    return {
        "trace": {"devices": {"/device:TPU:0": ops},
                  "host": [("t", tr.WINDOW_SPAN, 0.0, 1000.0)]},
        "trace_modules": {"/device:TPU:0": mods},
        "compiled_text": {"decode_step": STEP,
                          "prefill_bucket_chunk_4096_over_128": CHUNK},
        "registry": {
            "before": {"decode_steps_total": _counter(3),
                       sp.SCORED: _counter(1000), sp.SELECTED: _counter(100)},
            "after": {"decode_steps_total": _counter(5),
                      sp.SCORED: _counter(21000),
                      sp.SELECTED: _counter(4196)}},
        "config": CONFIG_AS_RUN, "traffic": {"gen_slots": 32},
        "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12},
    }


def test_sizes_and_the_algorithms_counts():
    # 256 B an index row, the ALGORITHM's 1,152 B a latent row
    assert sp.sizes({"config": CONFIG_AS_RUN}) == (5, 256, 1152)
    assert sp.sizes({"config": {"kv_lora_rank": 512}}) is None   # Kanana's


def test_the_seven_readers_arithmetic():
    rec = _record()
    got = {name: run.load_reader(name)(rec) for name in NEW_METRICS}
    # by scope in the two decode runs, over 2 steps; the conditional
    # that holds the branch is no instruction of its own
    assert abs(got["attn_index_ms_per_step"] - 60e-9 / 2 * 1e3) < 1e-12
    assert abs(got["attn_select_ms_per_step"] - 30e-9 / 2 * 1e3) < 1e-12
    assert abs(got["attn_sparse_ms_per_step"] - 23e-9 / 2 * 1e3) < 1e-12
    # the index kernel's 20 + 30 ns for 20,000 rows x 5 layers x 256 B
    want = 100.0 * 20000 * 5 * 256 / 50e-9 / 1e9
    assert abs(got["attn_index_roofline"] - want) < 1e-6 * want
    # the read: the fetch's 12 ns and the kernel's 8, not the absorbed
    # products' 3, for 4,096 rows x 5 layers x 1,152 B
    want = 100.0 * 4096 * 5 * 1152 / 20e-9 / 1e9
    assert abs(got["attn_sparse_roofline"] - want) < 1e-6 * want
    assert got["attn_index_selected_share"] == 100.0 * 4096 / 20000
    # under the two scopes inside the chunk's run, the loop's body and
    # not the loop: 40 + 25 ns a run
    assert abs(got["attn_index_prefill_ms"] - 65e-9 * 1e3) < 1e-12


def test_a_program_without_the_scopes_or_the_counters_reads_nothing():
    """The parent's programs, another model's, an untraced run: every
    reader hands back None and raises nothing."""
    bare = ('ENTRY %m {\n  %a.1 = f32[] add(), metadata={op_name='
            '"jit(_decode_step)/blk_mixer/attn_latent/mul"}\n}')
    for change in ({"compiled_text": {"decode_step": bare,
                                      "prefill_bucket_64": bare},
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
    # the index kernel is interpreted off the chip: no custom call of
    # its name, so its roofline finds nothing to read in a rehearsal
    assert allowed - {"attn_index_roofline"} <= set(out["metrics"]) <= allowed
    # latent and index rows, three toy layers, float32
    assert out["metrics"]["cache_bytes_per_live_row"]["value"] == 3 * 384 * 4
    assert 0 < out["metrics"]["attn_index_selected_share"]["value"] <= 100
