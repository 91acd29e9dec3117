"""Golden-config corpus: every v1 DSL config script from the reference's
trainer_config_helpers test suite (reference:
python/paddle/trainer_config_helpers/tests/configs/*.py, validated there
against 56 protostr goldens by ProtobufEqualMain.cpp).

Three oracles, strongest first:

- ``test_matches_reference_protostr`` — THE authoritative check: the
  captured layer graph is compared canonically against the
  *reference's own* checked-in protostr goldens
  (tests/protostr_oracle.py), so layer types, sizes, activations, and
  wiring are pinned to the reference spec, not to our own past output;
- most of the corpus additionally *runs one forward step* with
  synthesized feeds and must produce finite outputs — something the
  reference never does; PARSE_ONLY lists the exceptions with reasons;
- the self-captured JSON goldens (``tests/golden_v1_configs.json``)
  remain as a regression supplement (they also pin layer *names* and
  capture order, which the canonical protostr compare ignores).

Regenerate the supplement after an intentional DSL change (the
protostr oracle is never regenerated — it lives in the reference tree):
    PADDLE_TPU_REGEN_GOLDENS=1 python -m pytest tests/test_golden_configs.py -q
"""

import json
import os
import sys
import types

import numpy as np
import pytest

CONFIG_DIR = ("/root/reference/python/paddle/trainer_config_helpers/"
              "tests/configs")
GOLDEN_PATH = os.path.join(os.path.dirname(__file__),
                           "golden_v1_configs.json")
REGEN = os.environ.get("PADDLE_TPU_REGEN_GOLDENS", "0") == "1"

# The corpus lives in the reference tree, which most machines do not
# mount: the cases are parametrised from the checked-in golden file's
# names (so every xdist worker collects the same tests whether or not
# the tree is there) and skip when the tree is absent.
pytestmark = pytest.mark.skipif(
    not os.path.isdir(CONFIG_DIR),
    reason="reference config corpus not present")

# configs that parse+capture but do not run a forward step here, with
# the reason; everything else must run finite end-to-end
PARSE_ONLY = {
    "projections.py":
        "self-inconsistent feed contract: 'test' must simultaneously "
        "be embedding ids, a dense fc operand, and (via the chain) a "
        "context_projection sequence; the reference only proto-compares",
    "test_config_parser_for_non_file_config.py":
        "declares no outputs() (it tests the parse entrypoint itself)",
    "test_crop.py":
        "reference config bug: outputs(pad) references an undefined "
        "name; capture still validated up to the error",
    "test_cost_layers.py":
        "self-inconsistent feed contract: 'labels' is simultaneously a "
        "CTC id sequence, a 5000-wide huber regression target, and NCE "
        "class ids; the reference only proto-compares",
}

# per-config feed-kind overrides where a data layer's sequence level
# cannot be inferred from its consumers alone (the reference fixes the
# level in the data provider, which these proto-test configs omit):
#   nested  — 2-level nested sequence
#   nested1 — nested with exactly one subsequence per sample
#   seq1    — plain sequence of length exactly 1 (the reference
#             ExpandLayer contract for dense-side inputs)
FEED_KIND = {
    "test_sequence_pooling.py": {"dat_in": "nested"},
    "test_expand_layer.py": {"data": "seq1", "data_seq": "nested1"},
    # SubsequenceInput group iterates subsequences (reference:
    # RecurrentGradientMachine.cpp:530, sequence_nest_rnn.conf)
    "test_rnn_group.py": {"sub_seq_input": "nested"},
    # only input[0] of seq_slice is a sequence; starts/ends are (B, K)
    "test_seq_slice_layer.py": {"starts": "dense", "ends": "dense"},
    # selected_indices of sub_nested_seq is a dense (B, beam) id matrix
    "test_sub_nested_seq_select_layer.py": {"input": "dense"},
    # multibox 'label' rows are G dense ground-truth records of
    # [class, x1, y1, x2, y2, difficult], not class indices
    "test_multibox_loss_layer.py": {"label": "dense"},
}

# per-config batch-size overrides: trans_layer transposes the minibatch
# matrix, so the fc after it (weight 100x100, reference protostr
# test_fc.protostr dims 100,100) only type-checks when B == 100 — the
# same constraint the reference layer imposes at train time
B_OVERRIDE = {"test_fc.py": 100}

SEQ_CONSUMERS = {
    "seqlastins", "seqfirstins", "seq_pool", "pooling", "seq_concat",
    "seq_reshape", "seq_slice", "kmax_seq_score", "sub_seq",
    "sub_nested_seq", "expand", "lstmemory", "grumemory", "recurrent",
    "recurrent_layer_group",
    "row_conv", "ctc", "warp_ctc", "gated_recurrent", "seq_last",
    "seq_first", "max_id_seq", "crf", "seqtext_printer",
}
NESTED_CONSUMERS = {"sub_nested_seq"}


@pytest.fixture(scope="module", autouse=True)
def paddle_alias():
    """Reference config scripts do `from paddle.trainer_config_helpers
    import *`; alias our package under that name for the exec."""
    import paddle_tpu.trainer_config_helpers as tch

    created = "paddle" not in sys.modules
    pad = sys.modules.get("paddle") or types.ModuleType("paddle")
    pad.trainer_config_helpers = tch
    sys.modules["paddle"] = pad
    sys.modules["paddle.trainer_config_helpers"] = tch
    yield
    if created:
        sys.modules.pop("paddle", None)
        sys.modules.pop("paddle.trainer_config_helpers", None)


def _load_goldens():
    if os.path.exists(GOLDEN_PATH):
        with open(GOLDEN_PATH) as f:
            return json.load(f)
    return {}


def _configs():
    if REGEN:  # regenerating needs the tree: list what it holds now
        return sorted(f for f in os.listdir(CONFIG_DIR) if f.endswith(".py"))
    return sorted(_load_goldens())


def _fresh():
    import paddle_tpu.framework as framework
    import paddle_tpu.executor as em
    import paddle_tpu.v2.layer as v2_layer

    framework.reset_default_programs()
    em._global_scope = em.Scope()
    em._scope_stack = [em._global_scope]
    # auto-naming must be deterministic per config: reset the v2 uname
    # counter so captured structure is identical whether a config parses
    # alone or after 400 other tests (the golden diff is name-sensitive)
    v2_layer._counter[0] = 0


def _parse(fn):
    from paddle_tpu.trainer.config_parser import parse_config

    _fresh()
    path = os.path.join(CONFIG_DIR, fn)
    if fn == "test_crop.py":
        # the reference script ends with outputs(pad) where `pad` is
        # undefined; capture everything before that
        with pytest.raises(NameError):
            parse_config(path)
        from paddle_tpu.trainer_config_helpers import layers as _l

        # re-parse capturing manually so the partial capture is returned
        cap = {}
        _l._begin_capture(cap)
        try:
            src = open(path).read().replace("outputs(pad)", "outputs(crop)")
            exec(compile(src, path, "exec"), {"__name__": "cfg"})
        finally:
            _l._end_capture()
        from paddle_tpu.trainer.config_parser import TrainerConfig

        return TrainerConfig(cap)
    return parse_config(path)


def _structure(conf):
    rows = [[e["type"], e["name"], e.get("size")]
            for e in conf.model_config.layers]
    return {"layers": rows,
            "inputs": sorted(conf.model_config.input_layer_names),
            "n_outputs": len(conf.outputs or [])}


def _classify_inputs(conf):
    layers = conf.model_config.layers
    consumers = {}
    for e in layers:
        for i in e.get("inputs", []):
            consumers.setdefault(i, []).append(e)
    seq_names, nested_names = set(), set()
    data_names = set(conf.data_layers)

    def mark(origin, name, depth=0):
        for e in consumers.get(name, []):
            t = e["type"]
            if (t in NESTED_CONSUMERS and name == origin
                    and e.get("inputs") and e["inputs"][0] == origin):
                nested_names.add(origin)
                continue
            if t in SEQ_CONSUMERS:
                seq_names.add(origin)
                continue
            if depth < 3 and t in ("mixed", "concat", "addto", "scaling",
                                   "slope_intercept", "power",
                                   "interpolation", "fc"):
                mark(origin, e["name"], depth + 1)

    for n in data_names:
        mark(n, n)
    return seq_names & data_names, nested_names & data_names


def _run_config(fn, T=8, B=4):
    B = B_OVERRIDE.get(fn, B)
    import paddle_tpu as fluid
    import paddle_tpu.executor as executor_mod
    from paddle_tpu.v2 import data_type as dt
    from paddle_tpu.v2.topology import Topology
    from paddle_tpu.v2.trainer import V2DataFeeder

    conf = _parse(fn)
    seq_names, nested_names = _classify_inputs(conf)
    kinds = FEED_KIND.get(fn, {})
    rng = np.random.RandomState(0)
    for name, lo in conf.data_layers.items():
        size = lo.size or 1
        kind = kinds.get(name)
        if kind is not None:
            lo.input_type = (dt.dense_vector(size) if kind == "dense"
                             else dt.dense_vector_sub_sequence(size)
                             if kind.startswith("nested")
                             else dt.dense_vector_sequence(size))
        elif name in nested_names:
            lo.input_type = dt.dense_vector_sub_sequence(size)
        elif name in seq_names:
            lo.input_type = dt.dense_vector_sequence(size)
        elif "label" in name.lower() or name == "lbl":
            lo.input_type = dt.integer_value(size)
    outs = list(conf.outputs or [])
    assert outs, "config declares no outputs"
    topo = Topology(None, output_layers=outs)
    rows = []
    for _ in range(B):
        row = []
        for nm, t in topo.feed_types:
            if getattr(t, "seq_type", 0) == 2:
                nsub = (1 if kinds.get(nm) == "nested1"
                        else int(rng.randint(1, 3)))
                row.append([rng.rand(int(rng.randint(2, T)),
                                     t.dim).astype("float32")
                            for _ in range(nsub)])
            elif t.is_seq:
                L = 1 if kinds.get(nm) == "seq1" else int(rng.randint(2, T + 1))
                if t.dtype == "int64":
                    row.append(rng.randint(0, max(t.dim, 2), L).tolist())
                else:
                    row.append(rng.rand(L, t.dim).astype("float32"))
            else:
                if t.dtype == "int64":
                    row.append(int(rng.randint(0, max(t.dim, 2))))
                else:
                    row.append(rng.rand(t.dim).astype("float32"))
        rows.append(tuple(row))
    feed = V2DataFeeder(topo.feed_types).feed(rows)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = executor_mod.Scope()
    with executor_mod.scope_guard(scope):
        exe.run(topo.startup_program)
        vals = exe.run(topo.main_program, feed=feed,
                       fetch_list=[v.name for v in topo.output_vars])
    for v in vals:
        assert np.all(np.isfinite(np.asarray(v, dtype=np.float64))), \
            "non-finite output"


# Round-5 close: recurrent_group now captures its REAL machinery
# (step-input placeholders as scatter_agents, memory links as agents,
# the group node, gather_agent outputs) and gru_group/lstmemory_group
# are explicit groups like the reference's, so ALL 56 configs compare
# exactly and this table is empty.  Kept for any future deliberate
# redesign (entries get the weaker recurrence-site check below).
PROTOSTR_REDESIGNED = {}

# ref group-machinery types that mark one recurrence site
_REF_RECURRENCE_TYPES = {"recurrent_layer_group"}
_OUR_RECURRENCE_TYPES = {"gated_recurrent", "lstmemory", "recurrent",
                         "recurrent_group"}


def _protostr_name(fn):
    return fn[:-len(".py")] + ".protostr"


@pytest.mark.parametrize("fn", _configs())
def test_parse_and_structure(fn):
    conf = _parse(fn)
    got = _structure(conf)
    if fn != "test_config_parser_for_non_file_config.py":
        # that one only defines helpers for the non-file parse entry
        assert got["layers"], f"{fn}: no layers captured"
    goldens = _load_goldens()
    if REGEN:
        goldens[fn] = got
        with open(GOLDEN_PATH, "w") as f:
            json.dump(goldens, f, indent=1, sort_keys=True)
        return
    if fn not in goldens:
        pytest.fail(
            f"no golden recorded for {fn}; generate with "
            "PADDLE_TPU_REGEN_GOLDENS=1 (normal runs never write the "
            "golden file)")
    assert got == goldens[fn], (
        f"{fn}: captured structure diverges from the golden; if the "
        f"change is intentional regenerate with PADDLE_TPU_REGEN_GOLDENS=1")


@pytest.mark.parametrize("fn", _configs())
def test_matches_reference_protostr(fn):
    """THE v1 oracle: the captured layer graph must be
    wiring-equivalent to the reference's own checked-in protostr golden
    (reference: .../tests/configs/protostr/*.protostr, compared there
    by ProtobufEqualMain.cpp).  Canonical comparison is
    name-independent (tests/protostr_oracle.py): every layer's
    (type, size, activation, canonical inputs) and the output-layer
    multiset must match, modulo the short documented mapping tables in
    protostr_oracle (act/type spellings, aux-input folds, operator
    splices).  Configs in PROTOSTR_REDESIGNED assert the weaker
    recurrence-site invariant with the reason stated."""
    import collections

    import protostr_oracle as po

    if not os.path.exists(os.path.join(CONFIG_DIR, "protostr",
                                       _protostr_name(fn))):
        pytest.skip(f"the reference ships no protostr golden for {fn}")
    golden = po.load_golden(_protostr_name(fn))
    rl = po.ref_layers(golden)
    conf = _parse(fn)
    ours = conf.model_config.layers

    if fn in PROTOSTR_REDESIGNED:
        # weak invariant: same data layers, same output count, one of
        # our fused recurrent layers per reference recurrent group
        ref_data = {(e["name"], e["size"]) for e in rl
                    if e["type"] == "data"}
        our_data = {(e["name"], e["size"]) for e in ours
                    if e["type"] == "data"}
        assert ref_data == our_data, PROTOSTR_REDESIGNED[fn]
        n_ref_groups = sum(e["type"] in _REF_RECURRENCE_TYPES for e in rl)
        n_our_sites = sum(e["type"] in _OUR_RECURRENCE_TYPES for e in ours)
        assert n_our_sites == n_ref_groups, (
            f"{fn}: {n_ref_groups} reference recurrent groups vs "
            f"{n_our_sites} fused recurrence sites — "
            + PROTOSTR_REDESIGNED[fn])
        assert len(po.ref_outputs(golden)) == \
            len(conf.model_config.output_layer_names)
        return

    it = po.Interner()
    rcanon = po.canonicalize(rl, it, type_map=po.REF_TYPE_MAP,
                             drop_inputs=po.REF_DROP_INPUTS)
    ocanon = po.canonicalize(ours, it, type_map=po.OUR_TYPE_MAP,
                             drop_inputs=po.OUR_DROP_INPUTS,
                             splice_types=po.OUR_SPLICE_TYPES)
    spliced = {e["name"] for e in ours
               if e["type"] in po.OUR_SPLICE_TYPES}
    ocanon = {n: c for n, c in ocanon.items() if n not in spliced}

    r_out = collections.Counter(rcanon[n] for n in po.ref_outputs(golden))
    o_out = collections.Counter(
        ocanon[n] for n in conf.model_config.output_layer_names)
    assert r_out == o_out, f"{fn}: output layers diverge from protostr"

    r_all = collections.Counter(rcanon.values())
    o_all = collections.Counter(ocanon.values())
    if r_all != o_all:
        by_ref = {e["name"]: e for e in rl}
        by_our = {e["name"]: e for e in ours}

        def describe(names, by):
            return [
                {k: by[n].get(k) for k in
                 ("name", "type", "size", "active_type", "inputs")}
                for n in names]

        extra_ref = [n for n, c in rcanon.items() if c in (r_all - o_all)]
        extra_our = [n for n, c in ocanon.items() if c in (o_all - r_all)]
        pytest.fail(
            f"{fn}: layer graph diverges from the reference protostr.\n"
            f"reference-only: {describe(extra_ref, by_ref)}\n"
            f"ours-only: {describe(extra_our, by_our)}")


@pytest.mark.parametrize("fn", [f for f in _configs() if f not in PARSE_ONLY])
def test_config_runs_forward(fn):
    _run_config(fn)


def test_capture_is_order_independent():
    """The structural capture must be identical whether a config parses
    first or after hundreds of other tests have advanced the process-
    global auto-naming counters (the round-3 corpus failed 43 configs
    only in full-suite order because `v2_conv_237`-style names leaked
    into the goldens)."""
    import paddle_tpu.v2.layer as v2_layer

    fn = "img_layers.py"
    first = _structure(_parse(fn))
    # pollute every global the capture could leak: the v2 uname counter
    # and the default programs' name generator
    v2_layer._counter[0] = 9731
    import paddle_tpu as fluid

    for _ in range(7):
        fluid.layers.data(name=f"pollute_{v2_layer._counter[0]}",
                          shape=[3], dtype="float32")
        v2_layer._uname("pollute")
    second = _structure(_parse(fn))
    assert first == second
