"""The ``k-exaone-236b-a23b`` generate configuration's programs compiled at
their real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _assert_experts_read_where_they_lie, _assert_grouped_gemm_kernel,
    _assert_pools_in_place, _assert_step_outputs, _kernel_grids,
    _kernel_op_names, one_chip, _planned_bytes, _ring_dispatches, _under,
    _walk_dispatches, _walks_took)


def _exaone_cell(one_chip, monkeypatch):
    """The ``k-exaone-236b-a23b`` generate configuration at its real
    sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, pool, pool shape, block, table
    width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import exaone_moe as ex

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"][:L])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            ex.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            router_width=cfg["num_experts_published"],
            held=cfg["num_experts"],
            moe_layers=tuple(t == "sparse"
                             for t in cfg["mlp_layer_types"][:L]),
            dtype=dtype)))
    ring = cfg["sliding_window"] // g["page_size"] + 1
    block = ex.ExaoneMoeBlock(
        layer_types=types, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"], held=(0, cfg["num_experts"]),
        full_pages=g["pages_per_seq"], ring_pages=ring)
    width = g["pages_per_seq"] + ring * sum(t == ex.SLIDING for t in types)
    shape = (1, g["num_pages"], g["page_size"],
             cfg["num_key_value_heads"], cfg["head_dim"])
    assert g["num_pages"] == g["slots"] * width + 1
    return cfg, params, sds(shape, dtype), shape, block, width, sds


def test_exaone_decode_step_reads_both_caches_in_place(one_chip,
                                                       monkeypatch):
    """The decode step of the ``k-exaone-236b-a23b`` configuration at
    its real sizes (layer 0 + 6, 16 held experts of 2,048 beside a
    shared one, 64 heads on 8, 3,073 bf16 pages of 128 rows, 64 slots):
    ONE grouped-heads kernel (the full layer's; the six rings are plain
    XLA) and no other custom call: the step's 64 rows go through the
    16 held experts of a routed layer as batched matmuls that read
    each matrix once where it lies (``models/moe.py:expert_path``), 14
    in-place scatters into the two donated pools and nothing else of a
    pool's size (no slab, no reshaped copy), and a plan of weights +
    pools + 20 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, width, sds = _exaone_cell(
        one_chip, monkeypatch)
    g, L, S = cfg["generate"], cfg["num_hidden_layers"], 64
    walks = _walk_dispatches()
    before = _ring_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block).compile()
    _walks_took(walks, compiled_stored=1)
    # the six sliding layers' row-major rings stay on the gathered form
    after = _ring_dispatches()
    assert {p: after[p] - before[p] for p in after} == {
        "compiled": 0, "interpret": 0, "reference": 6}
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    planned = _planned_bytes(compiled)
    assert planned == 12_078_473_216 < 15.75e9, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2, float("inf"),
        scatters=2 * L)
    gqa = _kernel_op_names(text)
    assert len(gqa) == 1 and "_decode_step)/blk_mixer/attn_full/" in gqa[0]
    assert "ragged_paged_attention_gqa" in gqa[0]
    # a slot a grid step: the walk of its live pages, not 36 columns
    assert _kernel_grids(text) == [(gqa[0], (S,))]
    _assert_experts_read_where_they_lie(
        text, cfg["num_experts"], cfg["hidden_size"],
        cfg["moe_intermediate_size"])
    for scope in ("attn_window", "moe_shared", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


@pytest.mark.parametrize("bucket, plan, parents_plan", [
    (4096, 13_127_315_456, 13_979_091_456),
    (4608, 13_259_582_464, 14_382_459_904)])
def test_exaone_top_prefill_fits_beside_the_weights(one_chip, monkeypatch,
                                                    bucket, plan,
                                                    parents_plan):
    """The 4,096-row prefill bucket (the longest the cell's traffic
    sends) and the 4,608-row one (a sequence's capacity): the plan fits
    the chip beside 10.45 GB of weights and 1.61 GB of pools, both
    pools are aliased, the full layer runs the flash kernel on 64
    repeated heads and the six sliding layers run banded in plain XLA
    (no T x T scores: they would be 4.3 GB a layer).  The routed layers
    of a chip that holds 16 of 128 experts run their grouped GEMMs over
    blocks of 2 x bucket sorted assignments (``moe.grouped_block_rows``)
    and hold nothing of 8 x bucket rows by the model's width, which is
    why the plans lie under the ones of PR 37 (``parents_plan``, the
    4,608-row one the configuration's ``planned_bytes``).  The GEMMs are
    the grouped-GEMM kernel inside the loop over blocks (PR 47; with
    ``ragged_dot`` the plans read 12,948,948,480 and 13,052,315,136)."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import moe

    cfg, params, pool, shape, block, width, sds = _exaone_cell(
        one_chip, monkeypatch)
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        sds((L, bucket), jnp.int32), sds((), jnp.int32),
        heads=cfg["num_attention_heads"], block=block).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * math.prod(shape) * 2
    planned = _planned_bytes(compiled)
    assert planned == plan <= parents_plan < 15.75e9, planned
    text = compiled.as_text()
    assert moe.grouped_block_rows(
        bucket, k, cfg["num_experts"], cfg["num_experts_published"]) \
        == 2 * bucket
    assert re.search(rf"\[{2 * bucket},{cfg['hidden_size']}\]", text)
    assert not re.search(rf"\[{k * bucket},{cfg['hidden_size']}\]", text)
    ops = _kernel_op_names(text)
    flash = [op for op in ops if "grouped_gemm" not in op]
    assert len(flash) == 1
    assert "_prefill_bucket)/blk_mixer/attn_full/" in flash[0]
    assert "flash_attention_fwd" in flash[0]
    # thousands of rows: the experts keep the grouped GEMM
    _assert_grouped_gemm_kernel(text, L - 1, looped=True)
