"""The ``olmoe-1b-7b`` generate configuration's programs compiled at their
real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import os

import pytest

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _assert_experts_read_where_they_lie, _assert_grouped_gemm_kernel,
    _assert_pools_in_place, _assert_step_outputs, _kernel_grids,
    _kernel_op_names, one_chip, _planned_bytes, _under)


# the decode steps' plans at the parent of PR 27, whose steps were not
# donated and held a second copy of both pools (PERF.md section 4,
# ``perf/scratch_compile.py decode`` / ``scratch_compile_paged.py``)
OLMOE_STEP_PLAN_UNDONATED = 14_397_756_928
OLMOE_CHUNK_PLAN_UNDONATED = 14.42e9


def _olmoe_cell(one_chip, monkeypatch):
    """The ``olmoe-1b-7b`` generate configuration at its real sizes, as
    shapes on the described chip: (cfg, params, pool, pool shape, block,
    sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import olmoe

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    g = cfg["generate"]
    d, H, L = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_hidden_layers"])
    dtype = jnp.dtype(g["dtype"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            olmoe.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, layers=L, experts=cfg["num_experts"],
            expert_width=cfg["intermediate_size"], dtype=dtype)))
    shape = (L, g["num_pages"], g["page_size"], H, d // H)
    block = olmoe.OlmoeBlock(top_k=cfg["num_experts_per_tok"])
    return cfg, params, sds(shape, dtype), shape, block, sds


def test_olmoe_decode_step_compiles_with_bf16_pages(one_chip, monkeypatch):
    """The decode step of the ``olmoe-1b-7b`` generate configuration at
    its real sizes (8 layers, 64 experts of 1,024, bf16 weights and
    1,537 pages of 32 bf16 rows, 32 slots): the rpa kernel takes bf16
    pages at (32, 16, 128) and walks a slot's live pages, one grid step
    a slot (PR 60: ``walk_fits`` takes the pages; the plan is the
    ``(S, P)`` grid's to the byte, the double buffers live in VMEM);
    the step's 32 rows take the dense pass, so
    the experts ARE 64 masked dense matmuls a projection, batched into
    one: at four rows an expert that reads the same bytes faster than
    the chip's grouped-matmul kernel (``models/moe.py:expert_path``; a
    prefill bucket over its threshold keeps ``jax.lax.ragged_dot``),
    and no expert matrix is transposed or copied on its way; both
    donated pools are aliased and written and read in place, and the
    plan fits the chip.  The plan is pinned here as a literal: the
    configuration's ``planned_bytes`` is the undonated step's (PR 26)
    and is a benchmark file, which PR 27 could not edit."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    S, P = 32, g["pages_per_seq"]
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, P), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), heads=shape[3], page_size=g["page_size"],
        block=block).compile()
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    planned = _planned_bytes(compiled)
    assert planned == 10_367_236_608 < 15.75e9, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2,
        OLMOE_STEP_PLAN_UNDONATED)
    ops = _kernel_op_names(text)
    assert len(ops) == L and all(
        "_decode_step" in op and "ragged_paged_attention/" in op
        for op in ops)
    assert [grid for _, grid in _kernel_grids(text)] == [(S,)] * L
    _assert_experts_read_where_they_lie(
        text, cfg["num_experts"], cfg["hidden_size"],
        cfg["intermediate_size"])
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


@pytest.mark.parametrize("bucket", [256, 2048])
def test_olmoe_prefill_bucket_takes_the_path_of_its_rows(
        one_chip, monkeypatch, bucket):
    """The expert layers of the ``olmoe-1b-7b`` cell's prefill programs
    follow ``models/moe.py:expert_path``: the 2,048-row bucket (the
    cell's longest) runs the grouped-GEMM kernel twice a layer (gate
    and up in one call, down) under ``moe_experts`` and holds no
    ``ragged-dot``, a 256-row bucket streams the experts as the decode
    step does and holds neither; both fit the chip."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import moe

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    L = cfg["num_hidden_layers"]
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        sds((bucket,), jnp.int32), sds((), jnp.int32), heads=shape[3],
        block=block).compile()
    assert _planned_bytes(compiled) < 15.75e9
    text = compiled.as_text()
    k, E = cfg["num_experts_per_tok"], cfg["num_experts"]
    if moe.expert_path(bucket, k, E) == "grouped":
        _assert_grouped_gemm_kernel(text, L, looped=False)
    else:
        assert "grouped_gemm" not in text
        _assert_experts_read_where_they_lie(
            text, cfg["num_experts"], cfg["hidden_size"],
            cfg["intermediate_size"])
    assert {moe.expert_path(b, k, E) for b in (256, 2048)} == {
        "dense", "grouped"}


def test_olmoe_suffix_prefill_writes_and_reads_its_pools_in_place(
        one_chip, monkeypatch):
    """The suffix prefill over cached pages (a 136-row chunk at the
    ``olmoe-1b-7b`` cell's sizes, which planned 14.42 GB undonated):
    the same in-place writes and whole-pool reads through the chunked
    kernel, two pools fewer bytes."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    compiled = dm._prefill_chunk.lower(
        params, pool, pool, sds((g["pages_per_seq"],), jnp.int32),
        sds((), jnp.int32), sds((136,), jnp.int32), heads=shape[3],
        page_size=g["page_size"], block=block).compile()
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2,
        OLMOE_CHUNK_PLAN_UNDONATED)
    chunk = [op for op in _kernel_op_names(text)
             if "ragged_paged_attention_chunk" in op]
    assert len(chunk) == L and all("_prefill_chunk" in op for op in chunk)
