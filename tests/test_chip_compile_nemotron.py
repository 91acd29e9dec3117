"""The ``nemotron-3-nano-30b-a3b`` generate configuration's programs
compiled at their real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes): the
32-slot decode step and the 8,192-row top bucket with their plans
pinned, and the probes that chose how an expert's 1,856 columns are
stored and how ``ssd_step``'s block of rows of heads lies against the
groups of B and C.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _compiled_text, _kernel_grids, _kernel_op_names, one_chip,
    _planned_bytes, _under, _walk_dispatches, _walks_took)

PUBLISHED = 5_258_420_544         # the share's parameters as published
STORED = 5_385_036_096            # with an expert's lanes padded to 1,920
# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 1,423 pages
NEMOTRON_PLANS = {"decode": 13_555_410_944, 8192: 14_999_844_352,
                  # the form that was NOT kept: experts stored at the
                  # published 1,856 columns (0.25 GB fewer weights, and
                  # 46 ragged-dots where the kernel's calls were)
                  "8192 at 1,856 columns": 14_674_407_936}


def _nemotron_cell(one_chip, monkeypatch, stored=True):
    """The configuration at its real sizes, as shapes on the described
    chip, built as its gen_config builds the model: (cfg, params, (K
    pool, V pool), (state pool, tail pool), block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import nemotron_h as nh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    g = cfg["generate"]
    assert cfg["reduced"] == ["n_routed_experts", "vocab_size"]
    assert cfg["num_hidden_layers"] == len(cfg["hybrid_override_pattern"]) \
        == 52
    types = nh.layer_kinds(cfg["hybrid_override_pattern"])
    assert types == tuple(cfg["layer_types"])
    dtype = jnp.dtype(g["dtype"])
    H, P, N, G = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                  cfg["ssm_state_size"], cfg["n_groups"])
    KV, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    assert (cfg["hidden_size"], H, P, N, G, KV, dh) == (
        2688, 64, 64, 128, 8, 2, 128)
    held = cfg["n_routed_experts"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            nh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layer_types=types, d=cfg["hidden_size"],
            heads=cfg["num_attention_heads"], kv_heads=KV, head_dim=dh,
            mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N,
            mamba_n_groups=G, conv=cfg["conv_kernel"],
            width=cfg["moe_intermediate_size"],
            shared_width=cfg["moe_shared_expert_intermediate_size"],
            router_width=cfg["n_routed_experts_published"], held=held,
            dtype=dtype)))
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == STORED
    if not stored:          # an expert's matrices at the published 1,856
        d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
        for lp in params["layers"]:
            if "w_up" in lp:
                lp.update(w_up=sds((held, d, f), dtype),
                          w_down=sds((held, f, d), dtype))
        assert sum(math.prod(a.shape)
                   for a in jax.tree.leaves(params)) == PUBLISHED
    block = nh.NemotronHBlock(
        layer_types=types, kv_heads=KV, head_dim=dh, pack=1, state_pack=2,
        mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N, mamba_n_groups=G,
        eps=cfg["layer_norm_epsilon"], attention_multiplier=dh ** -0.5,
        full_pages=g["pages_per_seq"], top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        experts=cfg["n_routed_experts_published"],
        held=(cfg["ep_rank"] * held, held))
    mamba = types.count(nh.MAMBA)
    full = types.count(nh.ATTENTION)
    assert (mamba, full, types.count(nh.EXPERTS)) == (23, 6, 23)
    E = g["state_entries"]
    assert E == g["slots"] + 1
    pools = (sds((full, g["num_pages"], g["page_size"], KV, dh), dtype),) * 2
    tail = tail_shape(cfg["conv_kernel"], H * P + 2 * G * N)
    assert tail == (144, 128)
    extra = (sds((mamba, E, H // 2, N, 2 * P), jnp.float32),
             sds((mamba, E) + tail, dtype))
    entry = sum(math.prod(a.shape[2:]) * a.dtype.itemsize * mamba
                for a in extra)
    assert entry == 49_082_368
    return cfg, params, pools, extra, block, g["pages_per_seq"] + 1, sds


def _lower_bucket(cfg, params, pools, extra, block, sds, bucket):
    from paddle_tpu.decode import model as dm

    return dm._prefill_bucket.lower(
        params, *pools, sds((bucket,), jnp.int32),
        (sds((bucket,), jnp.int32), sds((), jnp.int32)), sds((), jnp.int32),
        heads=cfg["num_attention_heads"], block=block, extra=extra)


def test_nemotron_decode_step_at_32_slots(one_chip, monkeypatch):
    """All 52 layers in one step: ONE ``ssd_step`` and ONE ``conv_step``
    call a Mamba-2 layer over the entries where they lie, both pools
    aliased, a slot a grid step of the grouped walk in the six attention
    layers (16 query heads a K/V head on pages consumed as stored), and
    the experts by the grouped-GEMM kernel: 32 slots x 6 of 128 is 1.5
    rows an expert, so ``expert_path`` takes the grouped way, and its
    192 sorted rows run as ONE block of 256, two whole row tiles
    (``grouped_block_rows``; at 192 the block was no block the kernel
    takes, the step's 46 grouped GEMMs were ``jax.lax.ragged_dot`` and
    the step planned 13,543,929,344 B and read 54.5 ms on the chip, 42 of
    them under ``moe_experts``: PERF.md section 6, PR 64)."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import moe

    cfg, params, pools, extra, block, width, sds = _nemotron_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    assert moe.expert_path(S, block.top_k, block.experts) == "grouped"
    assert moe.grouped_block_rows(S, block.top_k, 16, block.experts) == 256
    before = _walk_dispatches()
    compiled = dm._decode_step.lower(
        params, *pools, sds((S, width), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), heads=cfg["num_attention_heads"],
        page_size=g["page_size"], block=block, extra=extra).compile()
    _walks_took(before, compiled_stored=6)
    planned = _planned_bytes(compiled)
    assert planned == NEMOTRON_PLANS["decode"] < 15.0e9, planned
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in pools + extra)
    assert m.alias_size_in_bytes >= buffers
    text = compiled.as_text()
    kernels = _kernel_op_names(text)
    step = [op for op in kernels if "ssd_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    gemms = [op for op in kernels if "grouped_gemm" in op]
    assert (len(step), len(conv), len(gqa), len(gemms)) == (23, 23, 6, 46)
    assert len(kernels) == 98 and "ragged-dot" not in text
    assert sum("grouped_gemm_up" in op for op in gemms) == 23
    assert all("_decode_step)/blk_mlp/" in op
               and "/while/body/moe_experts/" in op for op in gemms)
    assert all("_decode_step)/blk_mixer/ssm/ssm_state/" in op for op in step)
    assert all("_decode_step)/blk_mixer/ssm/ssm_conv/" in op for op in conv)
    assert all("_decode_step)/blk_mixer/attn_full/" in op for op in gqa)
    # (the grouped GEMM's grid has a dynamic extent: the visits)
    grids = dict(_kernel_grids("\n".join(
        ln for ln in text.splitlines() if "grouped_gemm" not in ln)))
    # 32 rows of heads in blocks of 16: four whole groups a grid step
    assert {grids[op] for op in step} == {(S, 2)}
    assert {grids[op] for op in gqa} == {(S,)}
    # the in- and out-projections under the scope ``ssm`` leaves out
    assert f"jit(_decode_step)/{_under('ssm_proj')}/" in text
    # an expert layer is a feed-forward alone, a mixer a mixer alone
    for scope in ("moe_shared", "moe_router", "moe_dispatch"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope
    assert "blk_mixer/moe_" not in text and "blk_mlp/ssm" not in text


def test_nemotron_top_bucket_fits_beside_weights_states_and_pages(
        one_chip, monkeypatch):
    """The 8,192-row bucket (the traffic's 5,000- and 7,500-row prompts
    run in it): the plan is the configuration's ``planned_bytes``, at
    or under 15.0 GB at 1,423 pages and over it at one page more; two
    grouped-GEMM kernel calls a routed layer (``up`` with the relu^2
    epilogue, then down) and no ragged-dot; the flash kernel in the six
    attention layers."""
    cfg, params, pools, extra, block, width, sds = _nemotron_cell(
        one_chip, monkeypatch)
    compiled = _lower_bucket(cfg, params, pools, extra, block, sds,
                             8192).compile()
    planned = _planned_bytes(compiled)
    g = cfg["generate"]
    assert planned == NEMOTRON_PLANS[8192] == g["planned_bytes"], planned
    page = sum(math.prod(a.shape[2:]) * a.dtype.itemsize * a.shape[0]
               for a in pools)
    assert page == 786_432 and planned <= 15.0e9 < planned + page
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        math.prod(a.shape) * a.dtype.itemsize for a in pools + extra)
    text = compiled.as_text()
    ops = _kernel_op_names(text)
    gemms = [op for op in ops if "grouped_gemm" in op]
    assert len(gemms) == 46 and len(ops) == 52
    assert sum("grouped_gemm_up" in op for op in gemms) == 23
    assert all("_prefill_bucket)/blk_mlp/" in op
               and "/while/body/moe_experts/" in op for op in gemms)
    flash = [op for op in ops if "flash_attention_fwd" in op]
    assert len(flash) == 6 and all(
        "_prefill_bucket)/blk_mixer/attn_full/" in op for op in flash)
    assert "ragged-dot" not in text
    for scope in ("ssm/ssm_scan", "ssm/ssm_conv", "ssm_proj"):
        assert f"jit(_prefill_bucket)/{_under(scope)}/" in text, scope


def test_nemotron_experts_stored_as_published_fall_to_ragged_dot(
        one_chip, monkeypatch):
    """The form that was NOT kept: ``W_up`` (2,688, 1,856).  1,856
    columns are 14.5 tiles of lanes, ``pallas/grouped_gemm.py:fits``
    refuses them, and all 46 grouped GEMMs of the bucket are
    ragged-dots (PR 47: 2 to 4 times their bytes' time); at 1,920
    stored columns (64 zero columns, exact: ``relu(0)^2 = 0``) the
    kernel takes them (the case above) for 0.25 GB of weights more."""
    from paddle_tpu.pallas import grouped_gemm as gg

    bf16 = jnp.bfloat16
    assert not gg.fits(bf16, bf16, 8192, 2688, 1856)
    assert gg.fits(bf16, bf16, 8192, 2688, 1920)
    assert gg.fits(bf16, bf16, 8192, 1920, 2688)
    assert gg.col_tile(gg.ROW_TILE, 2688, 1920, 2, 1) == 1920
    assert gg.col_chunk(1920) == 384 and gg.col_chunk(2688) == 384
    cfg, params, pools, extra, block, width, sds = _nemotron_cell(
        one_chip, monkeypatch, stored=False)
    compiled = _lower_bucket(cfg, params, pools, extra, block, sds,
                             8192).compile()
    assert _planned_bytes(compiled) == NEMOTRON_PLANS["8192 at 1,856 columns"]
    text = compiled.as_text()
    ops = _kernel_op_names(text)
    assert not [op for op in ops if "grouped_gemm" in op]
    assert sum(op.endswith("ragged-dot-none") for op in ops) == 46


@pytest.mark.parametrize("groups, grid", [(8, (32, 2)), (1, (32, 2))])
def test_ssd_step_blocks_of_16_rows_of_heads_against_the_groups(
        one_chip, groups, grid):
    """The kernel alone at the published entry (32 rows of two heads,
    state 128): ``head_block`` takes 16 rows a grid step (1 MB of the
    1.5 MB a block may hold), which is FOUR whole groups of 4 rows at 8
    groups: each group's B and C are turned to columns once a grid
    step, inside the block, and the block's groups come as one (32,
    128) tile.  A block a group (4 rows) would be 8 grid steps a slot
    and 256 KB a copy where Granite's sweep (PR 41) took the largest
    block; a block that straddles a group is refused by ``fits``."""
    from paddle_tpu.pallas import ssd_step as ssd

    S, E, R, N, lanes = 32, 33, 32, 128, 128
    assert ssd.head_block(R, N, lanes) == 16
    assert ssd.fits(jnp.float32, R, N, lanes, groups)
    bc = (S, N) if groups == 1 else (S, groups, N)
    text = _compiled_text(
        ssd.ssd_step, one_chip, ((E, R, N, lanes), jnp.float32),
        ((S,), jnp.int32), ((S, R, lanes), jnp.float32),
        ((S, R, lanes), jnp.float32), (bc, jnp.float32), (bc, jnp.float32))
    assert [g for _, g in _kernel_grids(text)] == [grid]
