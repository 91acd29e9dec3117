"""The ``glm-5`` generate configuration's programs compiled at their real
sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _assert_step_outputs, _kernel_op_names, one_chip, _planned_bytes,
    _pool_sized_strays, _under)


# -- sparse latent attention (PR 53) ------------------------------------------

# memory_analysis() of the programs at the configuration's 4,757 pages.
# perf/configs/glm-5.json records PR 53's (the bucket 14,486,642,176, the
# chunk 14,999,422,464: what set ``num_pages``); since PR 54 the
# selection's int32 keys and masks live in VMEM and both plan less; since
# PR 56 the step fetches no rows (12,539,195,392 with the fetch)
GLM_PLANS = {"decode": 12_521_984_512, 8192: 14_350_835_712,
             "chunk over 25600": 14_998_438_912}
GLM_PARAMS = 3_909_632_768


def _glm_cell(one_chip, monkeypatch):
    """The ``glm-5`` generate configuration at its real sizes, as shapes
    on the described chip, built as its gen_config builds the model:
    (cfg, params, latent pool, index pool, block, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import glm_dsa as gd

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "glm-5.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    block = gd.GlmDsaBlock(
        nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        held=(0, cfg["n_routed_experts"]),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_rope=cfg["qk_rope_head_dim"], index_topk=cfg["index_topk"])
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            gd.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layers=L, first_dense=cfg["leading_dense_layers"], dtype=dtype,
            d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            nope=block.nope, rope_dim=block.rope_dim, v_dim=block.v_dim,
            rank=block.rank, q_rank=cfg["q_lora_rank"],
            index_heads=block.index_heads, index_dim=block.index_dim,
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=(cfg["n_shared_experts"]
                          * cfg["moe_intermediate_size"]),
            router_width=cfg["n_routed_experts_published"],
            held=cfg["n_routed_experts"])))
    assert block.width == g["row_lanes_stored"] == 640
    assert block.index_dim == g["index_row_lanes"] == 128
    pages = (L, g["num_pages"], g["page_size"])
    return (cfg, params, sds(pages + (block.width,), dtype),
            sds(pages + (block.index_dim,), dtype), block, sds)


def _glm_pool_sizes(pool, index_pool):
    return {math.prod(pool.shape): "latent",
            math.prod(pool.shape[1:]): "latent slab",
            math.prod(index_pool.shape): "index",
            math.prod(index_pool.shape[1:]): "index slab"}


def test_glm_decode_step_walks_the_live_pages_under_the_selected_sets(
        one_chip, monkeypatch):
    """The decode step of the ``glm-5`` configuration at its real sizes
    (1 dense + 4 routed layers, 64 heads, 4,757 pages of 128 rows x (640
    + 128) lanes, 32 slots of 200 table columns): a layer walks the
    slots' live latent pages (``latent_paged_attention``) either way;
    where a slot is over 2,048 rows it first scores the slots' index
    rows (ONE ``paged_index_scores`` call), makes the 2,048 best a slot
    a bias from one read of the 32 x 25,600 scores (``selection_bias``,
    one grid step) and walks under it (``attn_sparse``): no ``top_k``,
    no sort, no gather under the mixer, nothing of the 32 x 2,048
    fetched rows' size; both pools aliased input to output, nothing of a
    pool's or a slab's size copied; 3,909,632,768 parameters; a plan of
    the arguments + 24 MB."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.observability import metrics

    cfg, params, pool, index_pool, block, sds = _glm_cell(
        one_chip, monkeypatch)
    g, L, S = cfg["generate"], cfg["num_hidden_layers"], 32
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == GLM_PARAMS
    per_layer = [sum(math.prod(a.shape) for a in jax.tree.leaves(lp))
                 for lp in params["layers"]]
    assert per_layer[:2] == [400_898_816, 817_708_032]
    count = metrics.REGISTRY.get("pallas_dispatch_total").value
    kernels = ("paged_index_scores", "selection_bias",
               "latent_paged_attention")
    before = [count(kernel=k, path="compiled") for k in kernels]
    compiled = dm._decode_step.lower(
        params, pool, index_pool, sds((S, g["pages_per_seq"]), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block).compile()
    assert [count(kernel=k, path="compiled") - b
            for k, b in zip(kernels, before)] == [L, L, 2 * L]
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    m = compiled.memory_analysis()
    pools = (math.prod(pool.shape) + math.prod(index_pool.shape)) * 2
    assert pools == 4757 * 983_040
    assert m.alias_size_in_bytes >= pools
    assert _planned_bytes(compiled) == GLM_PLANS["decode"]
    text = compiled.as_text()
    fetched = {S * cfg["index_topk"] * block.width: "fetched rows"}
    assert not _pool_sized_strays(
        text, {**_glm_pool_sizes(pool, index_pool), **fetched})
    mixer = [line for line in text.splitlines() if "/attn_latent/" in line]
    assert mixer and not [
        line for line in mixer
        if re.search(r"\b(sort|gather|topk)\(|top_k|TopK", line)]
    ops = [op for op in _kernel_op_names(text) if "grouped_gemm" not in op]
    under = "_decode_step)/blk_mixer/attn_latent/cond/"
    for scope, kernel in (("attn_index", "paged_index_scores"),
                          ("attn_index_select", "selection_bias"),
                          ("attn_sparse", "latent_paged_attention")):
        assert sum(under in op and f"/{scope}/" in op and kernel in op
                   for op in ops) == L, scope
    assert sum(under in op and "attn_sparse" not in op
               and "latent_paged_attention" in op for op in ops) == L
    assert len(ops) == 4 * L
    for scope in ("attn_latent/attn_latent_down", "attn_latent/attn_index",
                  "moe_shared", "moe_router"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope
    # 32 rows send a held expert one row in the mean: the grouped path
    assert "jit(_decode_step)/blk_mlp/while/body/moe_experts/" in text
    # the absorbed products stay where the read's seconds leave them out
    assert re.search(
        r"attn_latent/cond/\w+/attn_sparse/attn_latent_absorb/", text)


@pytest.mark.parametrize("program", [8192, "chunk over 25600"])
def test_glm_prefill_programs_fit_beside_weights_and_both_pools(
        one_chip, monkeypatch, program):
    """The 8,192-row top bucket, and a 4,096-row chunk over a sequence's
    whole 25,600 rows (the largest program any request can run: its plan
    set ``num_pages``, the most pages that leave it at or under 15.0
    GB): ``index_scores``, ``selection_bias`` and
    ``selected_flash_attention`` once a layer, under ``attn_index``,
    ``attn_index_select`` and ``attn_sparse``, and no loop of XLA's under
    the selection's scope (the bisection runs inside the kernel); no
    causal flash call (every row past the 2,048th selects); both pools
    aliased and nothing of their size copied; the experts keep the
    grouped GEMM."""
    import functools

    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import glm_dsa as gd
    from paddle_tpu.observability import metrics

    cfg, params, pool, index_pool, block, sds = _glm_cell(
        one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"]
    engaged = functools.partial(
        metrics.REGISTRY.get("pallas_dispatch_total").value,
        kernel="selection_bias", path="compiled")
    before = engaged()
    if program == 8192:
        name = "_prefill_bucket"
        compiled = dm._prefill_bucket.lower(
            params, pool, index_pool, sds((8192,), jnp.int32),
            sds((8192,), jnp.int32), sds((), jnp.int32), heads=heads,
            block=block).compile()
    else:
        name = "_prefill_bucket_chunk"
        compiled = gd._prefill_bucket_chunk.lower(
            params, pool, index_pool, sds((g["pages_per_seq"],), jnp.int32),
            sds((), jnp.int32), sds((g["chunk_rows"],), jnp.int32),
            sds((), jnp.int32), heads=heads, page_size=g["page_size"],
            block=block, extent=g["pages_per_seq"]).compile()
    assert engaged() - before == L
    m = compiled.memory_analysis()
    pools = (math.prod(pool.shape) + math.prod(index_pool.shape)) * 2
    assert m.alias_size_in_bytes >= pools
    planned = _planned_bytes(compiled)
    assert planned == GLM_PLANS[program], planned
    page_bytes = L * g["page_size"] * (block.width + block.index_dim) * 2
    # not a page more by the plan the configuration records (PR 53's:
    # the file is the benchmark's); today's largest is 983,552 B under it
    assert max(GLM_PLANS.values()) <= g["planned_bytes"] <= 15.0e9 \
        < g["planned_bytes"] + page_bytes
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _glm_pool_sizes(pool, index_pool))
    ops = _kernel_op_names(text)
    under = f"{name})/blk_mixer/attn_latent/"
    assert sum(under + "attn_index/" in op and "index_scores" in op
               for op in ops) == L
    assert sum(under + "attn_index_select/" in op
               and "selection_bias" in op for op in ops) == L
    assert sum(under + "attn_sparse/" in op
               and "selected_flash_attention" in op for op in ops) == L
    assert not [op for op in ops if "flash_attention_fwd" in op
                or "latent_paged_attention" in op]
    assert f"{under}attn_index_select/while" not in text
    # thousands of rows: the experts keep the grouped GEMM
    gemm = [op for op in ops if "grouped_gemm" in op]
    assert len(gemm) == 2 * (L - 1) and all(
        f"{name})/blk_mlp/while/body/moe_experts/" in op for op in gemm)
    assert "ragged-dot" not in text
