"""The ``ling-3.0-flash`` generate configuration's programs compiled at
their real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import math
import os
import re

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _assert_grouped_gemm_kernel, _kernel_op_names, one_chip,
    _planned_bytes, _pool_sized_strays)


# -- Ling-3.0-flash: KDA entries beside a latent page run ---------------------


def _ling_cell(one_chip, monkeypatch):
    """The ``ling-3.0-flash`` generate configuration at its real sizes,
    as shapes on the described chip, built as its gen_config builds the
    model: (cfg, params, the latent pool, the placeholder, (state_pool,
    conv_pool), block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import ling_hybrid as lh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "ling-3.0-flash.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])
    types = lh.layer_types_of(L, cfg["layer_group_size"])
    assert list(types) == cfg["layer_types"]
    H, Hl = cfg["num_attention_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            lh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layer_types=types, first_dense=cfg["first_k_dense_replace"],
            dtype=dtype, d=cfg["hidden_size"], heads=H,
            nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], lin_heads=Hl,
            d_k=dk, d_v=dv, conv=cfg["short_conv_kernel_size"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=cfg["moe_shared_expert_intermediate_size"],
            router_width=cfg["num_experts_published"],
            held=cfg["num_experts"])))
    # the parameters the configuration's file states, recounted
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == 3_639_533_344
    block = lh.LingHybridBlock(
        layer_types=types,
        latent=lh.LingLatentBlock(
            nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
            eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"])),
        lin_heads=Hl, d_k=dk, d_v=dv,
        lower_bound=float(cfg["kda_lower_bound"]), eps=cfg["rms_norm_eps"],
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"], held=(0, cfg["num_experts"]),
        groups=(cfg["n_group"], cfg["topk_group"]),
        full_pages=g["pages_per_seq"])
    assert block.latent.width == g["row_lanes_stored"] == 640
    pool = sds((1, g["num_pages"], g["page_size"], 640), dtype)
    E = g["state_entries"]
    tail = tail_shape(cfg["short_conv_kernel_size"], Hl * (2 * dk + dv))
    assert tail == (288, 128)
    extra = (sds((L - 1, E, Hl, dv, 128), jnp.float32),
             sds((L - 1, E, *tail), dtype))
    return (cfg, params, pool, sds((1, 1), dtype), extra, block,
            g["pages_per_seq"] + 1, sds)


def _ling_sizes(pool, extra):
    return {math.prod(pool.shape): "latent",
            math.prod(extra[0].shape): "state",
            math.prod(extra[1].shape): "conv"}


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 8,193 pages
LING_PLANS = {"decode": 10_057_012_224, 8192: 11_521_017_856}


def test_ling_decode_step_moves_states_and_latent_rows_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``ling-3.0-flash`` configuration at its
    real sizes (5 KDA layers + 1 latent layer, 8,193 bf16 pages of 128
    latent rows at 640 lanes, 129 state entries, 128 slots): the latent
    pool, the placeholder and both entry pools are aliased input to
    output; the latent layer runs ``latent_paged_attention`` under
    ``attn_latent``; every KDA layer advances the slots' states by ONE
    ``kda_step`` call under ``lin_attn/lin_attn_state`` after ONE
    ``conv_step`` call under ``lin_attn/lin_attn_conv``, each pool its
    kernel's in-place operand, no loop over the slots; the gate stands
    under ``lin_attn_gate`` and the router's group step under
    ``moe_dispatch/moe_group``; nothing else has the state pool's or the
    latent pool's size; the plan is arguments + 15 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, placeholder, extra, block, width, sds = _ling_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    compiled = dm._decode_step.lower(
        params, pool, placeholder, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool,) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LING_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    # no copy of the 1.35 GB state pool nor of the 1.34 GB latent pool;
    # the 47.5 MB tail pool the compiler moves into its fast memory
    # space and back round two of the five ``conv_step`` calls (async
    # copies it hides under the experts' matmuls: 190 MB of a step's
    # ~9.5 GB; Olmo-Hybrid's 40.6 MB pool it leaves where it is): PERF.md
    # section 7
    strays = _pool_sized_strays(text, _ling_sizes(pool, extra))
    assert {(op, which) for _, op, which in strays} <= {("copy-done", "conv")}
    assert len(strays) <= 4, strays
    kernels = _kernel_op_names(text)
    latent = [op for op in kernels if "latent_paged_attention" in op]
    step = [op for op in kernels if "kda_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(latent) == 1 and len(step) == len(conv) == 5
    assert len(kernels) == 11
    assert "_decode_step)/blk_mixer/attn_latent/" in latent[0]
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_state/" in op
               for op in step)
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_conv/" in op
               for op in conv)
    for name, operand in (("kda_step/", 6), ("conv_step/", 3)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 5, name
    assert not re.search(r"/lin_attn/while/", text)
    for scope in ("blk_mixer/lin_attn_gate/", "blk_mlp/moe_dispatch/"
                  "moe_group/", "blk_mlp/moe_shared/"):
        assert f"jit(_decode_step)/{scope}" in text, scope
    # 128 rows x 8 of 512: the dense pass over the held experts
    assert "ragged-dot" not in text and "grouped_gemm" not in text


def test_ling_top_prefill_fits_beside_weights_states_and_latent_rows(
        one_chip, monkeypatch):
    """The 8,192-row top bucket (the traffic's 6,000-row prompts run
    it): the plan, which is the configuration's ``planned_bytes``, fits
    the chip beside 7.28 GB of weights, 1.34 GB of latent pages and
    1.40 GB of entries; the pools are aliased; every KDA layer runs ONE
    ``kda_chunked`` call under ``lin_attn/lin_attn_scan``, the latent
    layer the flash kernel under ``attn_latent``, the four routed layers
    the grouped GEMM in a loop over blocks of what is held; the entry
    is written whole, and nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    bucket = 8192
    cfg, params, pool, placeholder, extra, block, width, sds = _ling_cell(
        one_chip, monkeypatch)
    compiled = dm._prefill_bucket.lower(
        params, pool, placeholder, sds((bucket,), jnp.int32),
        (sds((bucket,), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool,) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LING_PLANS[bucket] < 15.0e9, planned
    assert cfg["generate"]["planned_bytes"] == planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _ling_sizes(pool, extra))
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    scan = [op for op in kernels if "kda_chunked/" in op]
    assert len(flash) == 1 and len(scan) == 5
    assert "_prefill_bucket)/blk_mixer/attn_latent/" in flash[0]
    assert all("_prefill_bucket)/blk_mixer/lin_attn/lin_attn_scan/" in op
               for op in scan)
    assert not re.search(r"/lin_attn_scan/while", text)
    _assert_grouped_gemm_kernel(text, layers=4, looped=True)
    assert len(kernels) == 1 + 5 + 8
