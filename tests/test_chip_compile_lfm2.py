"""The ``lfm2-8b-a1b`` generate configuration's programs compiled at their
real sizes for the described v5e (``tests/chip_compile.py``: no chip
attached, nothing executes): the 64-slot decode step, the 8,192-row top
bucket and the largest chunk (4,096 rows over 20,480 cached).
"""

import math
import os
import re

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _kernel_grids, _kernel_op_names, one_chip, _planned_bytes,
    _pool_sized_strays, _under, _walk_dispatches, _walks_took)

PARAMETERS = 3_928_728_256


def _lfm2_cell(one_chip, monkeypatch):
    """The configuration at its real sizes, as shapes on the described
    chip, built as its gen_config builds the model: (cfg, params, K/V
    pool, (state placeholder, conv_pool), block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import lfm2_moe as lm

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers"] and L == 12
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"][:L])
    assert types == lm.PERIOD * 3
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, dh = cfg["hidden_size"], cfg["hidden_size"] // H

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            lm.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, heads=H, kv_heads=KV, head_dim=dh, layer_types=types,
            dense_layers=cfg["num_dense_layers"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            experts=cfg["num_experts"], conv=cfg["conv_L_cache"],
            dtype=dtype)))
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == PARAMETERS
    pack = lm.heads_a_row(KV, dh)
    assert (dh, pack) == (64, 2)
    block = lm.Lfm2MoeBlock(
        layer_types=types, kv_heads=KV, head_dim=dh, pack=pack,
        eps=cfg["norm_eps"], theta=float(cfg["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]), route_eps=g["route_eps"],
        experts=cfg["num_experts"], full_pages=g["pages_per_seq"])
    full = sum(t == lm.ATTENTION for t in types)
    pool = sds((full, g["num_pages"], g["page_size"], KV // pack, pack * dh),
               dtype)
    E = g["state_entries"]
    assert E == g["slots"] + 1 and (full, L - full) == (3, 9)
    # no state pool: a placeholder; an entry is the conv tail alone,
    # two rows of 2,048 channels as 32 rows of lanes, 8 KB a layer
    extra = (sds((L - full, 1), jnp.float32),
             sds((L - full, E, *tail_shape(cfg["conv_L_cache"], d)), dtype))
    assert extra[1].shape[2:] == (32, 128)
    assert math.prod(extra[1].shape[2:]) * 2 * (L - full) == 9 * 8192
    return cfg, params, pool, extra, block, g["pages_per_seq"] + 1, sds


def _sizes(pool, extra):
    return {math.prod(pool.shape): "kv", math.prod(pool.shape[1:]): "kv slab",
            math.prod(extra[1].shape): "conv"}


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's pages
LFM2_PLANS = {"decode": 13_084_417_024, 8192: 13_754_907_136,
              "chunk": 13_407_200_768}


def test_lfm2_decode_step_moves_tails_and_pages_in_place(one_chip,
                                                         monkeypatch):
    """The 64-slot decode step: all four cache buffers aliased input to
    output; every conv layer ONE ``conv_step`` call under
    ``short_conv/short_conv_step`` with the tail pool its in-place
    operand (no bias: operand 3) and no gather, scatter or loop over the
    slots; the three attention layers the grouped walk on the packed
    pages under ``attn_full``, a slot a grid step, their rows written by
    6 scatters; the ten routed layers the dense pass reading the experts
    where they lie; nothing else of a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _lfm2_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    walks = _walk_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    _walks_took(walks, compiled_stored=3)
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LFM2_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    # nothing of a K/V pool's size; the tail pool is 4.8 MB, and the
    # compiler moves it whole between memory spaces round the kernel's
    # calls (copy-start / copy-done to and from S(1)): moves, not the
    # gather and scatter the kernel replaces
    strays = _pool_sized_strays(text, _sizes(pool, extra))
    assert not [s for s in strays if s[2] != "conv"], strays
    assert {s[1] for s in strays} <= {"copy-done", "custom-call"}, strays
    # K and V an attention layer, a routed layer's count of its load,
    # and no other scatter: the tails move by the conv's kernel
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    assert len(scatters) == 6 + 10
    assert sum("/attn_full/" in ln for ln in scatters) == 6
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 3 and all("_decode_step)/blk_mixer/attn_full/" in op
                                 for op in gqa)
    assert {dict(_kernel_grids(text))[op] for op in gqa} == {(S,)}
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(conv) == 9 and len(kernels) == 12
    assert all("_decode_step)/blk_mixer/short_conv/short_conv_step/" in op
               for op in conv)
    aliased = "output_to_operand_aliasing={{1}: (3, {})}"
    assert sum("conv_step/" in ln and aliased in ln
               for ln in text.splitlines()) == 9
    assert not re.search(r"/short_conv/while/", text)
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


def test_lfm2_top_bucket_fits_beside_weights_and_pages(one_chip,
                                                       monkeypatch):
    """The 8,192-row top bucket: the plan fits the chip beside 7.86 GB
    of weights and the pages; the buffers aliased; the three attention
    layers the flash kernel at heads of 64; the ten routed layers two
    grouped-GEMM calls each; the conv under ``short_conv_scan``."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _lfm2_cell(
        one_chip, monkeypatch)
    bucket = cfg["generate"]["prefill_rows"]
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        (sds((bucket,), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LFM2_PLANS[bucket] < 15.0e9, planned
    # the largest of the three: the configuration's figure
    assert planned == cfg["generate"]["planned_bytes"] \
        == max(LFM2_PLANS.values())
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _sizes(pool, extra))
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    assert len(flash) == 3 and all(
        "_prefill_bucket)/blk_mixer/attn_full/" in op for op in flash)
    gemms = [op for op in kernels if "grouped_gemm" in op]
    assert len(gemms) == 20 and len(kernels) == 23
    assert f"jit(_prefill_bucket)/{_under('short_conv/short_conv_scan')}/" \
        in text


def test_lfm2_largest_chunk_fits_and_reads_the_run_by_the_table(
        one_chip, monkeypatch):
    """The largest chunk program: 4,096 rows over 20,480 cached.  The
    buffers aliased; a conv layer reads the entry's tail and writes it
    back (no ``conv_step``: that is a decode step's); an attention layer
    gathers the run's 20,480 rows by the table, ROW by row as the
    scatter addresses them, and reads them and the chunk by two flash
    calls under ``attn_full/attn_chunk``; nothing has a pool's size:
    gathered a PAGE at a time the compiler re-laid the whole pool for
    the gather (a 2.6 GB copy a layer and pool, 15.8 GB planned; this
    probe found it before any chip time)."""
    from paddle_tpu.decode import state_entry as se

    cfg, params, pool, extra, block, width, sds = _lfm2_cell(
        one_chip, monkeypatch)
    g = cfg["generate"]
    C = g["chunk_rows"]
    done = g["pages_per_seq"] * g["page_size"] - C
    assert (C, done) == (4096, 20480)
    compiled = se._prefill_state_chunk.lower(
        params, pool, pool, sds((width,), jnp.int32), sds((C,), jnp.int32),
        sds((), jnp.int32), heads=cfg["num_attention_heads"],
        page_size=g["page_size"], block=block, done=done,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LFM2_PLANS["chunk"] < 15.0e9, planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _sizes(pool, extra))
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    assert len(flash) == 6 and all(
        "_prefill_state_chunk)/blk_mixer/attn_full/attn_chunk/" in op
        for op in flash)
    assert not [op for op in kernels if "conv_step" in op]
    assert ("jit(_prefill_state_chunk)/"
            f"{_under('short_conv/short_conv_scan')}/") in text
