"""The ``mimo-v2.5`` generate configuration's programs compiled at their
real sizes for the described v5e (``tests/chip_compile.py``: no chip
attached, nothing executes): the 48-slot decode step, the 8,192-row top
bucket and the largest chunk (4,096 rows over 28,672 cached), and the
probes that chose how a full layer's 192-wide key is stored and what a
ring entry is.
"""

import math
import os

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _kernel_grids, _kernel_op_names, one_chip, _planned_bytes,
    _pool_sized_strays, _under, _walk_dispatches, _walks_took)

PARAMETERS = 3_429_955_392


def _mimo_cell(one_chip, monkeypatch, **over):
    """The configuration at its real sizes, as shapes on the described
    chip, built as its gen_config builds the model: (cfg, params, (K
    pool, V pool), (ring K, ring V), block, table width, sds).
    ``over``: fields of the block a probe changes."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import mimo_v2 as mm

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "mimo-v2.5.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"] and L == 7
    dtype = jnp.dtype(g["dtype"])
    types = tuple(mm.WINDOW if w else mm.FULL
                  for w in cfg["hybrid_layer_pattern"][:L])
    assert types == mm.PATTERN
    routed = tuple(bool(r) for r in cfg["moe_layer_freq"][:L])
    assert routed == (False,) + (True,) * 6
    H, KV, WKV = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["swa_num_key_value_heads"])
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    assert (H, KV, WKV, dk, dv) == (64, 4, 8, 192, 128)
    held = cfg["n_routed_experts"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            mm.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=H, kv_heads=KV, window_kv_heads=WKV,
            head_dim=dk, value_dim=dv, layer_types=types, moe_layers=routed,
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            router_width=cfg["n_routed_experts_published"], held=held,
            dtype=dtype)))
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == PARAMETERS
    fields = dict(
        layer_types=types, kv_heads=KV, window_kv_heads=WKV, head_dim=dk,
        value_dim=dv, rotary=64, key_lanes=256, ring_lanes=256,
        window=cfg["sliding_window"], eps=cfg["layernorm_epsilon"],
        theta=float(cfg["rope_theta"]),
        window_theta=float(cfg["swa_rope_theta"]),
        value_scale=cfg["attention_value_scale"],
        top_k=cfg["num_experts_per_tok"], scale=1.0,
        held=(cfg["ep_rank"] * held, held),
        experts=cfg["n_routed_experts_published"],
        full_pages=g["pages_per_seq"])
    fields.update(over)
    block = mm.MimoV2Block(**fields)
    full = sum(t == mm.FULL for t in types)
    run = (full, g["num_pages"], g["page_size"], KV)
    E = g["ring_entries"]
    assert E == g["slots"] + 1 and (full, L - full) == (2, 5)
    ring = (L - full, E, 2 * g["page_size"], WKV)
    pools = (sds(run + (block.key_lanes,), dtype), sds(run + (dv,), dtype))
    extra = (sds(ring + (block.ring_lanes,), dtype),
             sds(ring + (dv,), dtype))
    return cfg, params, pools, extra, block, g["pages_per_seq"] + 1, sds


def _sizes(pools, extra):
    out = {}
    for name, a in (("k", pools[0]), ("v", pools[1])):
        out[math.prod(a.shape)] = name + " pool"
        out[math.prod(a.shape[1:])] = name + " slab"
    for name, a in (("ring k", extra[0]), ("ring v", extra[1])):
        out[math.prod(a.shape)] = name
    return out


def _buffers(pools, extra):
    return sum(math.prod(a.shape) * a.dtype.itemsize for a in pools + extra)


def _lower_step(cfg, params, pools, extra, block, width, sds):
    from paddle_tpu.decode import model as dm

    g, S = cfg["generate"], cfg["generate"]["slots"]
    return dm._decode_step.lower(
        params, *pools, sds((S, width), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), heads=cfg["num_attention_heads"],
        page_size=g["page_size"], block=block, extra=extra)


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's pages
MIMO_PLANS = {"decode": 13_281_528_832, 8192: 14_999_749_120,
              "chunk": 14_506_603_520}


def test_mimo_decode_step_walks_the_run_and_gathers_the_rings(one_chip,
                                                              monkeypatch):
    """The 48-slot decode step: all four cache buffers aliased input to
    output; the two full layers the grouped walk under ``attn_full``, a
    slot a grid step, on K pages of 256 lanes and V pages of 128; the
    five window layers plain XLA under ``attn_window`` (no kernel);
    nothing of a run pool's size is copied; the six routed layers the
    grouped path under the ``moe_*`` scopes."""
    cell = _mimo_cell(one_chip, monkeypatch)
    cfg, params, pools, extra, block, width, sds = cell
    S = cfg["generate"]["slots"]
    walks = _walk_dispatches()
    compiled = _lower_step(*cell).compile()
    _walks_took(walks, compiled_stored=2)
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _buffers(pools, extra)
    planned = _planned_bytes(compiled)
    assert planned == MIMO_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    # of a ring pool's size: the halves a window layer's gather reads
    # its entries from, inside the gather's fusion; no copy
    strays = _pool_sized_strays(text, _sizes(pools, extra))
    assert {s[1] for s in strays} <= {"slice"}, strays
    assert all("ring" in s[2] for s in strays), strays
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 2
    assert all("_decode_step)/blk_mixer/attn_full/" in op for op in gqa)
    walks = "\n".join(ln for ln in text.splitlines()
                      if "ragged_paged_attention_gqa/" in ln)
    assert {grid for _, grid in _kernel_grids(walks)} == {(S,)}
    assert f"jit(_decode_step)/{_under('attn_window')}/" in text
    # 48 rows x 8 of 256 experts are 1.5 assignments an expert: the
    # grouped path over the held experts' blocks (``moe.expert_path``),
    # two grouped-GEMM calls a routed layer inside the share's loop
    gemms = [op for op in kernels if "grouped_gemm" in op]
    assert len(gemms) == 12 and len(kernels) == 14
    assert all("_decode_step)/blk_mlp/while/body/moe_experts/" in op
               for op in gemms), gemms
    for scope in ("moe_router", "moe_dispatch"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


def test_mimo_top_bucket_fits_beside_weights_and_pools(one_chip,
                                                       monkeypatch):
    """The 8,192-row top bucket: the plan fits the chip beside 6.86 GB
    of weights and the pools; the buffers aliased; the two full layers
    the flash kernel at heads of 192 (the values padded to them); the
    window layers banded in XLA under ``attn_window``; the six routed
    layers two grouped-GEMM calls each."""
    from paddle_tpu.decode import model as dm

    cfg, params, pools, extra, block, width, sds = _mimo_cell(
        one_chip, monkeypatch)
    bucket = cfg["generate"]["prefill_rows"]
    compiled = dm._prefill_bucket.lower(
        params, *pools, sds((bucket,), jnp.int32),
        (sds((bucket,), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _buffers(pools, extra)
    planned = _planned_bytes(compiled)
    assert planned == MIMO_PLANS[bucket] < 15.0e9, planned
    text = compiled.as_text()
    strays = _pool_sized_strays(text, _sizes(pools, extra))
    assert not [s for s in strays if "ring" not in s[2]], strays
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    assert len(flash) == 2 and all(
        "_prefill_bucket)/blk_mixer/attn_full/" in op for op in flash)
    gemms = [op for op in kernels if "grouped_gemm" in op]
    assert len(gemms) == 12 and len(kernels) == 14
    assert f"jit(_prefill_bucket)/{_under('attn_window')}/" in text


def test_mimo_largest_chunk_fits_and_reads_run_and_ring(one_chip,
                                                        monkeypatch):
    """The largest chunk program: 4,096 rows over 28,672 cached.  The
    buffers aliased; a full layer gathers the run's 28,672 rows by the
    table and reads them and the chunk by two flash calls under
    ``attn_full/attn_chunk``; a window layer is banded over the ring's
    newest page and the chunk under ``attn_window``; nothing has a run
    pool's size.  The largest of the three plans: the configuration's
    figure."""
    from paddle_tpu.decode import state_entry as se

    cfg, params, pools, extra, block, width, sds = _mimo_cell(
        one_chip, monkeypatch)
    g = cfg["generate"]
    C = g["chunk_rows"]
    done = g["pages_per_seq"] * g["page_size"] - C
    assert (C, done) == (4096, 28672)
    compiled = se._prefill_state_chunk.lower(
        params, *pools, sds((width,), jnp.int32), sds((C,), jnp.int32),
        sds((), jnp.int32), heads=cfg["num_attention_heads"],
        page_size=g["page_size"], block=block, done=done,
        extra=extra).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= _buffers(pools, extra)
    planned = _planned_bytes(compiled)
    assert planned == MIMO_PLANS["chunk"] < 15.0e9, planned
    assert cfg["generate"]["planned_bytes"] == max(MIMO_PLANS.values())
    text = compiled.as_text()
    strays = _pool_sized_strays(text, _sizes(pools, extra))
    assert not [s for s in strays if "ring" not in s[2]], strays
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    assert len(flash) == 4 and all(
        "_prefill_state_chunk)/blk_mixer/attn_full/attn_chunk/" in op
        for op in flash)
    assert f"jit(_prefill_state_chunk)/{_under('attn_window')}/" in text


def test_mimo_key_of_192_lanes_is_refused_by_the_walk(one_chip,
                                                      monkeypatch):
    """The probe that chose the key's stored form.  Stored as published,
    192 lanes a head, the compiled walk cannot copy a page out of HBM
    (rows that are no whole 128-lane tiles): ``walk_fits`` refuses it
    and the step's full layers take the gathered XLA reference, which
    re-lays the whole K pool out for its gather (a 3.3 GB copy and a
    4.4 GB padded one at 9,000 pages: 19.88 GB asked of the chip's 15.75,
    the compile refused).  At 256 lanes the walk takes the pages where
    they lie: the step above."""
    from paddle_tpu.decode import attention as pa

    assert not pa.walk_fits(jnp.bfloat16, 128, 4, 192)
    assert pa.walk_fits(jnp.bfloat16, 128, 4, 256)
    cell = _mimo_cell(one_chip, monkeypatch, key_lanes=192)
    try:
        compiled = _lower_step(*cell).compile()
    except Exception as e:                     # the chip's compiler's word
        assert "RESOURCE_EXHAUSTED" in str(e) and "hbm" in str(e), e
        return
    assert not _kernel_op_names(compiled.as_text())
    assert _planned_bytes(compiled) > MIMO_PLANS["decode"] + 1.0e9


def test_mimo_ring_key_of_192_lanes_is_copied_whole(one_chip, monkeypatch):
    """The probe that chose the ring entry's shape.  With a window
    layer's keys kept at the published 192 lanes the compiler re-lays
    the WHOLE ring pool out round every window layer's gather (copies of
    193 MB, ten of them a step, 0.4 GB more planned); at 256 lanes (the
    step above) nothing of a ring pool's size is left but the gathers'
    own slices inside their fusions.  The rings as pages of a second
    class in the run's pool were not probed: a ring page of 8 heads and a
    run page of 4 have no pool shape in common."""
    cell = _mimo_cell(one_chip, monkeypatch, ring_lanes=192)
    cfg, params, pools, extra, block, width, sds = cell
    compiled = _lower_step(*cell).compile()
    strays = _pool_sized_strays(compiled.as_text(), _sizes(pools, extra))
    copies = [s for s in strays if s[1] == "copy" and s[2] == "ring k"]
    assert len(copies) >= 5, strays
    assert _planned_bytes(compiled) > MIMO_PLANS["decode"] + 0.3e9
