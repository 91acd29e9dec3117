"""The ``phi-4-mini-flash-reasoning`` generate configuration's programs
compiled at their real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import math
import os
import re

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _hybrid_sizes, _kernel_grids, _kernel_op_names, one_chip, _planned_bytes,
    _pool_sized_strays, _ring_dispatches, _under, _walk_dispatches,
    _walks_took)


# -- a decoder-hybrid-decoder (PR 48) -----------------------------------------

# memory_analysis() of the two programs at the configuration's 7,041
# pages.  The bucket's was 14,930,227,200, which
# perf/configs/phi-4-mini-flash-reasoning.json records, while nine
# layers' bucket-long K/V lived to the program's end and were stacked
# there; a window layer keeps its ring's five pages alone now (PR 52;
# the configuration is a benchmark file, not that PR's to edit).  The
# step's was 12,723,929,088 (and is in that file's ``planned_how``)
# while the rings were gathered, turned and widened
PHI4_PLANS = {"decode": 12_623_595_520, 12288: 14_596_378_112}
PHI4_PARAMS = 3_852_562_944


def _phi4_cell(one_chip, monkeypatch):
    """The ``phi-4-mini-flash-reasoning`` generate configuration at its
    real sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, K/V pool, (state_pool, conv_pool),
    block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import phi4_flash as pf

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    g, L, sizes = cfg["generate"], cfg["num_hidden_layers"], \
        cfg["assumed_sizes"]
    assert cfg["reduced"] == [] and L == 32
    dtype = jnp.dtype(g["dtype"])
    types = pf.layer_kinds(L, cfg["mb_per_layer"])
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["hidden_size"])
    dh, C, N = sizes["head_dim"], sizes["mamba_expand"] * d, \
        sizes["mamba_d_state"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            pf.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, heads=H, kv_heads=KV, head_dim=dh, layer_types=types,
            width=cfg["intermediate_size"], d_inner=C, d_state=N,
            dt_rank=sizes["mamba_dt_rank"], conv=sizes["mamba_d_conv"],
            dtype=dtype)))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == PHI4_PARAMS
    rings = sum(t == pf.WINDOW for t in types)
    ring_pages = cfg["sliding_window"] // g["page_size"] + 1
    assert (rings, ring_pages) == (8, g["ring_pages"]) == (8, 5)
    block = pf.Phi4FlashBlock(
        layer_types=types, kv_heads=KV, head_dim=dh,
        window=cfg["sliding_window"], d_inner=C, d_state=N,
        dt_rank=sizes["mamba_dt_rank"], eps=cfg["layer_norm_eps"],
        full_pages=g["pages_per_seq"], ring_pages=ring_pages,
        page_size=g["page_size"])
    # a K/V PAIR a stored row of 128 lanes, a page's ten stored heads
    # outside its rows: whole tiles whatever the head count
    pool = sds((1, g["num_pages"], KV // 2, g["page_size"], 2 * dh), dtype)
    E, mamba = g["state_entries"], sum(t == pf.MAMBA for t in types)
    assert E == g["slots"] + 1 and mamba == 9
    extra = (sds((mamba, E, N, C), jnp.float32),
             sds((mamba, E, *tail_shape(sizes["mamba_d_conv"], C)), dtype))
    assert extra[1].shape[2:] == (120, 128)
    width = g["pages_per_seq"] + rings * ring_pages + 1
    return cfg, params, pool, extra, block, width, sds


def test_phi4_decode_step_moves_states_rings_and_the_one_run_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``phi-4-mini-flash-reasoning``
    configuration at its real sizes (7,041 bf16 pages of 10 stored heads
    x 128 rows x 128 lanes, 65 state entries of nine (16, 5,120) float32
    states, 64 slots, a table row of 96 + 40 + 1 columns): the four
    cache buffers are aliased input to output and the plan is the
    arguments + 42 MB; every Mamba-1 layer advances the slots' states
    by ONE ``s6_step`` call under ``ssm/ssm_state`` (the pool its
    in-place operand) after ONE ``conv_step`` call under
    ``ssm/ssm_conv``; the page run's owner and the seven cross layers
    run the grouped paged kernel on the heads-major pages under
    ``attn_shared``, EIGHT calls, and only TWO scatters lie under it:
    the owner's K and V row; a cross layer writes nothing; each of the
    eight window layers writes its row (two scatters) and reads its
    ring's five pages where they lie by ONE ``ring_paged_attention``
    call under ``attn_window``, with no gathered copy of a ring beside
    it.  Nothing has
    a pool's size but the pools (this is the probe that chose the
    layout: with the ten heads inside a page's rows, ``(N, 128, 10,
    128)``, the same step planned 5.6 GB of copies of the pool, 1.6
    times its bytes each)."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _phi4_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    assert width == 137
    walks = _walk_dispatches()
    before = _ring_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    _walks_took(walks, compiled=8)
    after = _ring_dispatches()
    assert {p: after[p] - before[p] for p in after} == {
        "compiled": 8, "interpret": 0, "reference": 0}
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    # the buffers alone are handed back: what layer 16 hands the GMUs
    # is no output
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == PHI4_PLANS["decode"] < 15.0e9, planned
    assert m.temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    # the 18 MB tail pool is small enough that the compiler moves it to
    # fast memory and back round the conv kernels (copy-start / -done to
    # S(1)): no layout copy, and not held here
    sizes = _hybrid_sizes(pool, extra)
    del sizes[math.prod(extra[1].shape)]
    assert not _pool_sized_strays(text, sizes)
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    assert sum("/attn_shared/" in ln for ln in scatters) == 2
    assert all("/attn_shared/" in ln or "/attn_window/" in ln
               for ln in scatters)
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 8 and all("_decode_step)/blk_mixer/attn_shared/" in op
                                 for op in gqa)
    ring = [op for op in kernels if "ring_paged_attention/" in op]
    assert len(ring) == 8 and all("_decode_step)/blk_mixer/attn_window/" in op
                                  for op in ring)
    # the run's eight reads walk a slot's live pages, a slot a grid
    # step (96 table columns a slot are no part of the grid); a ring's
    # keep a step a (slot, ring column)
    grids = dict(_kernel_grids(text))
    assert {grids[op] for op in gqa} == {(S,)}
    assert {grids[op] for op in ring} == {(S, g["ring_pages"])}
    assert sum("/attn_window/" in ln for ln in scatters) == 16
    # a slot's ring is 5 pages of 10 heads x 128 rows: no gathered copy
    assert not re.search(r"\[64,5,10,128,128\]|\[64,5,128,10,128\]"
                         r"|\[64,640,10,128\]", text)
    step = [op for op in kernels if "s6_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(step) == len(conv) == 9 and len(kernels) == 34
    assert all("_decode_step)/blk_mixer/ssm/ssm_state/" in op for op in step)
    assert all("_decode_step)/blk_mixer/ssm/ssm_conv/" in op for op in conv)
    # each writes the pool it was given as its output 1: the states
    # operand 6 (entries, dt, x, A, B, C, pool), the tails operand 4
    for name, operand in (("s6_step/", 6), ("conv_step/", 4)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 9, name
    assert not re.search(r"/ssm/while/", text)
    for scope in ("attn_window", "gmu"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


def test_phi4_top_prefill_fits_beside_weights_states_rings_and_the_run(
        one_chip, monkeypatch):
    """The 12,288-row prefill bucket (a sequence's capacity; the
    traffic's 10,500-row prompts run in it): the plan, 14.60 GB, is
    under the configuration's ``planned_bytes`` and fits 15.0 GB beside
    7.71 GB of weights, 4.61 GB of pages and 0.21 GB of state entries;
    all four buffers are aliased; what the prompt leaves in the pools
    is written under ``blk_store`` by ONE scatter a pool of 136 whole
    pages (eight rings' five and the run's 96), and no window layer's
    bucket-long K/V reach it; the selective scan materialises no ``rows x
    5,120 x 16`` tensor (4 GB at this bucket): it is a loop under
    ``ssm/ssm_scan`` whose body holds a state; the layers from the full
    one on run on ONE row (no 12,288-row instruction lies under ``gmu``,
    and the only ones under ``attn_shared`` are the K/V rows'); and
    nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _phi4_cell(
        one_chip, monkeypatch)
    bucket = 12288
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        (sds((9, bucket), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == PHI4_PLANS[bucket], planned
    assert planned <= cfg["generate"]["planned_bytes"] < 15.0e9
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    stores = [ln for ln in text.splitlines()
              if " scatter(" in ln and "/blk_store/" in ln
              and ln.lstrip().startswith("ROOT")]
    pages = [ln for ln in stores if "bf16[7041,10,128,128]" in ln]
    assert len(pages) == 2 and all(
        "update_window_dims={1,2,3}, inserted_window_dims={0}" in ln
        for ln in pages), stores
    assert re.search(r"s32\[136\]\S* [a-z]+\(.*/blk_store/", text)
    assert len(stores) == 2        # the entry's two are slices in place
    # the rows of all nine layers' K/V, stacked: 9 x 12,288
    assert not re.search(r"\[9,12288,10,128\]|\[110592,10,128\]", text)
    assert "12288,16,5120" not in text and "12288,5120,16" not in text
    assert re.search(r"_prefill_bucket\)/blk_mixer/ssm/ssm_scan/while", text)
    for scope in ("ssm/ssm_conv", "attn_window", "attn_shared", "gmu"):
        assert f"jit(_prefill_bucket)/{_under(scope)}/" in text, scope
    for ln in text.splitlines():
        if "/gmu/" in ln:
            assert "12288" not in ln.split("metadata")[0], ln
