"""The ``granite-4.0-h-micro`` generate configuration's programs compiled
at their real sizes for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import math
import os
import re

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _hybrid_sizes, _kernel_grids, _kernel_op_names, one_chip, _planned_bytes,
    _pool_sized_strays, _under, _walk_dispatches, _walks_took)


def _granite_cell(one_chip, monkeypatch, pack=None):
    """The ``granite-4.0-h-micro`` generate configuration at its real
    sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, K/V pool, (state_pool, conv_pool),
    block, table width, sds).  ``pack``: another layout of the pages
    (the probe that chose the layout)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import granite_hybrid as gh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    assert cfg["reduced"] == [] and L == 40 == len(cfg["layer_types"])
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"])
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // H
    Hm, P, N = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            gh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=H, kv_heads=KV, head_dim=dh,
            layer_types=types, width=cfg["shared_intermediate_size"],
            mamba_n_heads=Hm, mamba_d_head=P, mamba_d_state=N,
            conv=cfg["mamba_d_conv"],
            dtype=dtype)))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 3_191_396_096
    state_pack = gh.heads_a_row(Hm, P)
    assert (dh, gh.heads_a_row(KV, dh), state_pack) == (64, 2, 2)
    pack = pack or gh.heads_a_row(KV, dh)
    block = gh.GraniteHybridBlock(
        layer_types=types, kv_heads=KV, head_dim=dh, pack=pack,
        state_pack=state_pack, mamba_n_heads=Hm, mamba_d_head=P,
        mamba_d_state=N,
        eps=cfg["rms_norm_eps"],
        full_pages=g["pages_per_seq"])
    full = sum(t == gh.ATTENTION for t in types)
    # two K/V heads of 64 a stored row of 128 lanes: nothing padded
    pool = sds((full, g["num_pages"], g["page_size"],
                KV // pack, pack * dh), dtype)
    E = g["state_entries"]
    assert E == g["slots"] + 1 and (full, L - full) == (4, 36)
    # a layer's state: the state index down the rows, two heads'
    # channels along the lanes
    extra = (sds((L - full, E, Hm // state_pack, N, state_pack * P),
                 jnp.float32),
             sds((L - full, E,
                  *tail_shape(cfg["mamba_d_conv"], Hm * P + 2 * N)), dtype))
    assert extra[1].shape[2:] == (102, 128)
    return cfg, params, pool, extra, block, g["pages_per_seq"] + 1, sds


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 961 pages
GRANITE_PLANS = {"decode": 12_448_998_912, 1920: 12_684_793_856}


def test_granite_decode_step_moves_states_tails_and_pages_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``granite-4.0-h-micro`` configuration at
    its real sizes (36 mamba + 4 attention layers, 961 bf16 pages of
    128 rows of two 64-wide heads a 128-lane row, 65 state entries, 64
    slots): the four cache buffers are aliased input to output; every
    mamba layer advances the slots' states by ONE ``ssd_step`` call
    under ``ssm/ssm_state`` after ONE ``conv_step`` call under
    ``ssm/ssm_conv`` over the rows the same entries keep, each pool its
    kernel's in-place operand, with no gather, no scatter and no loop
    over the slots;
    the four attention layers run the grouped paged kernel on the
    packed pages under ``attn_full`` and write their rows by 8
    scatters; nothing else has a pool's size (this is the probe that
    chose the packed layout: it neither copies a pool nor pads a
    row); the plan is the arguments + 88 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _granite_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    walks = _walk_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    _walks_took(walks, compiled_stored=4)
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == GRANITE_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    # K and V a full layer, and no other scatter: the tails move by
    # the conv's kernel
    assert sum(" scatter(" in ln for ln in text.splitlines()) == 8
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 4 and all("_decode_step)/blk_mixer/attn_full/" in op
                                 for op in gqa)
    # a slot a grid step: the walk of its live pages, not 15 columns
    grids = dict(_kernel_grids(text))
    assert {grids[op] for op in gqa} == {(S,)}
    step = [op for op in kernels if "ssd_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(step) == len(conv) == 36 and len(kernels) == 76
    assert all("_decode_step)/blk_mixer/ssm/ssm_state/" in op for op in step)
    # the conv's kernel under its own scope, outside the state's
    assert all("_decode_step)/blk_mixer/ssm/ssm_conv/" in op for op in conv)
    # each writes the pool it was given as its output 1: the states
    # operand 5, the tails operand 4 (entries, rows, taps, bias, pool)
    for name, operand in (("ssd_step/", 5), ("conv_step/", 4)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 36, name
    # no loop over the slots anywhere in a mamba layer
    assert not re.search(r"/ssm/while/", text)
    # the tied head contracts the embedding where it lies
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    assert not [s for s in _pool_sized_strays(text, {emb: "emb"})
                if s[1] in ("copy", "transpose")]


def test_granite_pages_of_unpacked_heads_are_refused_by_the_walk(
        one_chip, monkeypatch):
    """The layout that was NOT kept: pages of 8 K/V heads of 64, one
    head a stored row.  Under the ``(S, P)`` grid (until PR 58) the step
    planned gigabytes of temporaries at the configuration's 961 pages
    (1.0 GB of K/V): the compiler padded a row's 64 lanes to 128 and
    copied the pools to that layout around the kernel's calls, where two
    heads a 128-lane row plan the arguments + 88 MB (the case above).
    The walk copies a page out of the pool where it lies, and Mosaic
    refuses the slice of a 64-lane row outright: ``walk_fits`` says so,
    and the step of such a pool takes the gathered reference (no grouped
    kernel call in it).  When the bare compile below passes the
    compiler's rule has changed and ``heads_a_row`` can be looked at
    again."""
    import pytest

    from paddle_tpu.decode import attention as A
    from paddle_tpu.decode import model as dm
    from tests.chip_compile import _compiled_text

    cfg, params, pool, extra, block, width, sds = _granite_cell(
        one_chip, monkeypatch, pack=1)
    page, (KV, dh) = cfg["generate"]["page_size"], pool.shape[3:]
    assert (KV, dh) == (8, 64)
    g, S, Hq = cfg["generate"], cfg["generate"]["slots"], \
        cfg["num_attention_heads"]
    assert A.fits(page, Hq, dh, KV)
    assert not A.walk_fits(pool.dtype, page, KV, dh)
    assert A.walk_fits(pool.dtype, page, KV // 2, 2 * dh)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compiled_text(
            A.ragged_paged_attention_gqa, one_chip,
            ((S, 1, Hq, dh), pool.dtype), (pool.shape[1:], pool.dtype),
            (pool.shape[1:], pool.dtype), ((S, width - 1), jnp.int32),
            ((S,), jnp.int32))
    text = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=Hq, page_size=g["page_size"], block=block,
        extra=extra).as_text()
    assert "ragged_paged_attention_gqa" not in text
    assert "ssd_step" in text


def test_granite_top_prefill_fits_beside_weights_states_and_pages(
        one_chip, monkeypatch):
    """The 1,920-row prefill bucket (a sequence's capacity; the
    traffic's 1,200-row prompt runs in it): the plan fits the chip
    beside 6.38 GB of weights, 4.97 GB of states and 1.01 GB of pages
    (961: 64 sequences of 15 pages and the null page, all that the 65
    state entries can ever seat; the configuration's
    ``planned_bytes`` is this plan with the tail pool of a row an
    entry, 6 MB more); all four buffers are aliased; the four
    attention layers run the flash kernel at heads of 64; the entry is
    written whole by one dynamic-update-slice a pool, the pages by two
    scatters, and nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _granite_cell(
        one_chip, monkeypatch)
    bucket = 1920
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
    assert planned == GRANITE_PLANS[bucket] < 15.0e9, planned
    # the configuration's figure dates from the tail pool of a row an
    # entry, 65 rows a slab padded to 80: 6 MB over since PR 42
    assert 0 <= cfg["generate"]["planned_bytes"] - planned < 8 << 20
    g = cfg["generate"]
    assert g["num_pages"] == g["slots"] * g["pages_per_seq"] + 1
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    flash = _kernel_op_names(text)
    assert len(flash) == 4 and all(
        "_prefill_bucket)/blk_mixer/attn_full/" in op
        and "flash_attention_fwd" in op
        for op in flash)
    for scope in ("ssm/ssm_scan", "ssm/ssm_conv"):
        assert f"jit(_prefill_bucket)/{_under(scope)}/" in text, scope
