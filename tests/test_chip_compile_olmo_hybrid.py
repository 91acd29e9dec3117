"""The ``olmo-hybrid-7b`` generate configuration's programs compiled at
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
    _hybrid_sizes, _kernel_grids, _kernel_op_names, one_chip,
    _planned_bytes, _pool_sized_strays)


def _hybrid_cell(one_chip, monkeypatch):
    """The ``olmo-hybrid-7b`` generate configuration at its real sizes,
    as shapes on the described chip, built as its gen_config builds the
    model: (cfg, params, (k_pool, v_pool), (state_pool, conv_pool),
    block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.attention import storage_heads
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import olmo_hybrid as oh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"][:L])
    H = cfg["num_attention_heads"]
    dh = cfg["hidden_size"] // H
    Hl, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                  cfg["linear_value_head_dim"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            oh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=H, head_dim=dh, layer_types=types,
            width=cfg["intermediate_size"], lin_heads=Hl, d_k=dk, d_v=dv,
            conv=cfg["linear_conv_kernel_dim"], dtype=dtype)))
    block = oh.OlmoHybridBlock(
        layer_types=types, head_dim=dh, lin_heads=Hl, d_k=dk, d_v=dv,
        eps=cfg["rms_norm_eps"], full_pages=g["pages_per_seq"])
    full = sum(t == oh.FULL for t in types)
    assert storage_heads(H, dtype) == 32 and oh.stored_key_width(dk) == 128
    pool = sds((full, g["num_pages"], g["page_size"], 32, dh), dtype)
    E = g["state_entries"]
    assert E == g["slots"] + 1
    # an entry's kept rows one after another in rows of lanes
    tail = tail_shape(cfg["linear_conv_kernel_dim"], Hl * (2 * dk + dv))
    assert tail == (270, 128)
    extra = (sds((L - full, E, Hl, dv, 128), jnp.float32),
             sds((L - full, E, *tail), dtype))
    return cfg, params, pool, extra, block, g["pages_per_seq"] + 1, sds


def test_hybrid_decode_step_moves_states_and_pages_in_place(one_chip,
                                                            monkeypatch):
    """The decode step of the ``olmo-hybrid-7b`` configuration at its
    real sizes (12 linear + 4 full layers, 447 bf16 pages of 128 rows
    at 32 stored heads, 49 state entries, 48 slots): the four cache
    buffers are aliased input to output; the four full layers run the
    paged kernel under ``attn_full`` and write their rows by 8
    scatters; every linear layer advances the slots' states by ONE
    ``gated_delta_step`` call under ``lin_attn/lin_attn_state`` after
    ONE ``conv_step`` call under ``lin_attn/lin_attn_conv`` over the
    rows the same entries keep, each pool its kernel's in-place
    operand, no loop over the slots; nothing else has a pool's size
    (the slot loop's tail pool met two layout copies of its 41 MB at
    the step's two ends; a layout copy of the 1.73 GB state pool before
    a custom call is what the K/V pools met at 30 heads); the plan is
    arguments + 70 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _hybrid_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == HYBRID_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    assert sum(" scatter(" in ln for ln in text.splitlines()) == 8
    kernels = _kernel_op_names(text)
    rpa = [op for op in kernels if "ragged_paged_attention/" in op]
    assert len(rpa) == 4 and all("_decode_step)/blk_mixer/attn_full/" in op
                                 for op in rpa)
    # 1 MB a page: ``walk_fits`` refuses the double buffers, and the
    # step keeps a grid step a (slot, table column) (PR 60)
    assert [grid for op, grid in _kernel_grids(text)
            if "ragged_paged_attention/" in op] == [(S, width - 1)] * 4
    step = [op for op in kernels if "gated_delta_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(step) == len(conv) == 12 and len(kernels) == 28
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_state/" in op
               for op in step)
    # the conv's kernel under its own scope, outside the state's
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_conv/" in op
               for op in conv)
    # each writes the pool it was given as its output 1: the states
    # operand 6, the tails operand 3 (entries, rows, taps, pool)
    for name, operand in (("gated_delta_step/", 6), ("conv_step/", 3)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 12, name
    # both pools are read and written by the kernels alone, no loop
    # over the slots anywhere in a linear layer
    assert not re.search(r"/lin_attn/while/", text)


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 447 pages
HYBRID_PLANS = {"decode": 13_760_889_344, 4096: 14_703_584_256,
                4608: 14_809_302_016}


@pytest.mark.parametrize("bucket", [4096, 4608])
def test_hybrid_top_prefill_fits_beside_weights_states_and_pages(
        one_chip, monkeypatch, bucket):
    """The 4,096-row prefill bucket (the longest the cell's traffic
    sends) and the 4,608-row one (a sequence's capacity): the plan fits
    the chip beside 8.2 GB of weights, 3.75 GB of pages and 1.78 GB of
    states (``num_pages`` was chosen by the 4,608-row plan of the XLA
    scan, 14,986,060,800 since PR 42; the kernel's needs 177 MB less,
    142 MB at 4,096 rows: the solve's and the scan's float32 operands
    for all chunks at once are gone); all four buffers are aliased; the
    four full layers run the flash kernel at 30 heads and every linear
    layer ONE ``gated_delta_chunked`` call under
    ``lin_attn/lin_attn_scan``, no loop there; the entry is written
    whole by one dynamic-update-slice a pool, the pages by two
    scatters, and nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _hybrid_cell(
        one_chip, monkeypatch)
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
    assert planned == HYBRID_PLANS[bucket] < 15.0e9, planned
    if bucket == 4608:
        # the configuration's figure is the XLA scan's plan with the
        # tail pool of three rows an entry
        assert 0 <= cfg["generate"]["planned_bytes"] - planned < 192 << 20
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    scan = [op for op in kernels if "gated_delta_chunked/" in op]
    assert len(flash) == 4 and len(scan) == 12 and len(kernels) == 16
    assert all("_prefill_bucket)/blk_mixer/attn_full/" in op for op in flash)
    assert all("_prefill_bucket)/blk_mixer/lin_attn/lin_attn_scan/" in op
               for op in scan)
    assert not re.search(r"/lin_attn_scan/while", text)
    assert "jit(_prefill_bucket)/blk_mixer/lin_attn/lin_attn_conv/" in text
