"""The bare Pallas kernels of the main path, each compiled alone at real
widths for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import pytest

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _compiled_text, _kernel_grids, _kernel_op_names, MARKER, one_chip)


# (slots, heads, head_dim, page, pool pages, pages/seq, dtype): a real
# decode batch, the /generate model chip_smoke.py serves, and the steps
# of the two cells that run this kernel (Cerebras f32, OLMoE bf16)
RPA_REAL = (64, 16, 128, 16, 2048, 32, jnp.bfloat16)
RPA_TOY = (4, 4, 8, 8, 64, 8, jnp.float32)
RPA_CEREBRAS = (16, 16, 128, 32, 641, 40, jnp.float32)
RPA_OLMOE = (32, 16, 128, 32, 2049, 64, jnp.bfloat16)


@pytest.mark.parametrize(
    "shape", [RPA_REAL, RPA_TOY, RPA_CEREBRAS, RPA_OLMOE],
    ids=["real", "toy", "cerebras-step", "olmoe-step"])
def test_ragged_paged_attention_compiles(one_chip, shape):
    from paddle_tpu.decode import attention as A

    S, H, D, page, N, P, dt = shape
    text = _compiled_text(
        A.ragged_paged_attention,
        one_chip, ((S, H, D), dt), ((N, page, H, D), dt),
        ((N, page, H, D), dt), ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text
    assert all("ragged_paged_attention/" in op
               for op in _kernel_op_names(text))


# the Olmo-Hybrid's full layers: 48 slots, 32 stored heads of 128 on
# 128-row bf16 pages, 1 MB a page
RPA_HYBRID = (48, 32, 128, 128, 447, 36, jnp.bfloat16)


@pytest.mark.parametrize("shape, walks", [
    (RPA_CEREBRAS, True), (RPA_OLMOE, True), (RPA_REAL, True),
    (RPA_HYBRID, False)],
    ids=["cerebras-step", "olmoe-step", "real", "olmo-hybrid-step"])
def test_the_steps_row_through_paged_attention_compiles(
        one_chip, monkeypatch, shape, walks):
    """The decode step's call on ungrouped heads, through the
    dispatcher: where ``walk_fits`` takes the pool's pages (the two
    cells', the /generate model's) the walk, a slot a grid step; where
    it refuses them (the hybrid's 1 MB pages) the ``(S, P)`` grid; both
    under the name the step's kernel has always had."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import attention as A

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    S, H, D, page, N, P, dt = shape
    assert A.walk_fits(dt, page, H, D) == walks
    text = _compiled_text(
        lambda *a: A.paged_attention(*a),
        one_chip, ((S, H, D), dt), ((N, page, H, D), dt),
        ((N, page, H, D), dt), ((S, P), jnp.int32), ((S,), jnp.int32))
    ops = _kernel_op_names(text)
    assert len(ops) == 1 and "ragged_paged_attention/" in ops[0]
    assert [grid for _, grid in _kernel_grids(text)] == [
        (S,) if walks else (S, P)]


def test_ragged_paged_attention_chunk_compiles(one_chip):
    from paddle_tpu.decode import attention as A

    S, T, H, D, page, N, P, dt = 8, 4, 16, 128, 16, 2048, 32, jnp.bfloat16
    text = _compiled_text(
        A.ragged_paged_attention_chunk, one_chip,
        ((S, T, H, D), dt), ((N, page, H, D), dt), ((N, page, H, D), dt),
        ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text
    assert all("ragged_paged_attention_chunk/" in op
               for op in _kernel_op_names(text))
    # the walk: a slot a grid step, not a (slot, table column)
    assert [grid for _, grid in _kernel_grids(text)] == [(S,)]


# (slots, query heads, stored K/V heads, key lanes, value lanes, pages a
# sequence, pages): the cells' full layers on row-major bf16 pages of 128
GQA_SHAPES = {
    "k_exaone": (64, 64, 8, 128, 128, 36, 3073),
    "mimo": (48, 64, 4, 256, 128, 256, 7656),       # keys at 256 lanes
    "packed": (64, 32, 4, 128, 128, 192, 6600),     # LFM2, Granite
}


@pytest.mark.parametrize("cell, T, q", [
    ("k_exaone", 1, jnp.bfloat16), ("k_exaone", 4, jnp.bfloat16),
    ("mimo", 1, jnp.bfloat16), ("packed", 1, jnp.bfloat16),
    ("mimo", 4, jnp.float32)],
    ids=["step", "chunk", "mimo_step", "packed_step", "mimo_chunk_f32_q"])
def test_ragged_paged_attention_gqa_compiles(one_chip, cell, T, q):
    """The grouped walk on the pages as they are stored (PR 63: a turn's
    pages a softmax update on bfloat16 operands) at the serving shapes:
    64 query heads on 8 K/V heads of 128 (K-EXAONE's full layer), 64 on 4
    with keys of 256 lanes on values of 128 (MiMo-V2.5), 32 widened heads
    on 4 stored rows of two (LFM2, Granite); the decode step's row, a
    chunk of four, and a float32 query, whose three exact bfloat16 parts
    ride as further rows."""
    from paddle_tpu.decode import attention as A

    S, Hq, Hkv, D, Dv, P, N = GQA_SHAPES[cell]
    page, dt = 128, jnp.bfloat16
    assert A.fits(page, Hq, D, Hkv) and A.walk_fits(dt, page, Hkv, D)
    assert A.page_form(dt, dt, False, T * Hq // Hkv) == "stored"
    text = _compiled_text(
        A.ragged_paged_attention_gqa, one_chip,
        ((S, T, Hq, D), q), ((N, page, Hkv, D), dt),
        ((N, page, Hkv, Dv), dt), ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text
    assert all("ragged_paged_attention_gqa/" in op
               for op in _kernel_op_names(text))
    assert [grid for _, grid in _kernel_grids(text)] == [(S,)]


@pytest.mark.parametrize("T", [1, 4], ids=["step", "chunk"])
def test_ring_paged_attention_compiles(one_chip, T):
    """A window layer's rings at the Phi-4-mini-flash serving shape: 64
    slots of 40 query heads on 10 stored heads of 128, heads-major bf16
    pages of 128 rows among 7,041, rings of five pages under a window of
    512; the decode step's row and a chunk of four.  The custom call
    carries the ring kernel's own name and not the grouped one's, which
    ``perf/layer_metrics/attn_full_roofline.py`` counts against the
    full layers' bytes."""
    import functools

    from paddle_tpu.decode import attention as A

    S, Hq, Hkv, D, page, N, R, window, dt = 64, 40, 10, 128, 128, 7041, \
        5, 512, jnp.bfloat16
    assert A.fits(page, Hq, D, Hkv)
    text = _compiled_text(
        functools.partial(A.ring_paged_attention, window=window,
                          heads_major=True), one_chip,
        ((S, T, Hq, D), dt), ((N, Hkv, page, D), dt),
        ((N, Hkv, page, D), dt), ((S, R), jnp.int32), ((S,), jnp.int32))
    ops = _kernel_op_names(text)
    assert len(ops) == 1 and "ring_paged_attention/" in ops[0]
    assert "ragged_paged_attention_gqa" not in ops[0]
    # the ring keeps a grid step a (slot, ring column)
    assert [grid for _, grid in _kernel_grids(text)] == [(S, R)]


def test_gated_delta_chunked_compiles(one_chip):
    """The prefill's kernel alone at the cell's shape (30 heads, d_k 96,
    d_v 192) over the 4,608-row bucket: Mosaic takes the 96-deep
    contractions, the 192-wide values and the turn of ``kT``'s block."""
    from paddle_tpu.pallas import gated_delta_chunked as gdc

    T, H, dk, dv = 4608, 30, 96, 192
    assert gdc.fits(jnp.float32, T, H, dv, dk)
    text = _compiled_text(
        gdc.gated_delta_chunked, one_chip, ((T, H, dk), jnp.float32),
        ((T, H, dk), jnp.float32), ((T, H, dv), jnp.float32),
        ((T, H), jnp.float32), ((T, H), jnp.float32),
        ((H, dv, dk), jnp.float32))
    names = _kernel_op_names(text)
    assert len(names) == 1 and "gated_delta_chunked/pallas_call" in names[0]


def _latent_kernel(one_chip, lanes, T=1):
    """``latent_paged_attention`` alone at the cell's shape (64 slots x
    64 columns of 128-row pages, 32 heads, the value the first 512
    lanes) on rows of ``lanes`` lanes -> the compiled program."""
    from paddle_tpu.pallas import latent_attention as la

    S, H, P, pg, N = 64, 32, 64, 128, 16 * 3971

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def call(q, pages, tables, lens):
        return la.latent_paged_attention(q, pages, tables, lens, heads=H,
                                         v_width=512, scale=192 ** -0.5)

    return jax.jit(call).lower(
        sds((S, T * H, lanes), jnp.bfloat16),
        sds((N, pg, lanes), jnp.bfloat16), sds((S, P), jnp.int32),
        sds((S,), jnp.int32)).compile()


@pytest.mark.parametrize("T", [1, 4], ids=["step", "chunk"])
def test_latent_paged_attention_compiles(one_chip, T):
    """At the cell's real shape, a decode step's and a verify chunk's:
    the pool stays where it lies (it is the kernel's HBM operand: no
    temporary at all), the output is the value's 512 lanes."""
    compiled = _latent_kernel(one_chip, 640, T)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes == 0
    assert m.output_size_in_bytes == 64 * T * 32 * 512 * 2
    ops = _kernel_op_names(compiled.as_text())
    assert len(ops) == 1 and "latent_paged_attention" in ops[0]


def test_latent_rows_of_576_lanes_are_laid_out_at_640_and_refused(one_chip):
    """The layout probe that settled the stored width (PR 45).  The
    algorithm's row is 512 + 64 = 576 numbers; a pool whose minor
    dimension is 576 is laid out by the chip's compiler in tiles of 128
    lanes, five a row (``...x128x640xbf16`` in Mosaic's own words), so
    it takes a 640-lane pool's bytes anyway, and the kernel's copy of
    one page out of it is refused: a 576-lane slice is not aligned to
    the tiling.  So the rows are stored at 640 lanes, zeros behind
    ``k^r``, and ``fits()`` says no to anything else."""
    from paddle_tpu.pallas import latent_attention as la

    assert not la.fits(jnp.bfloat16, 128, 32, 576, 512)
    assert la.fits(jnp.bfloat16, 128, 32, 640, 512)
    with pytest.raises(Exception) as e:
        _latent_kernel(one_chip, 576)
    said = str(e.value)
    assert "must be aligned to tiling (128), but is 576" in said
    assert "x128x640xbf16" in said          # how the 576-lane pool lies


def test_s6_step_compiles(one_chip):
    """The kernel alone at the published entry (state 16 down, 5,120
    channels along the lanes) float32, 64 slots on 65 entries of one
    layer: one block a slot, the pool aliased."""
    from paddle_tpu.pallas import s6_step as s6

    S, E, N, C = 64, 65, 16, 5120
    text = _compiled_text(
        lambda pool, at, dt, x, A, B, Cc: s6.s6_step(pool, at, dt, x, A,
                                                     B, Cc),
        one_chip, ((E, N, C), jnp.float32), ((S,), jnp.int32),
        ((S, C), jnp.float32), ((S, C), jnp.float32),
        ((N, C), jnp.float32), ((S, N), jnp.float32),
        ((S, N), jnp.float32))
    assert s6.channel_block(N, C) == C
    assert [op.split("/")[-2] for op in _kernel_op_names(text)] == [
        "s6_step"]
    assert "output_to_operand_aliasing={{1}: (6, {})}" in text


def test_ssd_step_compiles(one_chip):
    """The kernel alone at the published entry (64 heads of 64
    channels, state 128: 32 rows of two heads, 128 down, 128 lanes)
    float32, 64 slots on 65 entries of one layer: two blocks of 16 rows
    a slot, the pool aliased."""
    from paddle_tpu.pallas import ssd_step as ssd

    S, E, R, N, lanes = 64, 65, 32, 128, 128
    text = _compiled_text(
        lambda pool, at, a, x, B, C: ssd.ssd_step(pool, at, a, x, B, C),
        one_chip, ((E, R, N, lanes), jnp.float32), ((S,), jnp.int32),
        ((S, R, lanes), jnp.float32), ((S, R, lanes), jnp.float32),
        ((S, N), jnp.float32), ((S, N), jnp.float32))
    assert ssd.head_block(R, N, lanes) == 16
    assert [op.split("/")[-2] for op in _kernel_op_names(text)] == [
        "ssd_step"]


@pytest.mark.parametrize("channels, slots, bias", [
    (4352, 64, True), (11520, 48, False), (5120, 64, True)],
    ids=["granite", "olmo_hybrid", "phi4_flash"])
def test_conv_step_compiles(one_chip, channels, slots, bias):
    """The kernel alone at both cells' shapes, bfloat16: a slot a grid
    step on an entry of 3 x C / 128 rows of lanes (102 / 270: tap j
    starts at no tile's edge; 120 at 5,120 channels, where it does), the
    pool aliased."""
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.pallas import conv_step as cs

    bf, E = jnp.bfloat16, slots + 1
    entry = tail_shape(4, channels)
    assert cs.fits(bf, entry, bf, 4, channels)
    shapes = [((E, *entry), bf), ((slots,), jnp.int32),
              ((slots, channels), bf), ((4, channels), bf)]
    if bias:
        shapes.append(((channels,), bf))
    text = _compiled_text(
        lambda pool, at, row, w, b=None: cs.conv_step(pool, at, row, w, b),
        one_chip, *shapes)
    assert [op.split("/")[-2] for op in _kernel_op_names(text)] == [
        "conv_step"]
    pool_operand = 4 if bias else 3
    assert (f"output_to_operand_aliasing={{{{1}}: ({pool_operand}, {{}})}}"
            in text)


def _flash_fwd(q, k, v):
    from paddle_tpu.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, True)


def _flash_bwd(q, k, v):
    return jax.grad(lambda *a: _flash_fwd(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles(one_chip, grad):
    qkv = [((384, 1024, 128), jnp.bfloat16)] * 3
    text = _compiled_text(_flash_bwd if grad else _flash_fwd, one_chip, *qkv)
    assert text.count(MARKER) >= (2 if grad else 1)
    # forward and backward can be told apart by the kernels' own names,
    # beside the jitted wrappers' that flash_attn_ms_per_step matches
    ops = _kernel_op_names(text)
    fwd_ops = [op for op in ops if "flash_attention_fwd/" in op]
    bwd_ops = [op for op in ops if "flash_attention_bwd_" in op]
    assert len(fwd_ops) == 1 and "_flash_fwd_impl" in fwd_ops[0]
    assert len(bwd_ops) == (2 if grad else 0)
    assert all("_flash_bwd_impl" in op for op in bwd_ops)
    assert {op.split("/")[-2] for op in bwd_ops} == (
        {"flash_attention_bwd_dq", "flash_attention_bwd_dkv"} if grad
        else set())


@pytest.mark.parametrize("shape, dtype, grad", [
    ((64, 2048, 128), jnp.bfloat16, True),
    ((32, 8192, 192), jnp.bfloat16, False),
    ((16, 1024, 128), jnp.float32, False),
], ids=["lm-train", "latent-prefill-8192", "cerebras-prefill-1024-f32"])
def test_flash_attention_compiles_at_the_cells_shapes(one_chip, shape, dtype,
                                                      grad):
    """The pair the residency model admits at the shapes a cell runs
    compiles (PR 46: the model counts the operands' itemsize, a head's
    whole lanes and every kernel's own blocks): the LM step's forward +
    backward, the latent cell's top bucket at heads of 192, and the
    Cerebras generate cell's float32 1,024-row bucket."""
    from paddle_tpu.pallas import flash_attention as fa

    _, S, D = shape
    item = jnp.dtype(dtype).itemsize
    for kernel in fa.KERNELS:
        pair = fa._resolve_blocks(S, S, D, item, kernel=kernel)
        assert fa._blocks_ok(S, S, D, *pair, item, kernel), (kernel, pair)
    text = _compiled_text(_flash_bwd if grad else _flash_fwd, one_chip,
                          *[(shape, dtype)] * 3)
    names = sorted(op.split("/")[-2] for op in _kernel_op_names(text))
    assert names == (["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                      "flash_attention_fwd"] if grad
                     else ["flash_attention_fwd"])


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_lstm_compiles(one_chip, grad):
    from paddle_tpu.pallas.lstm import lstm_seq

    T, B, H = 100, 64, 256

    def fwd(x, w, b, h0, c0):
        return lstm_seq(x, w, b, h0, c0)[0]

    def bwd(x, w, b, h0, c0):
        return jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))(
            x, w, b, h0, c0)

    f32 = jnp.float32
    text = _compiled_text(
        bwd if grad else fwd, one_chip, ((T, B, 4 * H), f32),
        ((H, 4 * H), f32), ((4 * H,), f32), ((B, H), f32), ((B, H), f32))
    assert MARKER in text


def test_softmax_compiles(one_chip):
    from paddle_tpu.pallas.softmax import softmax

    text = _compiled_text(softmax, one_chip, ((4096, 256), jnp.float32))
    assert MARKER in text


def test_sparse_latent_kernels_compile_at_the_cells_shapes(one_chip):
    """The four kernels alone (``pallas/sparse_latent.py``).  The
    layout probe that settled the index pool: rows of 128 lanes are one
    tile, the kernel's page copy out of the pool seen as (layers x pages,
    128, 128) plans no temporary, so the index rows lie in the
    skeleton's second pool as they are.  The prefill's three at a bucket
    (8,192 x 8,192) and at the traffic's largest chunk (4,096 over
    24,576): no temporary either; the selection (PR 54) holds 64 query
    rows over the whole key width, at a sequence's 25,600 rows too."""
    import functools

    from paddle_tpu.pallas import sparse_latent as sl

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf16 = jnp.bfloat16
    scores = jax.jit(sl.paged_index_scores).lower(
        sds((32, 32, 128), bf16), sds((32, 32), jnp.float32),
        sds((5 * 4757, 128, 128), bf16), sds((32, 200), jnp.int32),
        sds((32,), jnp.int32)).compile()
    m = scores.memory_analysis()
    # (the weights' (32, 32, 1) column laid out in tiles: 512 KB)
    assert m.temp_size_in_bytes <= 1 << 20
    assert m.output_size_in_bytes == 32 * 25600 * 4
    ops = _kernel_op_names(scores.as_text())
    assert len(ops) == 1 and "paged_index_scores" in ops[0]
    assert sl.fetch_pages(200) == 25            # 3,200 rows a turn
    for T, n in ((8192, 8192), (4096, 24576)):
        dense = jax.jit(sl.index_scores).lower(
            sds((32, T, 128), bf16), sds((T, 32), jnp.float32),
            sds((n, 128), bf16), sds((1,), jnp.int32)).compile()
        assert dense.memory_analysis().temp_size_in_bytes == 0
        flash = jax.jit(functools.partial(
            sl.selected_flash_attention, scale=1 / 16)).lower(
            sds((64, T, 256), bf16), sds((64, n, 256), bf16),
            sds((64, n, 256), bf16), sds((T, n), bf16),
            sds((1,), jnp.int32)).compile()
        assert flash.memory_analysis().temp_size_in_bytes == 0
        assert flash.memory_analysis().output_size_in_bytes \
            == 64 * T * 256 * 2
    for T, n in ((8192, 8192), (4096, 24576), (4096, 25600)):
        assert sl.selection_fits(T, n, bf16)
        assert sl.selection_rows(T, n, 2) == 64
        select = jax.jit(functools.partial(
            sl.selection_bias, k=2048, dtype=bf16)).lower(
            sds((T, n), jnp.float32), sds((1,), jnp.int32)).compile()
        assert select.memory_analysis().temp_size_in_bytes == 0
        assert select.memory_analysis().output_size_in_bytes == T * n * 2
        ops = _kernel_op_names(select.as_text())
        assert len(ops) == 1 and "selection_bias" in ops[0]


def test_kda_kernels_compile_at_the_cells_shapes(one_chip):
    """The two kernels of ``pallas/kda.py`` alone at the cell's shapes
    (32 heads, d_k = d_v = 128): the chunked rule over the 8,192-row
    top bucket (Mosaic takes the eight row blocks' single-row slices at
    the blocks' middles, the lane slices of the transposed ``G`` and
    the solve's rolls), and the step over 128 slots on a pool of five
    layers' 129 entries, the pool aliased input to output."""
    from paddle_tpu.pallas import kda

    T, H, dk, dv = 8192, 32, 128, 128
    f32 = jnp.float32
    assert kda.chunked_fits(f32, T, H, dv, dk, -5.0)
    text = _compiled_text(
        kda.kda_chunked, one_chip, ((T, H, dk), f32), ((T, H, dk), f32),
        ((T, H, dv), f32), ((T, H, dk), f32), ((T, H), f32),
        ((H, dv, dk), f32))
    names = _kernel_op_names(text)
    assert len(names) == 1 and "kda_chunked/pallas_call" in names[0]
    S, N = 128, 5 * 129
    assert kda.step_fits(f32, H, dv, 128)
    text = _compiled_text(
        kda.kda_step, one_chip, ((N, H, dv, 128), f32), ((S,), jnp.int32),
        ((S, H, 128), f32), ((S, H, 128), f32), ((S, H, dv), f32),
        ((S, H, 128), f32), ((S, H), f32))
    names = _kernel_op_names(text)
    assert len(names) == 1 and "kda_step/pallas_call" in names[0]
    assert "output_to_operand_aliasing={{1}: (6, {})}" in text
