"""The grouped GEMM kernel (``paddle_tpu/pallas/grouped_gemm.py``)
against ``jax.lax.ragged_dot``, its reference, interpreted on the CPU;
its walk over (row tile, group) visits against a plain count; and the
routed layer of the toy OLMoE and K-EXAONE models with the kernel on
against the kernel off.

Tolerances: float32 operands are multiplied at full precision on both
sides and differ in the order of a 128-term sum (1e-5); bfloat16
operands are exact in float32 and the sums are float32 (1e-5 again);
``gate_up`` rounds its result to the rows' dtype, so a float32
difference in the last place can move a bfloat16 value by one step
(2 ** -7).
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from paddle_tpu import pallas as pk  # noqa: E402
from paddle_tpu.models import moe  # noqa: E402
from paddle_tpu.pallas import grouped_gemm as gg  # noqa: E402

M, K, N, TM = 64, 128, 256, 16


def _clipped(sizes, block, B=M):
    """A block's group sizes as ``moe._grouped_held_experts`` clips a
    layer's to it."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    lo = block * B
    return list(np.clip(ends, lo, lo + B) - np.clip(starts, lo, lo + B))


# name -> rows a group of the M sorted rows, walked in tiles of TM
SIZES = {
    "boundary_inside_a_tile": [10, 30, 24],
    "boundaries_on_tile_edges": [16, 32, 16],
    "a_group_over_several_tiles": [50, 14],
    "empty_group_first": [0, 20, 44],
    "empty_group_in_the_middle": [20, 0, 44],
    "empty_group_last": [20, 44, 0],
    "empty_groups_between_small_ones": [0, 5, 0, 0, 7, 0],
    "all_rows_in_one_group": [0, M, 0],
    "rows_behind_the_last_group": [10, 12, 3],
    "one_row": [0, 1],
    "clipped_to_the_first_block": _clipped([40, 50, 30, 60], 0),
    "clipped_to_a_middle_block": _clipped([40, 50, 30, 60], 1),
    "clipped_to_the_last_block": _clipped([40, 50, 30, 60], 2),
}


def _operands(dtype, C, seed=0):
    rng = np.random.RandomState(seed)
    xs = jnp.asarray(rng.randn(M, K), dtype)
    ws = [jnp.asarray(rng.randn(C, K, N) * 0.1, dtype) for _ in "gu"]
    return xs, ws


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(SIZES))
def test_kernel_equals_ragged_dot(case, dtype):
    sizes = SIZES[case]
    n = int(sum(sizes))
    xs, (w, _) = _operands(dtype, len(sizes))
    sizes = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.float32)
    got = gg.grouped_gemm(xs, w, sizes, tm=TM, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (M, N)
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-5, atol=1e-5)
    # the column blocks and the shape's own row tile change nothing
    for tiles in (dict(tm=TM, tn=128), dict()):
        again = gg.grouped_gemm(xs, w, sizes, interpret=True, **tiles)
        np.testing.assert_array_equal(again[:n], got[:n])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", list(SIZES))
def test_gate_up_is_the_swiglu_of_two_ragged_dots(case, dtype):
    sizes = SIZES[case]
    n = int(sum(sizes))
    xs, (wg, wu) = _operands(dtype, len(sizes), seed=1)
    sizes = jnp.asarray(sizes, jnp.int32)
    g, u = (jax.lax.ragged_dot(xs, w, sizes,
                               preferred_element_type=jnp.float32)
            for w in (wg, wu))
    want = (jax.nn.silu(g) * u).astype(dtype)
    got = gg.gate_up(xs, wg, wu, sizes, tm=TM, interpret=True)
    assert got.dtype == dtype and got.shape == (M, N)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[:n].astype(jnp.float32),
                               want[:n].astype(jnp.float32),
                               rtol=step, atol=1e-5)


@pytest.mark.parametrize("case", list(SIZES))
def test_the_walk_visits_each_overlapping_pair_once_in_order(case):
    sizes = np.asarray(SIZES[case])
    ends = np.minimum(np.cumsum(sizes), M)
    starts = np.concatenate([[0], ends[:-1]])
    want = [(t, g) for g in range(len(sizes)) for t in range(M // TM)
            if max(starts[g], t * TM) < min(ends[g], (t + 1) * TM)]
    tile, group, then, s, e, count = map(np.asarray, gg.visits(
        jnp.asarray(sizes, jnp.int32), M, TM))
    assert int(count) == len(want) <= len(tile) == M // TM + len(sizes) - 1
    assert list(zip(tile[:count], group[:count])) == want
    np.testing.assert_array_equal(s, starts)
    np.testing.assert_array_equal(e, ends)
    # the group whose matrix a group's first visit asks for: the next
    # one that has rows, -1 behind the last
    hit = [g for g in range(len(sizes)) if ends[g] > starts[g]]
    after = dict(zip(hit, hit[1:] + [-1]))
    assert list(then[:count]) == [after[g] for _, g in want]
    # behind the last visit the walk stands still: nothing new is fetched
    if count:
        assert set(zip(tile[count:], group[count:])) <= {want[-1]}
    # no tile behind the last group's end is ever visited
    assert all(t * TM < ends[-1] for t, _ in want)


@pytest.mark.parametrize("n, dtype", [(768, jnp.bfloat16), (1024, jnp.bfloat16),
                                      (768, jnp.float32)])
def test_a_visit_covers_its_block_in_column_chunks(n, dtype):
    """A block wider than ``COL_CHUNK`` is multiplied in chunks that
    divide it (768 columns, Kanana's expert width, in two of 384): every
    column of the block comes out, the same as in one product."""
    assert gg.col_chunk(n) < n and n % gg.col_chunk(n) == 0
    rng = np.random.RandomState(4)
    sizes = jnp.asarray([30, 0, 34], jnp.int32)
    xs = jnp.asarray(rng.randn(M, K), dtype)
    wg, wu = (jnp.asarray(rng.randn(3, K, n) * 0.1, dtype) for _ in "gu")
    g, u = (jax.lax.ragged_dot(xs, w, sizes,
                               preferred_element_type=jnp.float32)
            for w in (wg, wu))
    np.testing.assert_allclose(
        gg.grouped_gemm(xs, wg, sizes, tm=TM, interpret=True), g,
        rtol=1e-5, atol=1e-5)
    step = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        gg.gate_up(xs, wg, wu, sizes, tm=TM, interpret=True).astype(
            jnp.float32),
        (jax.nn.silu(g) * u).astype(dtype).astype(jnp.float32),
        rtol=step, atol=1e-5)


@pytest.mark.parametrize("shape, want", [
    ((jnp.bfloat16, jnp.bfloat16, 2304, 6144, 2048), True),   # K-EXAONE
    ((jnp.bfloat16, jnp.bfloat16, 4096, 2048, 1024), True),   # OLMoE
    ((jnp.bfloat16, jnp.bfloat16, 1024, 2048, 768), True),    # Kanana
    ((jnp.float32, jnp.float32, 512, 128, 128), True),
    ((jnp.float32, jnp.bfloat16, 512, 128, 128), False),      # mixed
    ((jnp.float16, jnp.float16, 512, 128, 128), False),
    ((jnp.float32, jnp.float32, 896, 16, 12), False),         # toy widths
    ((jnp.bfloat16, jnp.bfloat16, 512, 128, 96), False),      # part lanes
    ((jnp.float32, jnp.float32, 600, 128, 128), False),       # part tiles
    ((jnp.bfloat16, jnp.bfloat16, 32, 6144, 2048), False),    # a few slots
    ((jnp.bfloat16, jnp.bfloat16, 128, 6144, 2048), True),    # one tile
    ((jnp.bfloat16, jnp.bfloat16, 0, 128, 128), False),
    ((jnp.bfloat16, jnp.bfloat16, 128, 65536, 2048), False),  # never resident
])
def test_fits_is_a_rule_of_static_shape_and_dtype(shape, want):
    assert gg.fits(*shape) is want
    assert moe.GROUPED_ROW_TILE % gg.ROW_TILE == 0


@pytest.fixture
def kernels():
    """``pallas.enable`` restored, and nothing traced under one mode
    served to the other (the mode is no part of a jitted program's
    key)."""
    was = dict(pk._STATE)
    jax.clear_caches()
    yield pk.enable
    pk._STATE.update(was)
    jax.clear_caches()


def _primitive_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _primitive_names(sub)
    return out


def _olmoe_layer():
    from paddle_tpu.models.olmoe import OlmoeLM

    model = OlmoeLM(seed=5, vocab=101, d_model=128, num_heads=4,
                    num_layers=2, num_experts=8, experts_per_tok=2,
                    expert_width=128, max_len=128, num_pages=24,
                    page_size=8, pages_per_seq=8, dtype="float32",
                    eos_id=-1)
    lp = model.params["layers"][1]
    return lp, dict(top_k=model.block.top_k)


def _exaone_layer():
    from paddle_tpu.models.exaone_moe import FULL, SLIDING, ExaoneMoeLM

    model = ExaoneMoeLM(
        seed=3, vocab=80, d_model=128, num_heads=8, num_kv_heads=2,
        head_dim=16, layer_types=(SLIDING, FULL),
        mlp_layer_types=("dense", "sparse"), sliding_window=8,
        dense_width=48, expert_width=128, num_experts_published=16,
        held_experts=(4, 4), experts_per_tok=3, max_len=64, num_pages=80,
        page_size=4, pages_per_seq=16, dtype="float32")
    lp = model.params["layers"][1]
    return lp, dict(top_k=model.block.top_k, held=model.block.held,
                    scores=moe.sigmoid_scores(lp["b"] * 5,
                                              model.block.scale))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", [_olmoe_layer, _exaone_layer],
                         ids=["olmoe", "k_exaone"])
def test_routed_layer_with_the_kernel_on_equals_the_kernel_off(
        kernels, layer, dtype):
    """A 320-row call of the toy model's routed layer (the grouped
    path; K-EXAONE's share loops over blocks, OLMoE's is one pass) with
    the kernel interpreted against ``ragged_dot``: the same sum, load
    and count of assignments held elsewhere, and the kernel's calls in
    the traced program where the reference's were."""
    lp, kw = layer()
    R = moe.DENSE_MAX_ROWS + 64
    rng = np.random.RandomState(9)
    m = jnp.asarray(rng.randn(R, lp["wr"].shape[0]), dtype)
    live = jnp.asarray(np.arange(R) % 4 != 0)
    w3 = [lp[n].astype(dtype) for n in ("w_gate", "w_up", "w_down")]
    assert moe.expert_path(R, kw["top_k"], lp["wr"].shape[1]) == "grouped"

    def run(m, live):
        return moe.routed_experts(m, lp["wr"].astype(dtype), *w3, live=live,
                                  **kw)

    got = {}
    for name, mode in (("off", False), ("on", True)):
        kernels(mode, interpret=True)
        jax.clear_caches()
        names = _primitive_names(jax.make_jaxpr(run)(m, live).jaxpr)
        assert names.count("pallas_call") == (2 if mode else 0)
        assert names.count("ragged_dot_general") == (0 if mode else 3)
        got[name] = run(m, live)
    (y, load, away), (y0, load0, away0) = got["on"], got["off"]
    np.testing.assert_array_equal(load, load0)
    assert int(away) == int(away0) and int(load.sum()) > 0
    step = 2.0 ** -6 if dtype == jnp.bfloat16 else 1e-5
    scale = float(jnp.max(jnp.abs(y0)))
    assert scale > 0
    np.testing.assert_allclose(y, y0, rtol=step, atol=step * scale)
