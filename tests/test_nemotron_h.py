"""Nemotron-H (``paddle_tpu/models/nemotron_h.py``) at toy widths on the
CPU, float32: layers that are ONE part alone through the skeleton's
programs, Mamba-2 over groups of B and C, the grouped norm, experts of
two matrices under relu^2 on every way ``models/moe.py`` computes the
sum, an expert's matrices stored at whole lanes, and the model through
both caches against its plain reference
(``perf/reference/nemotron_h_block.py``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hybrid_models import lowered_texts, prompt, through_the_cache
from paddle_tpu import pallas as pk
from paddle_tpu.decode import model as dm
from paddle_tpu.decode.state_entry import UnsupportedOverState
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import moe
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.pallas import grouped_gemm as gg
from paddle_tpu.pallas import ssd_step as ssd
from perf.reference import nemotron_h_block as ref

# toy widths that keep the shapes' ratios: every kind of layer, attention
# heads of 128 on fewer K/V heads, eight mamba heads of 64 channels in
# four groups (two heads a row of lanes, one row a group), a state of
# 128, experts whose width is no whole tile of lanes
TOY = dict(vocab=96, d_model=32, num_heads=4, num_kv_heads=2, head_dim=128,
           pattern="MEM*EME", mamba_num_heads=8, mamba_head_dim=64,
           ssm_state_size=128, n_groups=4, expert_width=24, shared_width=48,
           num_experts_published=16, held_experts=(4, 8), experts_per_tok=3,
           max_len=128, num_pages=40, page_size=8, pages_per_seq=32,
           state_entries=5, dtype="float32")


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return nh.NemotronHLM(seed=3, **TOY)


def reference(m, ids, rows=None, ablate=None, held=None, **also):
    b = m.block
    out = ref.forward(
        m.params, jnp.asarray(ids, jnp.int32), layer_types=b.layer_types,
        num_heads=m.heads, head_dim=b.head_dim,
        mamba_n_heads=b.mamba_n_heads, mamba_d_head=b.mamba_d_head,
        mamba_d_state=b.mamba_d_state, mamba_n_groups=b.mamba_n_groups,
        top_k=b.top_k, scale=b.scale, held=held or b.held, eps=b.eps,
        ablate=ablate, rows=rows, **also)
    return jax.tree.map(np.asarray, out)


# -- B and C by group ---------------------------------------------------------


def _rows(T, seed, H=8, P=8, N=16, G=4):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (T, H))),
            -jnp.exp(jax.random.normal(k[2], (T, H)) - 2),
            jax.random.normal(k[3], (T, G, N)),
            jax.random.normal(k[4], (T, G, N)),
            jax.random.normal(k[5], (H, P, N)))


@pytest.mark.parametrize("T", [1, 127, 129, 300])
def test_chunked_scan_over_groups_is_one_group_recurrences_side_by_side(T):
    """``G = 4`` equals four one-group recurrences over the heads of each
    group."""
    x, dt, g, B, C, S = _rows(T, T)
    y, last = gh.chunked_ssd(x, dt, g, B, C, S)
    for grp in range(4):
        h = slice(2 * grp, 2 * grp + 2)
        y1, last1 = gh.chunked_ssd(x[:, h], dt[:, h], g[:, h], B[:, grp],
                                   C[:, grp], S[h])
        np.testing.assert_allclose(y[:, h], y1, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(last[h], last1, rtol=1e-5, atol=1e-5)


def test_one_group_is_bit_for_bit_what_the_ungrouped_call_gives():
    """``G = 1`` handed in as a group axis of one is Granite's call to
    the bit (and Granite itself hands in no group axis: its programs
    are what they were)."""
    x, dt, g, B, C, S = _rows(200, 7, G=1)
    y, last = gh.chunked_ssd(x, dt, g, B, C, S)
    y0, last0 = gh.chunked_ssd(x, dt, g, B[:, 0], C[:, 0], S)
    assert np.array_equal(y, y0) and np.array_equal(last, last0)
    ys, new = gh.step_ssd(x[0], dt[0], g[0], B[0], C[0], S)
    ys0, new0 = gh.step_ssd(x[0], dt[0], g[0], B[0, 0], C[0, 0], S)
    assert np.array_equal(ys, ys0) and np.array_equal(new, new0)


def test_one_token_step_over_groups_is_a_row_of_the_recurrence():
    x, dt, g, B, C, S = _rows(5, 11)
    want_y, want_S = gh.chunked_ssd(x, dt, g, B, C, S)
    for t in range(5):
        y, S = gh.step_ssd(x[t], dt[t], g[t], B[t], C[t], S)
        np.testing.assert_allclose(y, want_y[t], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-5, atol=1e-5)


# rows of heads R, groups G: the published 32 rows in 8 groups (a block
# of 16 rows is four whole groups), a block inside one group, one group
_STEP_CASES = [(32, 8), (32, 2), (8, 4), (4, 1)]


@pytest.mark.parametrize("R, G", _STEP_CASES)
def test_ssd_step_over_groups_is_the_step_on_the_gathered_entries(R, G):
    """The kernel interpreted against ``step_ssd`` at the new shapes."""
    N, lanes, S, E = 128, 128, 3, 5
    assert ssd.fits(jnp.float32, R, N, lanes, G)
    k = jax.random.split(jax.random.key(R * G), 6)
    pool = jax.random.normal(k[0], (E, R, N, lanes))
    at = jnp.asarray([3, 1, 4], jnp.int32)
    H, P = 2 * R, lanes // 2            # as published: two heads a row
    g = -jax.nn.softplus(jax.random.normal(k[1], (S, H)))
    # a decay a head, spread over the head's lanes
    a = jnp.repeat(jnp.exp(g), P, -1).reshape(S, R, lanes)
    x = jax.random.normal(k[2], (S, R, lanes))
    shape = (S, N) if G == 1 else (S, G, N)
    B, C = jax.random.normal(k[3], shape), jax.random.normal(k[4], shape)
    y, out = ssd.ssd_step(pool, at, a, x, B, C, interpret=True)
    state = gh.unpack_state(pool[at], 2)                   # (S, H, P, N)
    want_y, new = gh.step_ssd(x.reshape(S, H, P), jnp.ones((S, H)), g, B, C,
                              state)
    np.testing.assert_allclose(
        y.reshape(S, H, P), want_y, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[at], gh.pack_state(new, 2), rtol=2e-5,
                               atol=2e-5)
    untouched = np.setdiff1d(np.arange(E), np.asarray(at))
    assert np.array_equal(out[untouched], pool[untouched])


def test_ssd_step_refuses_a_block_that_straddles_a_group():
    assert ssd.fits(jnp.float32, 32, 128, 128, 8)
    assert not ssd.fits(jnp.float32, 32, 128, 128, 3)
    # 40 rows of heads run in blocks of 20: groups of 8 rows straddle
    assert ssd.head_block(40, 128, 128) == 20
    assert not ssd.fits(jnp.float32, 40, 128, 128, 5)
    assert ssd.fits(jnp.float32, 40, 128, 128, 4)


def test_the_grouped_norm_norms_each_group_on_its_own():
    block = nh.NemotronHBlock(mamba_n_heads=8, mamba_d_head=4,
                              mamba_n_groups=4)
    k = jax.random.split(jax.random.key(5), 4)
    y, xs = jax.random.normal(k[0], (3, 8, 4)), jnp.zeros((3, 8, 4))
    z = jax.random.normal(k[1], (3, 32))
    lp = {"D": jnp.ones((8,)), "w_norm": jax.random.normal(k[2], (32,))}
    got = block._gated_norm(lp, y, xs, z)
    v = (y.reshape(3, 32) * jax.nn.silu(z)).reshape(3, 4, 8)
    want = (v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + block.eps)
            ).reshape(3, 32) * lp["w_norm"]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    whole = nh.NemotronHBlock(mamba_n_heads=8, mamba_d_head=4,
                              mamba_n_groups=1)._gated_norm(lp, y, xs, z)
    assert not np.allclose(got, whole, atol=1e-3)


# -- experts of two matrices under relu^2 -------------------------------------


def _experts(seed, R=48, d=32, f=24, E=16, C=16):
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (R, d)),
            jax.random.normal(k[1], (d, E)),
            jax.random.normal(k[2], (C, d, f)) * d ** -0.5,
            jax.random.normal(k[3], (C, f, d)) * f ** -0.5,
            jax.random.normal(k[4], (E,)) * 0.1)


def _loop_over_experts(m, wr, w_up, w_down, b, top_k, held):
    """The sum as it is written: each row, each of its chosen experts."""
    w, idx = moe.route(m, wr, top_k, moe.sigmoid_scores(b, 2.5, 1e-20))
    first, C = held
    y = np.zeros(m.shape, np.float32)
    for r in range(m.shape[0]):
        for j in range(top_k):
            e = int(idx[r, j]) - first
            if 0 <= e < C:
                h = np.square(np.maximum(np.asarray(m[r] @ w_up[e]), 0.0))
                y[r] += float(w[r, j]) * np.asarray(h @ w_down[e])
    return y


# (rows, top_k, held): the three ways moe.py computes the sum
_WAYS = [(48, 3, (0, 16), "dense"), (4, 2, (0, 16), "grouped"),
         (300, 3, (0, 16), "grouped"), (16, 1, (4, 8), "grouped"),
         (48, 3, (4, 8), "dense")]


@pytest.mark.parametrize("R, top_k, held, path", _WAYS)
def test_the_relu2_sum_is_a_loop_over_experts(R, top_k, held, path):
    m, wr, w_up, w_down, b = _experts(R + top_k, R=R, C=held[1])
    assert moe.expert_path(R, top_k, 16) == path
    y, load, elsewhere = moe.routed_experts(
        m, wr, w_up, w_down, top_k=top_k,
        scores=moe.sigmoid_scores(b, 2.5, 1e-20),
        held=None if held == (0, 16) else held, form=moe.RELU2)
    np.testing.assert_allclose(
        y, _loop_over_experts(m, wr, w_up, w_down, b, top_k, held),
        rtol=2e-4, atol=2e-4)
    assert int(load.sum()) + int(elsewhere) == R * top_k


def test_a_steps_192_assignments_run_as_one_block_of_two_row_tiles():
    """The serving step's shape: 32 slots x 6 of 128, 16 held.  The
    grouped way's block is 256 sorted rows, a whole number of the
    kernel's row tiles, of which the 192 assignments' held part are some
    group's and the rest no group's."""
    m, wr, w_up, w_down, b = _experts(64, R=32, E=128, C=16)
    assert moe.expert_path(32, 6, 128) == "grouped"
    assert moe.grouped_block_rows(32, 6, 16, 128) == 256
    live = jnp.arange(32) < 29
    y, load, elsewhere = moe.routed_experts(
        m, wr, w_up, w_down, top_k=6, live=live,
        scores=moe.sigmoid_scores(b, 2.5, 1e-20), held=(8, 16),
        form=moe.RELU2)
    want = _loop_over_experts(m, wr, w_up, w_down, b, 6, (8, 16))
    np.testing.assert_allclose(y[:29], want[:29], rtol=2e-4, atol=2e-4)
    assert not np.any(y[29:])              # a row that is not live: zeros
    assert int(load.sum()) + int(elsewhere) == 29 * 6


@pytest.mark.parametrize("R, top_k, held, path", _WAYS)
def test_64_zero_columns_change_nothing(R, top_k, held, path):
    """An expert stored at whole lanes: zero columns of ``W_up``, zero
    rows of ``W_down``; ``relu(0)^2 = 0``, exact."""
    m, wr, w_up, w_down, b = _experts(R, R=R, C=held[1])
    kw = dict(top_k=top_k, scores=moe.sigmoid_scores(b, 2.5, 1e-20),
              held=None if held == (0, 16) else held, form=moe.RELU2)
    y, load, _ = moe.routed_experts(m, wr, w_up, w_down, **kw)
    padded, load_p, _ = moe.routed_experts(
        m, wr, jnp.pad(w_up, ((0, 0), (0, 0), (0, 64))),
        jnp.pad(w_down, ((0, 0), (0, 64), (0, 0))), **kw)
    assert np.array_equal(load, load_p)
    np.testing.assert_allclose(padded, y, rtol=1e-6, atol=1e-6)


def test_the_model_stores_its_experts_at_whole_lanes(model):
    assert nh.stored_width(1856) == 1920 and nh.stored_width(24) == 128
    lp = model.params["layers"][1]
    assert lp["w_up"].shape == (8, 32, 128)
    assert lp["w_down"].shape == (8, 128, 32)
    assert not np.any(lp["w_up"][..., 24:]) and np.any(lp["w_up"][..., :24])
    assert not np.any(lp["w_down"][:, 24:])


@pytest.mark.parametrize("form, mats", [(moe.RELU2, 1), (moe.SWIGLU, 2)],
                         ids=["relu2", "swiglu"])
def test_the_grouped_gemm_epilogues_are_their_xla_references(form, mats):
    """The ``up`` call (the rectified product squared) and ``gate_up``
    interpreted, at 1,920 columns' shape in small: 384 columns, a group
    boundary inside a row tile, an expert nobody chose."""
    M, K, N, C = 256, 128, 384, 4
    k = jax.random.split(jax.random.key(9), 3)
    xs = jax.random.normal(k[0], (M, K))
    ws = [jax.random.normal(kk, (C, K, N)) * K ** -0.5
          for kk in jax.random.split(k[1], mats)]
    sizes = jnp.asarray([100, 0, 90, 40], jnp.int32)
    assert gg.fits(jnp.float32, jnp.float32, M, K, N)
    got = form.fused(xs, *ws, sizes, interpret=True)
    want = form.act(*(gg.grouped_gemm_reference(xs, w, sizes) for w in ws))
    np.testing.assert_allclose(got[:230], want[:230], rtol=2e-5, atol=2e-5)


def test_the_kernels_run_the_relu2_layer_interpreted():
    """``routed_experts`` through the Pallas calls interpreted (sorted
    rows in whole row tiles, lanes in whole tiles) is the XLA path."""
    m, wr, w_up, w_down, b = _experts(21, R=320, d=128, f=128)
    assert moe.expert_path(320, 2, 16) == "grouped"
    kw = dict(top_k=2, scores=moe.sigmoid_scores(b, 2.5, 1e-20),
              form=moe.RELU2)
    want, _, _ = moe.routed_experts(m, wr, w_up, w_down, **kw)
    fam = pk._M_DISPATCH
    before = fam.value(kernel="grouped_gemm", path="interpret")
    pk.enable(True, interpret=True)
    try:
        got, _, _ = moe.routed_experts(m, wr, w_up, w_down, **kw)
    finally:
        pk.enable("auto", interpret=False)
    assert fam.value(kernel="grouped_gemm", path="interpret") == before + 1
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_8_expert_shares_add_up_to_the_uncut_layer():
    """The shares of a routed layer that the chips of the expert-parallel
    group hold (here 4 shares of 4 experts), the shared expert counted
    once, add up to the uncut layer's output: by the block's own
    ``mlp`` and by the reference."""
    whole = nh.NemotronHLM(seed=5, **{**TOY, "held_experts": (0, 16)})
    lp = whole.params["layers"][1]
    x = jax.random.normal(jax.random.key(2), (40, 32))
    uncut, _ = whole.block.layer(1).mlp(lp, x, None)
    m = whole.block.layer(1).router_rows(lp, x)
    shared = nh.relu2_expert(m, lp["ws_up"], lp["ws_down"])
    total = jnp.zeros_like(x)
    ref_total = jnp.zeros_like(x)
    u = ref.rms_norm(x, lp["w_in"], 1e-5)
    for rank in range(4):
        held = (4 * rank, 4)
        block = nh.NemotronHBlock(**{
            **{f.name: getattr(whole.block, f.name)
               for f in nh.dataclasses.fields(whole.block)}, "held": held})
        share = {**lp, "w_up": lp["w_up"][4 * rank:4 * rank + 4],
                 "w_down": lp["w_down"][4 * rank:4 * rank + 4]}
        y, report = block.layer(1).mlp(share, x, None)
        total = total + (y - x - shared)
        assert int(report.sum()) == 40 * 3
        out, _ = ref.experts_part(share, u, top_k=3, scale=2.5, held=held,
                                  ablate="no_shared")
        ref_total = ref_total + out
    np.testing.assert_allclose(x + shared + total, uncut, rtol=2e-5,
                               atol=2e-5)
    all_held, _ = ref.experts_part(lp, u, top_k=3, scale=2.5, held=(0, 16),
                                   ablate=None)
    np.testing.assert_allclose(
        ref_total + ref._expert(u, lp["ws_up"], lp["ws_down"], ablate=None),
        all_held, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(uncut - x, all_held, rtol=2e-4, atol=2e-4)


# -- the model through both caches --------------------------------------------


# under a chunk of the scan's 128 rows... the toy's bucket is 64: under a
# page, a page's edge, several pages, over a bucket
@pytest.mark.parametrize("T", [5, 8, 63, 64, 65, 100])
def test_prefill_then_steps_through_both_caches_match_the_reference(model, T):
    ids, toks = prompt(T, seed=T), prompt(6, seed=T + 1)
    got = through_the_cache(model, ids, toks)
    want = reference(model, ids + toks, list(range(T - 1, T + 6)))
    assert ref.rel_rms(got, want) < 1e-5


@pytest.fixture(scope="module")
def decoded(model):
    ids, toks = prompt(40, seed=1), prompt(4, seed=2)
    return ids + toks, through_the_cache(model, ids, toks)


# what the toy sizes show in float32; the cell holds the rest on the chip
_MOVES = {"bias_in_weights": 5e-5, "state_bf16": 5e-5, "no_scale": 1e-3,
          "no_renorm": 1e-3}


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_each_ablation_moves_the_logits(model, decoded, ablate):
    ids, got = decoded
    rows = list(range(39, 44))
    assert ref.rel_rms(got, reference(model, ids, rows)) < 1e-5
    assert ref.rel_rms(got, reference(model, ids, rows, ablate)) > \
        _MOVES.get(ablate, 5e-3)


def test_the_entry_after_steps_is_the_entry_one_prefill_leaves(model):
    from perf.drivers.generate_ssm import (through_one_prefill,
                                           through_the_cache as cached)

    ids, toks = prompt(30, seed=8), prompt(5, seed=9)
    _, stepped = cached(model, ids, toks, 4)
    whole = through_one_prefill(model, ids + toks)
    assert stepped.shape == (3, 8, 64, 128)
    assert ref.rel_rms(stepped, whole) < 1e-5
    _, states = reference(model, ids + toks, [34], states=True)
    assert ref.rel_rms(stepped, states) < 1e-5


def test_steps_by_the_kernels_are_the_xla_paths_steps():
    """``ssd_step`` over four groups and ``conv_step`` interpreted, the
    grouped walk interpreted, against the XLA paths."""
    ids, toks = prompt(20, seed=3), prompt(6, seed=4)
    rows = {}
    fam = pk._M_DISPATCH
    before = fam.value(kernel="ssd_step", path="interpret")
    for kernels in (False, True):
        pk.enable(kernels, interpret=kernels)
        jax.clear_caches()
        try:
            rows[kernels] = through_the_cache(
                nh.NemotronHLM(seed=3, **TOY), ids, toks)
        finally:
            pk.enable("auto", interpret=False)
            jax.clear_caches()
    assert fam.value(kernel="ssd_step", path="interpret") > before
    assert ref.rel_rms(rows[True], rows[False]) < 1e-5


# -- a layer of one part through every program of the skeleton ----------------


def test_a_layer_is_one_part_in_the_step_and_in_the_bucket(model):
    """No instruction of an ``E`` layer under ``blk_mixer``, none of an
    ``M`` or ``*`` layer under ``blk_mlp``; the reports stacked over the
    routed layers alone."""
    texts = lowered_texts(model)
    for name, text in texts.items():
        assert "blk_mixer/ssm/" in text and "blk_mixer/attn_full/" in text
        assert "blk_mixer/ssm_proj/" in text
        assert "blk_mlp/moe_shared/" in text
        assert "blk_mlp/moe_router/" in text
        assert "blk_mixer/moe_" not in text and "blk_mlp/ssm" not in text
        assert "blk_mlp/attn_full" not in text
    cache = model._cache()
    out = jax.eval_shape(
        lambda *a: dm._decode_step(
            *a, heads=model.heads, page_size=model.page_size,
            block=model.block, extra=cache[2:]),
        model.params, *cache[:2],
        np.zeros((4, model.pages_per_seq), np.int32),
        np.zeros((4,), np.int32), np.zeros((4,), np.int32))
    assert out[3].shape == (3, 8 + 1)        # three E layers, held + 1


def test_the_counters_count_the_routed_layers_alone(model):
    from paddle_tpu.observability import metrics

    fam = metrics.REGISTRY.get("moe_expert_path_total")
    before = fam.value(path="grouped", phase="decode")
    through_the_cache(model, prompt(9, seed=1), prompt(2, seed=2))
    # 4 slots x 3 of 16: the rule picks the grouped way; 3 layers a step
    assert moe.expert_path(4, 3, 16) == "grouped"
    assert fam.value(path="grouped", phase="decode") - before == 2 * 3
    assert model.cache_rows([10, 20]) == {"full": 30, "state": 2 * 3}
    assert model.full_layers == 1 and model.linear_layers == 3


def test_what_needs_an_earlier_state_is_refused_by_name(model):
    cache = model._cache()
    args = (model.params, *cache[:2],
            np.zeros((4, model.pages_per_seq), np.int32),
            np.zeros((4,), np.int32), np.zeros((4, 3), np.int32))
    with pytest.raises(UnsupportedOverState, match="speculative verify"):
        dm._verify_step.lower(*args, heads=model.heads,
                              page_size=model.page_size, block=model.block,
                              extra=cache[2:])
    with pytest.raises(UnsupportedOverState):
        model.verify_chunk(None, None, None, None)
    with pytest.raises(UnsupportedOverState):
        model.copy_page(1, 2)
    pages = model.allocator.alloc(model.context_pages([2] * 20, 0))
    try:
        with pytest.raises(UnsupportedOverState, match="cached"):
            model.prefill([2] * 20, pages, cached_len=8)
    finally:
        model.allocator.free(pages)
    assert not (model.supports_prefix_cache or model.supports_fork
                or model.supports_verify)
    # a prompt over the top bucket would go on in chunks over the state
    assert model.prefill_cap == 128 and model.seq_rows == 256
    with pytest.raises(UnsupportedOverState, match="recurrent_chunk"):
        model.prefill_bucket(129)
    with pytest.raises(ValueError):
        model.prefill_bucket(257)
    assert model.prefill_bucket(128) == 128


def test_the_published_pattern_and_an_entrys_shapes():
    kinds = nh.layer_kinds(nh.PATTERN)
    assert len(kinds) == 52
    assert [kinds.count(k) for k in (nh.MAMBA, nh.EXPERTS, nh.ATTENTION)] \
        == [23, 23, 6]
    assert [i for i, k in enumerate(kinds) if k == nh.ATTENTION] == [
        5, 12, 19, 26, 33, 42]
    from paddle_tpu.decode.state_entry import tail_shape

    assert tail_shape(4, 4096 + 2 * 8 * 128) == (144, 128)
    assert gh.heads_a_row(2, 128) == 1 and gh.heads_a_row(64, 64) == 2
