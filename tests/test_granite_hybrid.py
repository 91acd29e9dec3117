"""Granite-4.0-H behind /generate (``paddle_tpu/models/granite_hybrid.py``):
Mamba-2 layers whose recurrent state lives in a state entry a sequence
beside the K/V pages of the attention layers, in one cache manager.
CPU, float32, toy widths that keep the ratios (heads of 64 on fewer K/V
heads, a state of 128, a period of ten); the plain reference is
``perf/reference/granite_hybrid_block.py``.  A decode step advances the
states gathered and scattered in XLA here (off a TPU the kernels are
not dispatched); the cases that take ``step_path`` run once more
through ``pallas/ssd_step.py`` interpreted.  What the model has of the
hybrids' shared base is held in ``test_state_entry.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hybrid_models import GRANITE, lowered_texts, prompt, reference
from hybrid_models import steps_by, through_the_cache
from paddle_tpu import pallas as pk
from paddle_tpu.decode.attention import ragged_paged_attention_gqa_reference
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models.granite_hybrid import ATTENTION, MAMBA
from paddle_tpu.pallas import ssd_step as ssd
from perf.reference import granite_hybrid_block as ref

TYPES = GRANITE.types


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return GRANITE.make()


@pytest.fixture(params=["xla", "kernel"])
def step_path(request):
    if request.param == "xla":
        yield
        return
    pk.enable(True, interpret=True)
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        yield
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()


# -- the recurrence -----------------------------------------------------------


def _plain_recurrence(x, dt, g, B, C, S):
    ys = []
    for t in range(x.shape[0]):
        S = (np.exp(g[t])[:, None, None] * S
             + (dt[t][:, None] * x[t])[:, :, None] * B[t][None, None, :])
        ys.append(np.einsum("hpn,n->hp", S, C[t]))
    return np.stack(ys), S


def _rows(T, seed, H=3, P=8, N=16):
    rng = np.random.RandomState(seed)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (T, H)))
    return (rng.randn(T, H, P), dt, -rng.uniform(1.0, 16.0, (H,)) * dt,
            rng.randn(T, N), rng.randn(T, N), rng.randn(H, P, N))


@pytest.mark.parametrize("T", [1, 127, 128, 129, 300])
def test_chunked_scan_is_the_plain_recurrence(T):
    args = _rows(T, T)
    want_y, want_S = _plain_recurrence(*args)
    y, S = gh.chunked_ssd(*(jnp.asarray(a, jnp.float32) for a in args))
    np.testing.assert_allclose(y, want_y, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5, rtol=1e-5)


def test_the_chunk_length_does_not_change_the_result():
    args = [jnp.asarray(a, jnp.float32) for a in _rows(200, 9)]
    y, S = gh.chunked_ssd(*args)
    for chunk in (16, 64, 256):
        y2, S2 = gh.chunked_ssd(*args, chunk=chunk)
        np.testing.assert_allclose(y2, y, atol=5e-5, rtol=1e-5)
        np.testing.assert_allclose(S2, S, atol=5e-5, rtol=1e-5)


def test_one_token_step_is_a_row_of_the_recurrence():
    x, dt, g, B, C, S = _rows(1, 7)
    want_y, want_S = _plain_recurrence(x, dt, g, B, C, S)
    y, new = gh.step_ssd(*(jnp.asarray(a[0], jnp.float32)
                           for a in (x, dt, g, B, C)),
                         jnp.asarray(S, jnp.float32))
    np.testing.assert_allclose(y, want_y[0], atol=1e-5)
    np.testing.assert_allclose(new, want_S, atol=1e-5)


def test_padding_rows_leave_the_state_as_it_was():
    """dt = 0 (and so g = 0) from row n on: the state after the bucket
    is the state after n rows."""
    x, dt, g, B, C, S = (jnp.asarray(a, jnp.float32) for a in _rows(256, 3))
    n = 150
    live = (jnp.arange(256) < n)[:, None]
    _, padded = gh.chunked_ssd(x, jnp.where(live, dt, 0.0),
                               jnp.where(live, g, 0.0), B, C, S)
    _, cut = gh.chunked_ssd(x[:n], dt[:n], g[:n], B[:n], C[:n], S)
    np.testing.assert_allclose(padded, cut, atol=1e-6)


_STEP_CASES = {
    # entries, heads, channels, state size, each slot's entry
    "distinct": (6, 4, 64, 128, [3, 1, 5]),
    "inactive_slots_on_entry_0": (5, 2, 128, 16, [2, 0, 0, 4]),
    "two_blocks_of_rows": (4, 64, 64, 128, [1, 3]),
}


@pytest.mark.parametrize("case", _STEP_CASES)
def test_ssd_step_is_the_step_on_the_gathered_entries(case):
    """``ssd_step`` (interpreted) against ``step_ssd`` on the entries
    the slots address, stored as ``pack_state`` lays them out: ``y``
    and the new entries within 1e-5, every entry no slot addresses
    bit-identical.  Slots on the null entry 0 write it in no order: it
    stays finite and the other slots' are right.  At the published
    (64, 64, 128) an entry is 32 rows of two heads and goes in two
    blocks of 16."""
    N, H, P, W, at = _STEP_CASES[case]
    rng = np.random.RandomState(len(case))
    S, pack = len(at), gh.heads_a_row(H, P)
    at = np.asarray(at, np.int32)
    pool = rng.randn(N, H, P, W).astype(np.float32)
    x = rng.randn(S, H, P).astype(np.float32)
    dt = rng.uniform(1e-3, 0.3, (S, H)).astype(np.float32)
    g = (-rng.uniform(1.0, 16.0, (H,)) * dt).astype(np.float32)
    B, C = (rng.randn(S, W).astype(np.float32) for _ in range(2))
    stored = np.asarray(gh.pack_state(pool, pack))
    R, lanes = H // pack, pack * P
    assert stored.shape == (N, R, W, lanes) and lanes == 128
    np.testing.assert_array_equal(gh.unpack_state(stored, pack), pool)
    assert ssd.fits(stored.dtype, R, W, lanes)
    assert ssd.head_block(R, W, lanes) == (16 if H == 64 else R)
    y, new = ssd.ssd_step(
        jnp.asarray(stored), at,
        np.repeat(np.exp(g), P, -1).reshape(S, R, lanes),
        (x * dt[..., None]).reshape(S, R, lanes), B, C, interpret=True)
    want_y, want_new = gh.step_ssd(x, dt, g, B, C, pool[at])
    y, new = np.asarray(y).reshape(S, H, P), np.asarray(new)
    live = at != 0
    np.testing.assert_allclose(y[live], np.asarray(want_y)[live],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        gh.unpack_state(new[at[live]], pack), np.asarray(want_new)[live],
        atol=1e-5, rtol=1e-5)
    untouched = np.setdiff1d(np.arange(N), at)
    np.testing.assert_array_equal(new[untouched], stored[untouched])
    assert np.isfinite(new[0]).all()


def test_ssd_step_fits_whole_tiles_of_float32():
    assert ssd.fits(jnp.float32, 32, 128, 128)    # nothing padded
    assert not ssd.fits(jnp.bfloat16, 32, 128, 128)      # a bf16 state
    assert not ssd.fits(jnp.float32, 64, 128, 64)        # heads off lanes
    assert not ssd.fits(jnp.float32, 32, 12, 128)        # a state off tiles


# -- block == reference --------------------------------------------------------


def test_prefill_then_16_steps_through_both_caches_match_the_reference(
        step_path):
    model = GRANITE.make()
    ids, tokens = prompt(70, 1), prompt(16, 2)
    got = through_the_cache(model, ids, tokens)
    want = reference(model, ids + tokens,
                     list(range(len(ids) - 1, len(ids) + len(tokens))))
    assert got.shape[0] == 17
    assert ref.rel_rms(got, want) < 1e-5
    np.testing.assert_allclose(got, want, atol=2e-6)


def _reference_states(model, ids, ablate=None):
    b = model.block
    return ref.forward(
        model.params, jnp.asarray(ids, jnp.int32),
        layer_types=b.layer_types, num_heads=model.heads,
        head_dim=b.head_dim, mamba_n_heads=b.mamba_n_heads,
        mamba_d_head=b.mamba_d_head, mamba_d_state=b.mamba_d_state,
        eps=b.eps, ablate=ablate, rows=[len(ids) - 1], states=True)[1]


def test_the_entry_after_16_steps_is_the_entry_one_prefill_leaves(
        step_path):
    """The benchmark's second number (``perf/drivers/generate_ssm.py``):
    the sequence's states as prefill + 16 steps leave them in the entry,
    as published (9 layers of (2, 64, 128)), equal what one prefill of
    the same rows leaves and the reference's final states; the
    reference's state rounded to bfloat16 at every row does not."""
    from perf.drivers import generate_ssm as driver

    model = GRANITE.make()
    ids, tokens = prompt(70, 1), prompt(16, 2)
    got, stepped = driver.through_the_cache(model, ids, tokens, 4)
    assert got.shape[0] == 17 and stepped.shape == (9, 2, 64, 128)
    whole = driver.through_one_prefill(model, ids + tokens)
    assert ref.rel_rms(stepped, whole) < 1e-5
    want = _reference_states(model, ids + tokens)
    assert ref.rel_rms(stepped, want) < 1e-5
    low = _reference_states(model, ids + tokens, "state_bf16")
    assert ref.rel_rms(low, want) > 1e-3


def test_eight_steps_by_the_kernels_are_the_xla_paths_steps():
    """A prefill + 8 decode steps with the step's kernels interpreted
    (``conv_step``, ``ssd_step``) against the same under
    ``pallas.enable(False)``: the conv's tails, written by the prefill's
    ``conv_tail`` and carried across the steps, are only moved, so the
    first recurrent layer's are bit-identical in every entry but the
    null one (later layers' rows inherit float32 rounding); the logits
    agree to it; the dispatch counter says which path each trace of the
    step took, once a recurrent layer."""
    ids, tokens = prompt(70, 1), prompt(8, 2)
    by_kernel, tails, took = steps_by(GRANITE, True, ids, tokens)
    by_xla, want_tails, took_xla = steps_by(GRANITE, False, ids, tokens)
    assert took == {"interpret": 9, "reference": 0}
    assert took_xla == {"interpret": 0, "reference": 9}
    assert by_kernel.shape[0] == 9
    np.testing.assert_allclose(by_kernel, by_xla, atol=2e-6)
    # the first recurrent layer's rows come of the embedding alone
    np.testing.assert_array_equal(tails[0, 1:], want_tails[0, 1:])
    np.testing.assert_allclose(tails[:, 1:], want_tails[:, 1:], atol=2e-6)
    assert tails[:, 1:].any()


@pytest.mark.parametrize("n", [1, 3, 8, 63, 64, 65, 127, 128, 129, 200])
def test_prompt_lengths_round_a_chunk_and_a_bucket(model, n):
    ids, tokens = prompt(n, n), prompt(3, n + 1)
    got = through_the_cache(model, ids, tokens)
    want = reference(model, ids + tokens, list(range(n - 1, n + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5


@pytest.fixture(scope="module")
def decoded():
    """A model through both caches.  Its attention scores are of the
    size they have at the published width: the q and k projections are
    drawn so at any width (``granite_hybrid.py:QK_ROW_STD``; at N(0,
    0.02) the softmax is flat whatever scales or rotates it)."""
    model = GRANITE.make(seed=5)
    ids, tokens = prompt(70, 1), prompt(6, 2)
    got = through_the_cache(model, ids, tokens)
    rows = list(range(len(ids) - 1, len(ids) + len(tokens)))
    assert ref.rel_rms(got, reference(model, ids + tokens, rows)) < 1e-5
    return model, ids + tokens, rows, got


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_each_ablation_moves_the_logits(decoded, ablate):
    """What the benchmark's limit has to catch: every ablation of the
    reference, the rounded state and the rounded weights included, lies
    well outside float32 noise of the system's logits (1e-6)."""
    model, ids, rows, got = decoded
    floor = {"state_bf16": 1e-5, "rope_on_attention": 1e-3,
             "softmax_scale_rsqrt": 1e-3}.get(ablate, 1e-2)
    assert ref.rel_rms(got, reference(model, ids, rows, ablate)) > floor


def test_dense_forward_is_the_reference(model):
    ids = prompt(40, 5)
    logits, kept, _ = model._forward(jnp.asarray(ids, jnp.int32))
    assert ref.rel_rms(logits, reference(model, ids)) < 1e-5
    assert len(kept) == len(TYPES)


# -- padding, the conv's tail ---------------------------------------------------


def test_bucket_padding_leaves_state_and_conv_tail_untouched(model):
    """A prompt of 70 rows runs in the 128-row bucket: the entry written
    is the state and the tail after 70 rows, whatever ids fill the
    padding."""
    ids = prompt(70, 11)
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    entry = model.allocator.entry_of(pages)
    try:
        model.prefill(ids, pages)
        state = np.asarray(model.state_pool[:, entry])
        tail = np.asarray(model.conv_pool[:, entry])
    finally:
        model.allocator.free(pages)
    _, kept, _ = model._forward(jnp.asarray(ids, jnp.int32))
    rec = [k for k, t in zip(kept, TYPES) if t == MAMBA]
    assert len(rec) == state.shape[0] == 9
    assert state.shape[1:] == (1, 128, 128)      # two heads a row of lanes
    for i, (want_state, want_tail) in enumerate(rec):
        np.testing.assert_allclose(state[i], want_state, atol=1e-5)
        # the kept rows one after another in rows of lanes
        assert tail[i].shape == (9, 128) and want_tail.shape == (3, 384)
        np.testing.assert_allclose(tail[i].reshape(3, 384), want_tail,
                                   atol=1e-6)


def test_a_conv_tail_of_a_short_prompt_is_zeros_before_row_0(model):
    """The tail is three rows of the conv's 2 x 64 + 2 x 128 channels,
    stored one after another in rows of 128 lanes."""
    ids = prompt(2, 12)
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    entry = model.allocator.entry_of(pages)
    try:
        model.prefill(ids, pages)
        tail = np.asarray(model.conv_pool[:, entry]).reshape(9, 3, -1)
    finally:
        model.allocator.free(pages)
    assert tail.shape[-1] == 2 * 64 + 2 * 128
    assert not tail[:, 0].any() and tail[:, 1:].any()


# -- pages of 64-wide heads ------------------------------------------------------


@pytest.mark.parametrize("kv_heads, head_dim, want", [
    (8, 64, 2), (2, 64, 2), (8, 128, 1), (3, 64, 1), (8, 32, 4), (8, 96, 1)])
def test_heads_a_row(kv_heads, head_dim, want):
    assert gh.heads_a_row(kv_heads, head_dim) == want


def test_pages_hold_two_heads_of_64_a_row(model):
    """2 K/V heads of 64 are one stored row of 128 lanes, head 0 in
    lanes 0..63 and head 1 beyond; nothing is padded."""
    assert model.k_pool.shape[3:] == (1, 128) and model.block.pack == 2
    ids = prompt(11, 13)
    pages = model.allocator.alloc(model.context_pages(ids, 0))
    try:
        model.prefill(ids, pages)
        k_pool = np.asarray(model.k_pool)
        run = model.allocator.pages_of(pages)
    finally:
        model.allocator.free(pages)
    _, kept, _ = model._forward(jnp.asarray(ids, jnp.int32))
    k = np.asarray(kept[TYPES.index(ATTENTION)][0])          # (T, 2, 64)
    rows = k_pool[0, run].reshape(-1, 128)[:len(ids)]
    # (k's numbers are ~3 at ``QK_ROW_STD``: float32 rounding, relative)
    np.testing.assert_allclose(rows[:, :64], k[:, 0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(rows[:, 64:], k[:, 1], rtol=2e-5, atol=1e-6)


def test_the_packed_step_is_grouped_attention_at_the_multiplier(step_path):
    """``cached_attention`` over pages of two heads a row against the
    grouped reference over the same rows unpacked, at scale 1/64:
    query heads in their own half of the lanes, zeros in the other."""
    rng = np.random.RandomState(4)
    S, Hq, Hkv, dh, pg, P, N = 3, 16, 4, 64, 8, 4, 14
    block = gh.GraniteHybridBlock(
        layer_types=(MAMBA, ATTENTION), kv_heads=Hkv, head_dim=dh, pack=2,
        full_pages=P, at=1)
    k_pool, v_pool = (jnp.asarray(rng.randn(1, N, pg, Hkv // 2, 2 * dh),
                                  jnp.float32) for _ in range(2))
    tables = np.zeros((S, P + 1), np.int32)
    tables[:, :P] = 1 + rng.permutation(N - 1)[:S * P].reshape(S, P)
    lens = np.asarray([5, 17, 31], np.int32)
    q = rng.randn(S, Hq, dh).astype(np.float32)
    k, v = (rng.randn(S, Hkv, dh).astype(np.float32) for _ in range(2))
    flat = tables[np.arange(S), lens // pg] * pg + lens % pg
    # q as ``qkv`` hands it on: attention_multiplier * dh^1/2 folded in
    a, k_new, v_new = block.cached_attention(
        k_pool, v_pool, 1, jnp.asarray(q * (dh ** 0.5 / 64)), k, v,
        jnp.asarray(flat), jnp.asarray(tables), jnp.asarray(lens))

    def unpacked(pool):
        return np.asarray(pool)[0].reshape(N, pg, Hkv, dh)

    want = ragged_paged_attention_gqa_reference(
        jnp.asarray(q[:, None]), unpacked(k_new), unpacked(v_new),
        jnp.asarray(tables[:, :P]), jnp.asarray(lens), scale=1.0 / 64)[:, 0]
    np.testing.assert_allclose(a, want, atol=2e-6)
    np.testing.assert_allclose(unpacked(k_new).reshape(-1, Hkv, dh)[flat], k)


# -- gauges, scopes -------------------------------------------------------------


def test_cache_rows_and_bytes_by_kind(model):
    lens = [10, 100]
    assert model.cache_rows(lens) == {"full": 110 * 1, "state": 2 * 9}
    b = model.cache_bytes(lens)
    assert b["full"] == 110 * 1 * (2 * 2 * 64 * 4)       # 2 heads of 64
    entry = 9 * (2 * 64 * 128 * 4 + 3 * (2 * 64 + 2 * 128) * 4)
    assert b["state"] == 2 * entry == 2 * model.entry_bytes()


def test_named_scopes_place_the_layers(model):
    text = lowered_texts(model)
    for scope in ("ssm/", "ssm/ssm_state/", "ssm/ssm_conv/", "attn_full/"):
        assert scope in text["_decode_step"], scope
    for scope in ("ssm/", "ssm/ssm_scan/", "ssm/ssm_conv/", "attn_full/"):
        assert scope in text["_prefill_bucket"], scope
    assert "ssm_scan/" not in text["_decode_step"]
    assert "ssm_state/" not in text["_prefill_bucket"]
