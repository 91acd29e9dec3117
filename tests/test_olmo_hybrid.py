"""Olmo-Hybrid behind /generate (``paddle_tpu/models/olmo_hybrid.py``):
Gated-DeltaNet layers whose recurrent state lives in a state entry a
sequence beside the K/V pages of the full layers, in one cache manager.
CPU, float32, toy widths with d_k != d_v and two periods of (linear x3,
full); the plain reference is ``perf/reference/olmo_hybrid_block.py``.
A decode step advances the states slot by slot in XLA here (off a TPU
the kernels are not dispatched); the cases that take ``step_path`` run
once more through ``pallas/gated_delta.py`` interpreted.  What the
model has of the hybrids' shared base (the cache manager's two
resources, admission, the refusals, the pools lost together, the
gauges) is held in ``test_state_entry.py``, over both hybrids.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hybrid_models import OLMO, lowered_texts, steps_by, through_the_cache
from hybrid_models import prompt as _prompt
from hybrid_models import reference as _reference
from paddle_tpu import pallas as pk
from paddle_tpu.models import olmo_hybrid as oh
from paddle_tpu.models.olmo_hybrid import LINEAR, OlmoHybridLM
from perf.reference import olmo_hybrid_block as ref

TYPES = OLMO.types
SIZES, KERNEL_SIZES = OLMO.sizes, OLMO.kernel_sizes
_through_the_cache = through_the_cache


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model():
    return OlmoHybridLM(seed=3, **SIZES)


@pytest.fixture(params=["loop", "kernel"])
def step_path(request):
    """How a decode step advances the states -> (the sizes of a model
    that takes that path, the path ``pallas_dispatch_total`` counts)."""
    if request.param == "loop":
        yield SIZES, "reference"
        return
    pk.enable(True, interpret=True)
    try:
        yield KERNEL_SIZES, "interpret"
    finally:
        pk.enable("auto", interpret=False)


# -- the recurrence -----------------------------------------------------------


def _plain_recurrence(q, k, v, g, beta, S):
    outs = []
    for t in range(q.shape[0]):
        a = np.exp(g[t])[:, None, None]
        u = beta[t][:, None] * (v[t] - np.einsum("hvk,hk->hv", a * S, k[t]))
        S = a * S + u[:, :, None] * k[t][:, None, :]
        outs.append(np.einsum("hvk,hk->hv", S, q[t]))
    return np.stack(outs), S


def _rows(T, seed, H=3, dk=6, dv=10):
    rng = np.random.RandomState(seed)
    q, k = rng.randn(T, H, dk), rng.randn(T, H, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return (q, k, rng.randn(T, H, dv), -rng.uniform(0.001, 0.3, (T, H)),
            rng.uniform(0.0, 2.0, (T, H)), rng.randn(H, dv, dk))


@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_chunked_scan_is_the_plain_recurrence(T):
    """beta in (0, 2): the eigenvalue of a write may be negative."""
    args = _rows(T, T)
    want_o, want_S = _plain_recurrence(*args)
    o, S = oh.chunked_gated_delta(*(jnp.asarray(a, jnp.float32)
                                    for a in args))
    np.testing.assert_allclose(o, want_o, atol=5e-5)
    np.testing.assert_allclose(S, want_S, atol=5e-5)


def test_one_token_step_is_a_row_of_the_recurrence():
    q, k, v, g, beta, S = _rows(1, 7)
    want_o, want_S = _plain_recurrence(q, k, v, g, beta, S)
    o, new = oh.step_gated_delta(*(jnp.asarray(a[0], jnp.float32)
                                   for a in (q, k, v, g, beta)),
                                 jnp.asarray(S, jnp.float32))
    np.testing.assert_allclose(o, want_o[0], atol=1e-5)
    np.testing.assert_allclose(new, want_S, atol=1e-5)


def test_padding_rows_leave_the_state_as_it_was():
    """g = 0, beta = 0 from row n on: the state after the bucket is the
    state after n rows, to the bit."""
    q, k, v, g, beta, S = (jnp.asarray(a, jnp.float32) for a in _rows(128, 3))
    n = 70
    live = (jnp.arange(128) < n)[:, None]
    _, padded = oh.chunked_gated_delta(
        q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0), S)
    _, cut = oh.chunked_gated_delta(q[:n], k[:n], v[:n], g[:n], beta[:n], S)
    np.testing.assert_allclose(padded, cut, atol=1e-6)


def test_causal_conv_taps_rows_t_minus_3_to_t():
    rng = np.random.RandomState(0)
    z, w = rng.randn(9, 5).astype(np.float32), rng.randn(4, 5).astype(
        np.float32)
    want = np.zeros_like(z)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += w[j] * z[t - 3 + j]
    np.testing.assert_allclose(oh.causal_conv(jnp.asarray(z), jnp.asarray(w)),
                               want, atol=1e-6)


# -- block == reference --------------------------------------------------------


@pytest.fixture(scope="module")
def decoded(model):
    prompt, tokens = _prompt(70, 1), _prompt(6, 2)
    got = _through_the_cache(model, prompt, tokens)
    rows = list(range(len(prompt) - 1, len(prompt) + len(tokens)))
    return prompt + tokens, rows, got


def test_prefill_then_decode_through_both_caches_match_the_reference(
        step_path):
    model = OlmoHybridLM(seed=3, **step_path[0])
    prompt, tokens = _prompt(70, 1), _prompt(6, 2)
    got = _through_the_cache(model, prompt, tokens)
    want = _reference(model, prompt + tokens,
                      list(range(len(prompt) - 1, len(prompt) + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_eight_steps_by_the_kernels_are_the_xla_paths_steps():
    """A prefill + 8 decode steps with the step's kernels interpreted
    (``conv_step``, ``gated_delta_step``) against the same under
    ``pallas.enable(False)``: the conv's tails, written by the prefill's
    ``conv_tail`` and carried across the steps, are only moved, so the
    first recurrent layer's are bit-identical in every entry but the
    null one (later layers' rows inherit float32 rounding); the logits
    agree to it; the dispatch counter says which path each trace of the
    step took, once a recurrent layer."""
    ids, tokens = _prompt(70, 1), _prompt(8, 2)
    by_kernel, tails, took = steps_by(OLMO, True, ids, tokens)
    by_xla, want_tails, took_xla = steps_by(OLMO, False, ids, tokens)
    assert took == {"interpret": 6, "reference": 0}
    # the slot loop is the other path here: it asks nothing of the
    # conv's kernel
    assert took_xla == {"interpret": 0, "reference": 0}
    assert by_kernel.shape[0] == 9
    np.testing.assert_allclose(by_kernel, by_xla, atol=2e-5)
    # the first recurrent layer's rows come of the embedding alone
    np.testing.assert_array_equal(tails[0, 1:], want_tails[0, 1:])
    np.testing.assert_allclose(tails[:, 1:], want_tails[:, 1:], atol=2e-6)
    assert tails[:, 1:].any()


@pytest.mark.parametrize("n", [1, 3, 8, 63, 64, 65, 130])
def test_prompt_lengths_round_a_chunk_and_a_bucket(model, n):
    prompt, tokens = _prompt(n, n), _prompt(3, n + 1)
    got = _through_the_cache(model, prompt, tokens)
    want = _reference(model, prompt + tokens,
                      list(range(n - 1, n + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5


@pytest.mark.parametrize("ablate", ref.ABLATIONS)
def test_each_ablation_moves_the_logits(model, decoded, ablate):
    """What the benchmark's limits have to catch: every ablation of the
    reference, the rounded state and the rounded weights included, lies
    well outside float32 noise of the system's logits."""
    ids, rows, got = decoded
    floor = {"state_bf16": 2e-4, "fp8": 1e-2}.get(ablate, 1e-2)
    assert ref.rel_rms(got, _reference(model, ids, rows, ablate)) > floor


def test_dense_forward_is_the_reference(model):
    ids = _prompt(40, 5)
    logits, kept, _ = model._forward(jnp.asarray(ids, jnp.int32))
    assert ref.rel_rms(logits, _reference(model, ids)) < 1e-5
    assert len(kept) == len(TYPES)


# -- padding, reuse, inactive slots -------------------------------------------


def test_bucket_padding_leaves_state_and_conv_tail_untouched(model):
    """A prompt of 70 rows runs in the 128-row bucket: the entry written
    is the state and the tail after 70 rows, whatever ids fill the
    padding."""
    prompt = _prompt(70, 11)
    ids = model.allocator.alloc(model.context_pages(prompt, 0))
    entry = model.allocator.entry_of(ids)
    try:
        model.prefill(prompt, ids)
        state = np.asarray(model.state_pool[:, entry])
        tail = np.asarray(model.conv_pool[:, entry])
    finally:
        model.allocator.free(ids)
    _, kept, _ = model._forward(jnp.asarray(prompt, jnp.int32))
    lin = [k for k, t in zip(kept, TYPES) if t == LINEAR]
    d_k = model.block.d_k
    for i, (want_state, want_tail) in enumerate(lin):
        np.testing.assert_allclose(state[i][..., :d_k], want_state,
                                   atol=1e-5)
        assert not state[i][..., d_k:].any()         # the stored width
        np.testing.assert_allclose(tail[i], want_tail, atol=1e-6)


def test_a_conv_tail_of_a_short_prompt_is_zeros_before_row_0(model):
    prompt = _prompt(2, 12)
    ids = model.allocator.alloc(model.context_pages(prompt, 0))
    entry = model.allocator.entry_of(ids)
    try:
        model.prefill(prompt, ids)
        tail = np.asarray(model.conv_pool[:, entry])
    finally:
        model.allocator.free(ids)
    assert not tail[:, 0].any() and tail[:, 1:].any()


def test_pages_are_stored_at_whole_tiles_of_heads(model):
    """4 float32 heads are stored as 8 (``attention.storage_heads``);
    the padding heads stay zero."""
    assert model.k_pool.shape[3] == 8 and model.heads == 4
    assert not np.asarray(model.k_pool)[..., 4:, :].any()


# -- gauges, health, scopes ---------------------------------------------------


def test_cache_rows_and_bytes_by_kind(model):
    lens = [10, 100]
    assert model.cache_rows(lens) == {"full": 110 * 2, "state": 2 * 6}
    b = model.cache_bytes(lens)
    assert b["full"] == 110 * 2 * (2 * 8 * 8 * 4)         # 8 stored heads
    entry = 6 * (4 * 10 * 128 * 4 + 3 * 4 * (2 * 6 + 10) * 4)
    assert b["state"] == 2 * entry == 2 * model.entry_bytes()


def test_named_scopes_place_the_linear_layers(model):
    text = lowered_texts(model)
    for scope in ("lin_attn/", "lin_attn_state/", "lin_attn_conv/",
                  "attn_full/"):
        assert scope in text["_decode_step"], scope
    for scope in ("lin_attn/", "lin_attn_scan/", "lin_attn_conv/",
                  "attn_full/"):
        assert scope in text["_prefill_bucket"], scope
    assert "lin_attn_scan/" not in text["_decode_step"]
    # the XLA form's scan over the chunks is a loop under the scope ...
    assert "/lin_attn_scan/while" in text["_prefill_bucket"]
    # ... and with the kernel on (lowered for a TPU, nothing runs) a
    # 128-row bucket holds the kernel's custom call there, once a
    # linear layer, its relayout beside it, and no loop
    pk.enable(True, interpret=False)
    jax.clear_caches()          # the mode is no part of a program's key
    try:
        on = lowered_texts(OLMO.make(kernel=True), bucket=128,
                           platforms=("tpu",))["_prefill_bucket"]
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()
    call = "/lin_attn/lin_attn_scan/gated_delta_chunked/pallas_call"
    assert call in on and "tpu_custom_call" in on
    assert "/lin_attn_scan/while" not in on
