"""The prefill's chunked gated-delta kernel
(``paddle_tpu/pallas/gated_delta_chunked.py``) interpreted on the CPU at
toy widths: against the recurrence row by row (numpy, float64) and
against ``chunked_gated_delta``, its XLA reference; what ``fits()``
takes; the dispatch counter; and a toy Olmo-Hybrid's prefill through it
(``test_olmo_hybrid.py``'s ``step_path`` cases run it too: under
``pallas.enable(True, interpret=True)`` a 128-row bucket fits)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hybrid_models import OLMO, through_the_cache
from hybrid_models import prompt as _prompt
from hybrid_models import reference as _reference
from paddle_tpu import pallas as pk
from paddle_tpu.models import olmo_hybrid as oh
from paddle_tpu.observability import metrics
from paddle_tpu.pallas import gated_delta_chunked as gdc
from perf.reference import olmo_hybrid_block as ref
from test_olmo_hybrid import _plain_recurrence, _rows

ATOL = 5e-5


@pytest.fixture(autouse=True, scope="module")
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def kernels_interpreted():
    pk.enable(True, interpret=True)
    jax.clear_caches()           # the mode is no part of a program's key
    try:
        yield
    finally:
        pk.enable("auto", interpret=False)
        jax.clear_caches()


def _kernel(*args):
    return gdc.gated_delta_chunked(
        *(jnp.asarray(a, jnp.float32) for a in args), interpret=True)


def _xla(*args):
    return oh.chunked_gated_delta(
        *(jnp.asarray(a, jnp.float32) for a in args))


def _dispatched():
    fam = metrics.REGISTRY.get("pallas_dispatch_total")
    return {path: fam.value(kernel="gated_delta_chunked", path=path)
            for path in ("compiled", "interpret", "reference")}


# T = 700: six chunks of 128, the state carried through five of them
@pytest.mark.parametrize("T", [1, 63, 64, 65, 200, 700])
def test_kernel_is_the_plain_recurrence_and_the_xla_form(T):
    """From a non-zero state (``_rows`` draws one), beta in (0, 2)."""
    args = _rows(T, T)
    want_o, want_S = _plain_recurrence(*args)
    o, S = _kernel(*args)
    np.testing.assert_allclose(o, want_o, atol=ATOL)
    np.testing.assert_allclose(S, want_S, atol=ATOL)
    o_x, S_x = _xla(*args)
    np.testing.assert_allclose(o, o_x, atol=ATOL)
    np.testing.assert_allclose(S, S_x, atol=ATOL)


def test_kernel_from_the_zero_state_at_wider_heads():
    """What a prefill hands it: zeros, and d_k, d_v that are no toy's
    (40 keys and the two scalars after them in one row of lanes, 136
    values in two)."""
    q, k, v, g, beta, S = _rows(300, 5, H=2, dk=40, dv=136)
    S = np.zeros_like(S)
    want_o, want_S = _plain_recurrence(q, k, v, g, beta, S)
    o, new = _kernel(q, k, v, g, beta, S)
    np.testing.assert_allclose(o, want_o, atol=ATOL)
    np.testing.assert_allclose(new, want_S, atol=ATOL)


@pytest.mark.parametrize("keys", ["random", "one_key"])
def test_worst_conditioning_a_whole_chunk_of_strong_writes(keys):
    """beta near 2 and g near 0 over whole chunks: ``I + A`` at its
    least diagonally dominant, every write's eigenvalue near -1 and
    nothing forgotten, so outputs and state grow to 20-35 and the
    tolerance is the other cases' times that.  With ONE key on every
    row (a run of one token) ``A`` is 2 everywhere below the diagonal:
    its powers grow to 1e27 inside a chunk while the true inverse stays
    +-2; the doubling keeps to the inverse.  An inverse times a right
    hand side is not backward stable as substitution is: here the
    kernel reads 5e-5 of the largest value where the XLA form's solve
    reads 5e-6 (a refinement step on ``T`` changes nothing; one on
    ``U`` would cost a sixth more MXU time); on random keys 7e-6
    against 3e-6."""
    q, k, v, g, beta, S = _rows(256, 11)
    beta = np.full_like(beta, 1.98)
    g = g * 1e-3
    if keys == "one_key":
        k = np.broadcast_to(k[:1], k.shape)
    want_o, want_S = _plain_recurrence(q, k, v, g, beta, S)
    o, new = _kernel(q, k, v, g, beta, S)
    grown = {"random": 1.0, "one_key": 2.0}[keys]
    assert np.abs(want_o).max() > 20
    np.testing.assert_allclose(o, want_o,
                               atol=grown * ATOL * np.abs(want_o).max())
    np.testing.assert_allclose(new, want_S,
                               atol=grown * ATOL * np.abs(want_S).max())


def test_a_fast_decaying_head_does_not_overflow():
    """g = -3 a row: exp(-G_s) alone would pass float32's range inside
    a chunk (e^384); the decay matrix is only ever exp(G_t - G_s)."""
    q, k, v, g, beta, S = _rows(256, 13)
    g = np.full_like(g, -3.0)
    want_o, want_S = _plain_recurrence(q, k, v, g, beta, S)
    o, new = _kernel(q, k, v, g, beta, S)
    assert np.isfinite(o).all() and np.isfinite(new).all()
    np.testing.assert_allclose(o, want_o, atol=ATOL)
    np.testing.assert_allclose(new, want_S, atol=ATOL)


def test_padding_rows_leave_the_state_as_it_was():
    """g = 0, beta = 0 from row n on, whole chunks of it too: the state
    after the bucket is the state after n rows."""
    q, k, v, g, beta, S = (jnp.asarray(a, jnp.float32)
                           for a in _rows(384, 3))
    n = 70
    live = (jnp.arange(384) < n)[:, None]
    _, padded = _kernel(q, k, v, jnp.where(live, g, 0.0),
                        jnp.where(live, beta, 0.0), S)
    _, cut = _kernel(q[:n], k[:n], v[:n], g[:n], beta[:n], S)
    np.testing.assert_allclose(padded, cut, atol=1e-6)
    _, want = _plain_recurrence(*(np.asarray(a, np.float64) for a in (
        q[:n], k[:n], v[:n], g[:n], beta[:n], S)))
    np.testing.assert_allclose(padded, want, atol=ATOL)


@pytest.mark.parametrize("case,want", [
    # the cell's: 30 heads, d_v 192, d_k 96, every bucket of its ladder
    ((jnp.float32, 4096, 30, 192, 96), True),
    ((jnp.float32, 4608, 30, 192, 96), True),
    ((jnp.float32, 128, 30, 192, 96), True),
    ((jnp.float32, 128, 4, 16, 8), True),        # a toy's
    ((jnp.bfloat16, 4096, 30, 192, 96), False),  # no float32 state
    ((jnp.float32, 4096 + 64, 30, 192, 96), False),   # half a chunk over
    ((jnp.float32, 64, 30, 192, 96), False),
    ((jnp.float32, 128, 4, 10, 6), False),       # keys, values no whole tiles
])
def test_fits(case, want):
    assert gdc.fits(*case) is want


def test_head_block_and_key_width():
    hb = gdc.head_block(30)
    assert hb <= gdc.HEAD_BLOCK and 30 % hb == 0
    assert gdc.head_block(7) in (1, 7)
    # the keys and the two scalars after them, in whole rows of lanes
    assert gdc.key_width(96) == 128 and gdc.key_width(8) == 128
    assert gdc.key_width(127) == 256


@pytest.mark.parametrize("mode,interpret,rows,path", [
    (True, True, 128, "interpret"), ("auto", True, 256, "interpret"),
    (False, True, 128, "reference"), (True, True, 64, "reference"),
    ("auto", False, 128, "reference"),      # no TPU here: the XLA form
])
def test_dispatch_is_counted_by_path(mode, interpret, rows, path):
    before = _dispatched()
    pk.enable(mode, interpret=interpret)
    try:
        use = pk.use_gated_delta_chunked(jnp.float32, rows, 4, 16, 8)
    finally:
        pk.enable("auto", interpret=False)
    assert use is (path != "reference")
    after = _dispatched()
    assert {p: after[p] - before[p] for p in after} == {
        p: float(p == path) for p in after}


def test_a_prefill_by_the_kernel_is_the_reference(kernels_interpreted):
    """A toy Olmo-Hybrid's 128-row bucket through the kernel, once a
    linear layer by the counter, then six decode steps from the entry
    it wrote: the logits are the plain reference's."""
    model = OLMO.make(kernel=True)
    before = _dispatched()
    prompt, tokens = _prompt(70, 1), _prompt(6, 2)
    got = through_the_cache(model, prompt, tokens)
    after = _dispatched()
    linear = sum(t == oh.LINEAR for t in OLMO.types)
    assert after["interpret"] - before["interpret"] == linear
    assert after["reference"] == before["reference"]
    want = _reference(model, prompt + tokens,
                      list(range(len(prompt) - 1, len(prompt) + len(tokens))))
    assert ref.rel_rms(got, want) < 1e-5
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_64_row_bucket_takes_the_xla_form(kernels_interpreted):
    """Half a chunk: ``fits()`` refuses it, whatever the mode."""
    model = OLMO.make(kernel=True)
    before = _dispatched()
    got = through_the_cache(model, _prompt(40, 3), _prompt(2, 4))
    after = _dispatched()
    assert after["interpret"] == before["interpret"]
    assert after["reference"] - before["reference"] == sum(
        t == oh.LINEAR for t in OLMO.types)
    assert np.isfinite(got).all()
