"""``pallas/conv_step.py``: a decode step's depthwise conv over the
slots' tails as one Pallas call over the tail pool, against the shared
XLA reference (``decode/state_entry.py:step_conv``).  Interpret mode,
CPU, at both cells' channel counts: Granite's 4,352 with a bias,
Olmo-Hybrid's 11,520 without.  bfloat16 rows (as served) multiply and
add in float32 in the reference's order, so the outputs agree bitwise;
float32 rows to float32 rounding (XLA:CPU sums the taps as a tree)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode.state_entry import (
    causal_conv,
    conv_over_entries,
    conv_tail,
    step_conv,
    tail_shape,
)
from paddle_tpu.pallas import conv_step as cs

TAPS = 4
CELLS = {"granite": (4352, True), "olmo_hybrid": (11520, False)}


def _case(name, dtype, slots=6, entries=9, seed=0):
    """-> (pool (entries, *tail_shape), at, row, w, b): random kept rows
    in every entry, ``slots`` live slots on distinct entries above the
    null entry 0."""
    C, bias = CELLS[name]
    rng = np.random.RandomState(seed)
    pool = jnp.asarray(rng.randn(entries, *tail_shape(TAPS, C)), dtype)
    at = jnp.asarray(1 + rng.permutation(entries - 1)[:slots], jnp.int32)
    row = jnp.asarray(rng.randn(slots, C), dtype)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (TAPS, C)), dtype)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (C,)), dtype) if bias else None
    return pool, at, row, w, b


def _reference(pool, at, row, w, b):
    S, C = row.shape
    out, kept = step_conv(pool[at].reshape(S, TAPS - 1, C), row, w, b)
    return out, pool.at[at].set(kept.reshape((S,) + pool.shape[1:]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_kernel_is_the_reference(name, dtype):
    """``out`` and the addressed entries are the reference's (bitwise
    on bfloat16 rows and for the entries, which are only moved); every
    entry no slot addresses is untouched."""
    pool, at, row, w, b = _case(name, jnp.dtype(dtype))
    before = np.asarray(pool.astype(jnp.float32))
    want_out, want_pool = _reference(pool, at, row, w, b)
    out, new = cs.conv_step(pool, at, row, w, b, interpret=True)
    assert out.dtype == jnp.float32 and out.shape == row.shape
    if dtype == "bfloat16":
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-6, atol=2e-6)
    new = np.asarray(new.astype(jnp.float32))
    np.testing.assert_array_equal(
        new, np.asarray(want_pool.astype(jnp.float32)))
    idle = np.setdiff1d(np.arange(pool.shape[0]), np.asarray(at))
    assert len(idle) == 3
    np.testing.assert_array_equal(new[idle], before[idle])
    # an addressed entry keeps rows[1:]: its last row is the step's
    C = row.shape[1]
    np.testing.assert_array_equal(
        new[np.asarray(at)].reshape(len(at), TAPS - 1, C)[:, -1],
        np.asarray(row.astype(jnp.float32)))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_unseated_slots_on_the_null_entry_leave_live_entries_alone(name):
    """Slots seated nowhere all address entry 0: the live slots' rows
    and entries are what they are without them, and no entry but 0 and
    the live ones changes."""
    pool, at, row, w, b = _case(name, jnp.bfloat16)
    live = np.array([0, 2, 5])
    at = jnp.asarray(np.where(np.isin(np.arange(6), live), at, 0))
    out, new = cs.conv_step(pool, at, row, w, b, interpret=True)
    want_out, want_pool = _reference(pool, at[live], row[live], w, b)
    np.testing.assert_array_equal(np.asarray(out)[live],
                                  np.asarray(want_out))
    new, want = (np.asarray(a.astype(jnp.float32)) for a in (new, want_pool))
    np.testing.assert_array_equal(new[1:], want[1:])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_steps_carry_the_prefills_tail(name):
    """The tails ``conv_tail`` leaves of a prompt are what the kernel
    reads on the first step: eight steps after a prompt of 5 rows give
    the rows a causal conv over all 13 gives."""
    C, bias = CELLS[name]
    rng = np.random.RandomState(3)
    z = jnp.asarray(rng.randn(13, C), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (TAPS, C)), jnp.bfloat16)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (C,)), jnp.bfloat16) if bias \
        else None
    want = causal_conv(z, w)
    if bias:
        want = want + b.astype(jnp.float32)
    want = jax.nn.silu(want)
    pool = jnp.zeros((3,) + tail_shape(TAPS, C), jnp.bfloat16)
    pool = pool.at[2].set(conv_tail(z, TAPS, 5).reshape(pool.shape[1:]))
    at = jnp.asarray([2], jnp.int32)
    for t in range(5, 13):
        out, pool = cs.conv_step(pool, at, z[t:t + 1], w, b, interpret=True)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[t]),
                                   rtol=2e-6, atol=2e-6)


def test_fits_asks_whole_lanes_and_the_entry_as_stored():
    bf = jnp.bfloat16
    for C in (4352, 11520, 384):
        assert cs.fits(bf, tail_shape(TAPS, C), bf, TAPS, C)
    # a channel count that is no multiple of 128: stored (taps - 1, C)
    assert tail_shape(TAPS, 112) == (3, 112)
    assert not cs.fits(bf, tail_shape(TAPS, 112), bf, TAPS, 112)
    assert not cs.fits(bf, (3, 4352), bf, TAPS, 4352)      # rows an entry
    assert not cs.fits(bf, (13056,), bf, TAPS, 4352)       # a row a slab
    assert not cs.fits(jnp.float32, (102, 128), bf, TAPS, 4352)


@pytest.mark.parametrize("mode, channels, path", [
    ("on", 384, "interpret"), ("on", 112, "reference"),
    ("off", 384, "reference"), ("auto", 384, "reference")])
def test_the_path_is_chosen_by_shape_and_counted(mode, channels, path):
    """``state_entry.conv_over_entries`` takes the kernel where
    ``fits()`` holds and the mode allows (off a TPU ``auto`` dispatches
    no kernel), the XLA gather and scatter elsewhere; both give the same
    rows; the counter says which ran."""
    from paddle_tpu.observability import metrics

    C = channels
    rng = np.random.RandomState(1)
    pool = jnp.asarray(rng.randn(5, *tail_shape(TAPS, C)), jnp.float32)
    at = jnp.asarray([3, 1], jnp.int32)
    row = jnp.asarray(rng.randn(2, C), jnp.float32)
    w = jnp.asarray(rng.randn(TAPS, C), jnp.float32)
    want_out, want_pool = _reference(pool, at, row, w, None)
    fam = metrics.REGISTRY.get("pallas_dispatch_total")
    before = fam.value(kernel="conv_step", path=path)
    pk.enable(mode if mode == "auto" else mode == "on",
              interpret=mode == "on")
    try:
        out, new = conv_over_entries(pool, at, row, w)
    finally:
        pk.enable("auto", interpret=False)
    assert fam.value(kernel="conv_step", path=path) == before + 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(want_pool))
