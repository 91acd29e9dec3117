"""Compile the main path's Pallas kernels at real widths for a DESCRIBED
TPU v5e (no chip attached): what the chip's compiler refuses — a dot
form Mosaic does not take, a misaligned slice, too much VMEM — fails
here, at no chip time.  Interpret-mode tests cannot see any of that.
A compile that passes is not a chip run: nothing executes.

The topology is described inside a module-scoped fixture (only one
process may load libtpu, and only after a test of this file has
started), the compiles run in the test's own process, and JAX's
persistent compilation cache is off around them (an entry written for a
described device cannot be read back without one).
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

MARKER = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (slots, heads, head_dim, page, pool pages, pages/seq, dtype): a real
# decode batch, and the /generate model chip_smoke.py serves
RPA_REAL = (64, 16, 128, 16, 2048, 32, jnp.bfloat16)
RPA_TOY = (4, 4, 8, 8, 64, 8, jnp.float32)


@pytest.mark.parametrize("slots_per_block", [1, 4], ids=["plain", "blocked"])
@pytest.mark.parametrize("shape", [RPA_REAL, RPA_TOY], ids=["real", "toy"])
def test_ragged_paged_attention_compiles(one_chip, shape, slots_per_block):
    from paddle_tpu.decode import attention as A

    S, H, D, page, N, P, dt = shape
    text = _compiled_text(
        lambda q, k, v, pt, ln: A.ragged_paged_attention(
            q, k, v, pt, ln, slots_per_block=slots_per_block,
            slot_semantics="parallel"),
        one_chip, ((S, H, D), dt), ((N, page, H, D), dt),
        ((N, page, H, D), dt), ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text


def test_ragged_paged_attention_chunk_compiles(one_chip):
    from paddle_tpu.decode import attention as A

    S, T, H, D, page, N, P, dt = 8, 4, 16, 128, 16, 2048, 32, jnp.bfloat16
    text = _compiled_text(
        A.ragged_paged_attention_chunk, one_chip,
        ((S, T, H, D), dt), ((N, page, H, D), dt), ((N, page, H, D), dt),
        ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_flash_attention_compiles(one_chip, grad):
    from paddle_tpu.pallas.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    qkv = [((384, 1024, 128), jnp.bfloat16)] * 3
    text = _compiled_text(bwd if grad else fwd, one_chip, *qkv)
    assert text.count(MARKER) >= (2 if grad else 1)


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
