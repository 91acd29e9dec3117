"""The ``cerebras-gpt-1.3b`` generate configuration's programs (and the toy
/generate model's step, the same ``decode/model.py`` block) compiled
for the described v5e
(``tests/chip_compile.py``: no chip attached, nothing executes).
"""

import jax
import jax.numpy as jnp

from tests.chip_compile import (  # noqa: F401 (one_chip: a fixture)
    _assert_pools_in_place, _assert_step_outputs, _kernel_grids,
    _kernel_op_names, one_chip, _planned_bytes)


def test_decode_step_names_its_kernel_and_its_wrapper(one_chip, monkeypatch):
    """The jitted decode step of the /generate model: every Pallas
    custom call's op_name holds the kernel's own name (what the
    per-kernel metrics of a later PR match) and the jitted wrapper's
    (what ``rpa_ms_per_step`` matches today)."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import model as dm

    # jax's backend is the CPU here, so "auto" would take the jnp
    # reference: steer the dispatch in the test, as on the chip
    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    lm_kw = dict(vocab=64, d=32, heads=4, layers=2, max_len=64)
    params = jax.eval_shape(
        lambda: dm._init_params(jax.random.key(0), **lm_kw))
    S, N, pg, P = 4, 16, 8, 8
    dh = lm_kw["d"] // lm_kw["heads"]
    pool = ((lm_kw["layers"], N, pg, lm_kw["heads"], dh), jnp.float32)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = dm._decode_step.lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), params),
        sds(*pool), sds(*pool), sds((S, P), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=lm_kw["heads"], page_size=pg).compile().as_text()
    ops = _kernel_op_names(text)
    assert len(ops) == lm_kw["layers"]
    assert all("_decode_step" in op and "ragged_paged_attention/" in op
               for op in ops)
    # heads of 8 lanes: ``walk_fits`` refuses, the (S, P) grid compiles
    assert [grid for _, grid in _kernel_grids(text)] == [
        (S, P)] * lm_kw["layers"]


# the decode steps' plans at the parent of PR 27, whose steps were not
# donated and held a second copy of both pools (PERF.md section 4,
# ``perf/scratch_compile.py decode`` / ``scratch_compile_paged.py``)
CEREBRAS_STEP_PLAN_UNDONATED = 13_665_261_056


def test_cerebras_decode_step_writes_and_reads_its_pools_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``cerebras-gpt-1.3b`` generate
    configuration at its real sizes (24 layers f32, 320 pages of 32
    rows, 16 slots): the donated pools are aliased, every K/V row is
    scattered into the pool's own buffer and the rpa kernel reads the
    whole pool through moved page tables, so the plan is at least two
    pools under the undonated step's.  The kernel walks a slot's live
    pages, one grid step a slot (PR 60: ``walk_fits`` takes the f32
    pages; the plan was 9,314,695,680 under the ``(S, P)`` grid)."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import model as dm

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    d, H, L, N, pg, S, P = 2048, 16, 24, 320, 32, 16, 40

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: dm._init_params(
            jax.random.key(0), 50257, d, H, L, 2048)))
    shape = (L, N, pg, H, d // H)
    pool = sds(shape, jnp.float32)
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, P), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), heads=H, page_size=pg).compile()
    _assert_step_outputs(compiled, S, 50257)
    planned = _planned_bytes(compiled)
    assert planned == 9_314_921_472, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 4,
        CEREBRAS_STEP_PLAN_UNDONATED)
    ops = _kernel_op_names(text)
    assert len(ops) == L
    assert all("_decode_step" in op and "ragged_paged_attention/" in op
               for op in ops)
    assert [grid for _, grid in _kernel_grids(text)] == [(S,)] * L


def test_prefill_top_bucket_fits_and_aliases_its_pools(one_chip, monkeypatch):
    """The 1,280-row prefill bucket of the ``cerebras-gpt-1.3b``
    generate configuration (24 layers f32, 320 pages of 32 rows): the
    two facts a CPU run cannot see.  The plan fits the chip's 16 GB,
    and both donated pools are aliased input to output, so an
    admission writes its rows in place and copies no pool."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.decode import model as dm

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    bucket, d, H, L, N, pg = 1280, 2048, 16, 24, 320, 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: dm._init_params(
            jax.random.key(0), 50257, d, H, L, 2048)))
    pool = sds((L, N, pg, H, d // H), jnp.float32)
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        sds((bucket,), jnp.int32), sds((), jnp.int32), heads=H).compile()
    m = compiled.memory_analysis()
    pool_bytes = L * N * pg * d * 4
    assert m.alias_size_in_bytes >= 2 * pool_bytes
    planned = (m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert planned < 16e9, planned
    text = compiled.as_text()
    header = text[:text.index("\n")]
    n_leaves = len(jax.tree.leaves(params))
    for out, arg in ((1, n_leaves), (2, n_leaves + 1)):
        assert f"{{{out}}}: ({arg}, {{}}, may-alias)" in header, header[:300]
    # one flash-attention kernel a layer at this length, under the
    # prefill program's name
    ops = _kernel_op_names(text)
    assert len(ops) == L
    assert all("_prefill_bucket" in op and "flash_attention_fwd" in op
               for op in ops)
