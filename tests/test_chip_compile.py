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

import math
import os
import re

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


def _ring_dispatches():
    """``pallas_dispatch_total{kernel="ring_paged_attention"}`` by path:
    one decision a window layer a traced program."""
    from paddle_tpu import pallas as pk

    return {p: pk._M_DISPATCH.value(kernel="ring_paged_attention", path=p)
            for p in ("compiled", "interpret", "reference")}


def _under(scope):
    """``scope`` behind the skeleton's own (PR 51: ``decode/model.py``
    names its call sites, outermost): a feed-forward's mechanism lies
    under ``blk_mlp``, a mixer's under ``blk_mixer``."""
    return ("blk_mlp/" if scope.startswith("moe_") else "blk_mixer/") + scope


def _kernel_op_names(text):
    """The op_name of every Pallas custom call of a compiled program:
    what a trace reduction finds a kernel's device events by."""
    return [m.group(1) for line in text.splitlines() if MARKER in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def _assert_grouped_gemm_kernel(text, layers, looped):
    """A grouped prefill bucket: two grouped-GEMM custom calls a routed
    layer (gate and up in one, then down), each under ``moe_experts``
    (inside a share's loop over blocks where ``looped``), which is where
    ``moe_prefill_ms`` finds them; and no ``ragged-dot`` instruction."""
    ops = [op for op in _kernel_op_names(text) if "grouped_gemm" in op]
    assert len(ops) == 2 * layers, ops
    under = "/while/body/moe_experts/" if looped else "/moe_experts/"
    assert all("_prefill_bucket)/blk_mlp/" in op and under in op
               for op in ops), ops
    assert sum("grouped_gemm_gate_up" in op for op in ops) == layers
    assert "ragged-dot" not in text


_RESULT = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\]\S*\s+([\w\-]+)\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")


def _planned_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _assert_step_outputs(compiled, slots, vocab):
    """The decode step hands out float32 logits (S, V) first and, last,
    what the next step is entered with on the device: the greedy choice
    it made of them, int32 (S,), which the host also reads every tick
    in the logits' place, and the lengths it leaves, int32 (S,)."""
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((slots, vocab), jnp.float32)
    for handed_on in out[-2:]:
        assert (handed_on.shape, handed_on.dtype) == ((slots,), jnp.int32)


def _assert_pools_in_place(compiled, n_param_leaves, pool_shape, itemsize,
                           undonated_plan, scatters=None):
    """A program ``(params, k_pool, v_pool, ...) -> (logits, k_pool,
    v_pool, ...)`` compiled for the chip: both pools are aliased input
    to output, and no instruction's result has as many elements as a
    pool or as one layer's slab except the pools' parameters, their
    bitcasts (the flat views the scatters and the kernels take) and the
    in-place scatters (a ``scatter``, and the fusion whose root it is).
    So no copy, slice or rewrite of a pool or a slab is left, and the
    plan is at least two pools under ``undonated_plan``, the same
    program's before its pools were donated.  -> the compiled text."""
    pool_elems = math.prod(pool_shape)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * pool_elems * itemsize
    planned = _planned_bytes(compiled)
    assert planned <= undonated_plan - 2 * pool_elems * itemsize, planned
    text = compiled.as_text()
    header = text[:text.index("\n")]
    for out, arg in ((1, n_param_leaves), (2, n_param_leaves + 1)):
        assert f"{{{out}}}: ({arg}, {{}}, may-alias)" in header, header[:300]
    big = {pool_elems, pool_elems // pool_shape[0]}
    scatter_roots, cur, stray = set(), None, []
    lines = text.splitlines()
    for line in lines:
        c = _COMPUTATION.match(line)
        if c and " = " not in line.split("(")[0]:
            cur = c.group(1)
        elif "ROOT" in line and " scatter(" in line:
            scatter_roots.add(cur)
    n_scatters = 0
    for line in lines:
        r = _RESULT.match(line)
        if not r or not r.group(2):
            continue
        if math.prod(map(int, r.group(2).split(","))) not in big:
            continue
        name, op = r.group(1), r.group(3)
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if op == "scatter":
            n_scatters += 1
        elif not (op in ("parameter", "bitcast") or (
                op == "fusion" and called
                and called.group(1) in scatter_roots)):
            stray.append((name, op))
    assert not stray, stray
    # K and V, every layer (``scatters``: of a pool that is not one
    # slab a layer)
    assert n_scatters == (scatters or 2 * pool_shape[0])
    return text


def _assert_experts_read_where_they_lie(text, experts, d, f):
    """A program whose rows take the dense pass over the experts
    (``models/moe.py:expert_path``): no grouped-GEMM custom call, and
    nothing of the size of a layer's stacked gate, up or down matrices
    but the parameters and their bitcasts, so no transposed or copied
    weight: each matrix is streamed once from where it lies."""
    assert "ragged-dot" not in text
    assert not [op for op in _kernel_op_names(text) if "grouped_gemm" in op]
    stray = []
    for line in text.splitlines():
        r = _RESULT.match(line)
        if not r or not r.group(2):
            continue
        if math.prod(map(int, r.group(2).split(","))) != experts * d * f:
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if not (r.group(3) in ("parameter", "bitcast") or (
                r.group(3) == "fusion" and called
                and called.group(1).startswith("bitcast_fusion"))):
            stray.append((r.group(1), r.group(3)))
    assert not stray, stray


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


@pytest.mark.parametrize("T", [1, 4], ids=["step", "chunk"])
def test_ragged_paged_attention_gqa_compiles(one_chip, T):
    """64 query heads on 8 K/V heads of 128 over 128-row bf16 pages:
    K-EXAONE's full layer at the serving shape (64 slots, 36 pages a
    sequence)."""
    from paddle_tpu.decode import attention as A

    S, Hq, Hkv, D, page, N, P, dt = 64, 64, 8, 128, 128, 3073, 36, \
        jnp.bfloat16
    assert A.fits(page, Hq, D, Hkv)
    text = _compiled_text(
        A.ragged_paged_attention_gqa, one_chip,
        ((S, T, Hq, D), dt), ((N, page, Hkv, D), dt),
        ((N, page, Hkv, D), dt), ((S, P), jnp.int32), ((S,), jnp.int32))
    assert MARKER in text
    assert all("ragged_paged_attention_gqa/" in op
               for op in _kernel_op_names(text))


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
    assert all("_decode_step" in op and "ragged_paged_attention" in op
               for op in ops)


# the decode steps' plans at the parent of PR 27, whose steps were not
# donated and held a second copy of both pools (PERF.md section 4,
# ``perf/scratch_compile.py decode`` / ``scratch_compile_paged.py``)
CEREBRAS_STEP_PLAN_UNDONATED = 13_665_261_056
OLMOE_STEP_PLAN_UNDONATED = 14_397_756_928
OLMOE_CHUNK_PLAN_UNDONATED = 14.42e9


def test_cerebras_decode_step_writes_and_reads_its_pools_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``cerebras-gpt-1.3b`` generate
    configuration at its real sizes (24 layers f32, 320 pages of 32
    rows, 16 slots): the donated pools are aliased, every K/V row is
    scattered into the pool's own buffer and the rpa kernel reads the
    whole pool through moved page tables, so the plan is at least two
    pools under the undonated step's."""
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
    assert planned == 9_314_695_680, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 4,
        CEREBRAS_STEP_PLAN_UNDONATED)
    ops = _kernel_op_names(text)
    assert len(ops) == L
    assert all("_decode_step" in op and "ragged_paged_attention" in op
               for op in ops)


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


def _olmoe_cell(one_chip, monkeypatch):
    """The ``olmoe-1b-7b`` generate configuration at its real sizes, as
    shapes on the described chip: (cfg, params, pool, pool shape, block,
    sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import olmoe

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    g = cfg["generate"]
    d, H, L = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_hidden_layers"])
    dtype = jnp.dtype(g["dtype"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            olmoe.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, layers=L, experts=cfg["num_experts"],
            expert_width=cfg["intermediate_size"], dtype=dtype)))
    shape = (L, g["num_pages"], g["page_size"], H, d // H)
    block = olmoe.OlmoeBlock(top_k=cfg["num_experts_per_tok"])
    return cfg, params, sds(shape, dtype), shape, block, sds


def test_olmoe_decode_step_compiles_with_bf16_pages(one_chip, monkeypatch):
    """The decode step of the ``olmoe-1b-7b`` generate configuration at
    its real sizes (8 layers, 64 experts of 1,024, bf16 weights and
    1,537 pages of 32 bf16 rows, 32 slots): the rpa kernel takes bf16
    pages at (32, 16, 128); the step's 32 rows take the dense pass, so
    the experts ARE 64 masked dense matmuls a projection, batched into
    one: at four rows an expert that reads the same bytes faster than
    the chip's grouped-matmul kernel (``models/moe.py:expert_path``; a
    prefill bucket over its threshold keeps ``jax.lax.ragged_dot``),
    and no expert matrix is transposed or copied on its way; both
    donated pools are aliased and written and read in place, and the
    plan fits the chip.  The plan is pinned here as a literal: the
    configuration's ``planned_bytes`` is the undonated step's (PR 26)
    and is a benchmark file, which PR 27 could not edit."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    S, P = 32, g["pages_per_seq"]
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, P), jnp.int32), sds((S,), jnp.int32),
        sds((S,), jnp.int32), heads=shape[3], page_size=g["page_size"],
        block=block).compile()
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    planned = _planned_bytes(compiled)
    assert planned == 10_367_236_608 < 15.75e9, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2,
        OLMOE_STEP_PLAN_UNDONATED)
    ops = _kernel_op_names(text)
    assert len(ops) == L and all(
        "_decode_step" in op and "ragged_paged_attention" in op
        for op in ops)
    _assert_experts_read_where_they_lie(
        text, cfg["num_experts"], cfg["hidden_size"],
        cfg["intermediate_size"])
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


@pytest.mark.parametrize("bucket", [256, 2048])
def test_olmoe_prefill_bucket_takes_the_path_of_its_rows(
        one_chip, monkeypatch, bucket):
    """The expert layers of the ``olmoe-1b-7b`` cell's prefill programs
    follow ``models/moe.py:expert_path``: the 2,048-row bucket (the
    cell's longest) runs the grouped-GEMM kernel twice a layer (gate
    and up in one call, down) under ``moe_experts`` and holds no
    ``ragged-dot``, a 256-row bucket streams the experts as the decode
    step does and holds neither; both fit the chip."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import moe

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    L = cfg["num_hidden_layers"]
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        sds((bucket,), jnp.int32), sds((), jnp.int32), heads=shape[3],
        block=block).compile()
    assert _planned_bytes(compiled) < 15.75e9
    text = compiled.as_text()
    k, E = cfg["num_experts_per_tok"], cfg["num_experts"]
    if moe.expert_path(bucket, k, E) == "grouped":
        _assert_grouped_gemm_kernel(text, L, looped=False)
    else:
        assert "grouped_gemm" not in text
        _assert_experts_read_where_they_lie(
            text, cfg["num_experts"], cfg["hidden_size"],
            cfg["intermediate_size"])
    assert {moe.expert_path(b, k, E) for b in (256, 2048)} == {
        "dense", "grouped"}


def test_olmoe_suffix_prefill_writes_and_reads_its_pools_in_place(
        one_chip, monkeypatch):
    """The suffix prefill over cached pages (a 136-row chunk at the
    ``olmoe-1b-7b`` cell's sizes, which planned 14.42 GB undonated):
    the same in-place writes and whole-pool reads through the chunked
    kernel, two pools fewer bytes."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, sds = _olmoe_cell(one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    compiled = dm._prefill_chunk.lower(
        params, pool, pool, sds((g["pages_per_seq"],), jnp.int32),
        sds((), jnp.int32), sds((136,), jnp.int32), heads=shape[3],
        page_size=g["page_size"], block=block).compile()
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2,
        OLMOE_CHUNK_PLAN_UNDONATED)
    chunk = [op for op in _kernel_op_names(text)
             if "ragged_paged_attention_chunk" in op]
    assert len(chunk) == L and all("_prefill_chunk" in op for op in chunk)


def _exaone_cell(one_chip, monkeypatch):
    """The ``k-exaone-236b-a23b`` generate configuration at its real
    sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, pool, pool shape, block, table
    width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import exaone_moe as ex

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"][:L])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            ex.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            router_width=cfg["num_experts_published"],
            held=cfg["num_experts"],
            moe_layers=tuple(t == "sparse"
                             for t in cfg["mlp_layer_types"][:L]),
            dtype=dtype)))
    ring = cfg["sliding_window"] // g["page_size"] + 1
    block = ex.ExaoneMoeBlock(
        layer_types=types, kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"], held=(0, cfg["num_experts"]),
        full_pages=g["pages_per_seq"], ring_pages=ring)
    width = g["pages_per_seq"] + ring * sum(t == ex.SLIDING for t in types)
    shape = (1, g["num_pages"], g["page_size"],
             cfg["num_key_value_heads"], cfg["head_dim"])
    assert g["num_pages"] == g["slots"] * width + 1
    return cfg, params, sds(shape, dtype), shape, block, width, sds


def test_exaone_decode_step_reads_both_caches_in_place(one_chip,
                                                       monkeypatch):
    """The decode step of the ``k-exaone-236b-a23b`` configuration at
    its real sizes (layer 0 + 6, 16 held experts of 2,048 beside a
    shared one, 64 heads on 8, 3,073 bf16 pages of 128 rows, 64 slots):
    ONE grouped-heads kernel (the full layer's; the six rings are plain
    XLA) and no other custom call: the step's 64 rows go through the
    16 held experts of a routed layer as batched matmuls that read
    each matrix once where it lies (``models/moe.py:expert_path``), 14
    in-place scatters into the two donated pools and nothing else of a
    pool's size (no slab, no reshaped copy), and a plan of weights +
    pools + 20 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, shape, block, width, sds = _exaone_cell(
        one_chip, monkeypatch)
    g, L, S = cfg["generate"], cfg["num_hidden_layers"], 64
    before = _ring_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block).compile()
    # the six sliding layers' row-major rings stay on the gathered form
    after = _ring_dispatches()
    assert {p: after[p] - before[p] for p in after} == {
        "compiled": 0, "interpret": 0, "reference": 6}
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    planned = _planned_bytes(compiled)
    assert planned == 12_078_473_216 < 15.75e9, planned
    text = _assert_pools_in_place(
        compiled, len(jax.tree.leaves(params)), shape, 2, float("inf"),
        scatters=2 * L)
    gqa = _kernel_op_names(text)
    assert len(gqa) == 1 and "_decode_step)/blk_mixer/attn_full/" in gqa[0]
    assert "ragged_paged_attention_gqa" in gqa[0]
    _assert_experts_read_where_they_lie(
        text, cfg["num_experts"], cfg["hidden_size"],
        cfg["moe_intermediate_size"])
    for scope in ("attn_window", "moe_shared", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


@pytest.mark.parametrize("bucket, plan, parents_plan", [
    (4096, 13_127_315_456, 13_979_091_456),
    (4608, 13_259_582_464, 14_382_459_904)])
def test_exaone_top_prefill_fits_beside_the_weights(one_chip, monkeypatch,
                                                    bucket, plan,
                                                    parents_plan):
    """The 4,096-row prefill bucket (the longest the cell's traffic
    sends) and the 4,608-row one (a sequence's capacity): the plan fits
    the chip beside 10.45 GB of weights and 1.61 GB of pools, both
    pools are aliased, the full layer runs the flash kernel on 64
    repeated heads and the six sliding layers run banded in plain XLA
    (no T x T scores: they would be 4.3 GB a layer).  The routed layers
    of a chip that holds 16 of 128 experts run their grouped GEMMs over
    blocks of 2 x bucket sorted assignments (``moe.grouped_block_rows``)
    and hold nothing of 8 x bucket rows by the model's width, which is
    why the plans lie under the ones of PR 37 (``parents_plan``, the
    4,608-row one the configuration's ``planned_bytes``).  The GEMMs are
    the grouped-GEMM kernel inside the loop over blocks (PR 47; with
    ``ragged_dot`` the plans read 12,948,948,480 and 13,052,315,136)."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import moe

    cfg, params, pool, shape, block, width, sds = _exaone_cell(
        one_chip, monkeypatch)
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        sds((L, bucket), jnp.int32), sds((), jnp.int32),
        heads=cfg["num_attention_heads"], block=block).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * math.prod(shape) * 2
    planned = _planned_bytes(compiled)
    assert planned == plan <= parents_plan < 15.75e9, planned
    text = compiled.as_text()
    assert moe.grouped_block_rows(
        bucket, k, cfg["num_experts"], cfg["num_experts_published"]) \
        == 2 * bucket
    assert re.search(rf"\[{2 * bucket},{cfg['hidden_size']}\]", text)
    assert not re.search(rf"\[{k * bucket},{cfg['hidden_size']}\]", text)
    ops = _kernel_op_names(text)
    flash = [op for op in ops if "grouped_gemm" not in op]
    assert len(flash) == 1
    assert "_prefill_bucket)/blk_mixer/attn_full/" in flash[0]
    assert "flash_attention_fwd" in flash[0]
    # thousands of rows: the experts keep the grouped GEMM
    _assert_grouped_gemm_kernel(text, L - 1, looped=True)


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


# what may hold as many elements as a pool: the pool's parameter (the
# entry's, a loop body's), its bitcasts, a loop's tuple element, and the
# in-place writes: a scatter, a dynamic-update-slice, and the fusion
# whose root one is
_IN_PLACE = ("parameter", "bitcast", "get-tuple-element", "scatter",
             "dynamic-update-slice")


def _pool_sized_strays(text, sizes):
    """[(instruction, op, which pool)] of the instructions with as many
    elements as one of ``sizes`` ({elements: name}) that are neither a
    view of the pool nor an in-place write to it."""
    roots, cur = set(), None
    lines = text.splitlines()
    for line in lines:
        c = _COMPUTATION.match(line)
        if c and " = " not in line.split("(")[0]:
            cur = c.group(1)
        elif "ROOT" in line and (" scatter(" in line
                                 or " dynamic-update-slice(" in line):
            roots.add(cur)
    stray = []
    for line in lines:
        r = _RESULT.match(line)
        if not r or not r.group(2):
            continue
        which = sizes.get(math.prod(map(int, r.group(2).split(","))))
        if which is None:
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if not (r.group(3) in _IN_PLACE or (
                r.group(3) == "fusion" and called
                and called.group(1) in roots)):
            stray.append((r.group(1), r.group(3), which))
    return stray


def _hybrid_sizes(pool, extra):
    return {math.prod(pool.shape): "kv", math.prod(pool.shape[1:]): "kv slab",
            math.prod(extra[0].shape): "state",
            math.prod(extra[1].shape): "conv"}


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


def _granite_cell(one_chip, monkeypatch, pack=None):
    """The ``granite-4.0-h-micro`` generate configuration at its real
    sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, K/V pool, (state_pool, conv_pool),
    block, table width, sds).  ``pack``: another layout of the pages
    (the probe that chose the layout)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import granite_hybrid as gh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    assert cfg["reduced"] == [] and L == 40 == len(cfg["layer_types"])
    dtype = jnp.dtype(g["dtype"])
    types = tuple(cfg["layer_types"])
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["hidden_size"] // H
    Hm, P, N = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                cfg["mamba_d_state"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            gh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=cfg["hidden_size"], heads=H, kv_heads=KV, head_dim=dh,
            layer_types=types, width=cfg["shared_intermediate_size"],
            mamba_n_heads=Hm, mamba_d_head=P, mamba_d_state=N,
            conv=cfg["mamba_d_conv"],
            dtype=dtype)))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == 3_191_396_096
    state_pack = gh.heads_a_row(Hm, P)
    assert (dh, gh.heads_a_row(KV, dh), state_pack) == (64, 2, 2)
    pack = pack or gh.heads_a_row(KV, dh)
    block = gh.GraniteHybridBlock(
        layer_types=types, kv_heads=KV, head_dim=dh, pack=pack,
        state_pack=state_pack, mamba_n_heads=Hm, mamba_d_head=P,
        mamba_d_state=N,
        eps=cfg["rms_norm_eps"],
        full_pages=g["pages_per_seq"])
    full = sum(t == gh.ATTENTION for t in types)
    # two K/V heads of 64 a stored row of 128 lanes: nothing padded
    pool = sds((full, g["num_pages"], g["page_size"],
                KV // pack, pack * dh), dtype)
    E = g["state_entries"]
    assert E == g["slots"] + 1 and (full, L - full) == (4, 36)
    # a layer's state: the state index down the rows, two heads'
    # channels along the lanes
    extra = (sds((L - full, E, Hm // state_pack, N, state_pack * P),
                 jnp.float32),
             sds((L - full, E,
                  *tail_shape(cfg["mamba_d_conv"], Hm * P + 2 * N)), dtype))
    assert extra[1].shape[2:] == (102, 128)
    return cfg, params, pool, extra, block, g["pages_per_seq"] + 1, sds


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 961 pages
GRANITE_PLANS = {"decode": 12_448_998_912, 1920: 12_684_793_856}


def test_granite_decode_step_moves_states_tails_and_pages_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``granite-4.0-h-micro`` configuration at
    its real sizes (36 mamba + 4 attention layers, 961 bf16 pages of
    128 rows of two 64-wide heads a 128-lane row, 65 state entries, 64
    slots): the four cache buffers are aliased input to output; every
    mamba layer advances the slots' states by ONE ``ssd_step`` call
    under ``ssm/ssm_state`` after ONE ``conv_step`` call under
    ``ssm/ssm_conv`` over the rows the same entries keep, each pool its
    kernel's in-place operand, with no gather, no scatter and no loop
    over the slots;
    the four attention layers run the grouped paged kernel on the
    packed pages under ``attn_full`` and write their rows by 8
    scatters; nothing else has a pool's size (this is the probe that
    chose the packed layout: it neither copies a pool nor pads a
    row); the plan is the arguments + 88 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _granite_cell(
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
    assert planned == GRANITE_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    # K and V a full layer, and no other scatter: the tails move by
    # the conv's kernel
    assert sum(" scatter(" in ln for ln in text.splitlines()) == 8
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 4 and all("_decode_step)/blk_mixer/attn_full/" in op
                                 for op in gqa)
    step = [op for op in kernels if "ssd_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(step) == len(conv) == 36 and len(kernels) == 76
    assert all("_decode_step)/blk_mixer/ssm/ssm_state/" in op for op in step)
    # the conv's kernel under its own scope, outside the state's
    assert all("_decode_step)/blk_mixer/ssm/ssm_conv/" in op for op in conv)
    # each writes the pool it was given as its output 1: the states
    # operand 5, the tails operand 4 (entries, rows, taps, bias, pool)
    for name, operand in (("ssd_step/", 5), ("conv_step/", 4)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 36, name
    # no loop over the slots anywhere in a mamba layer
    assert not re.search(r"/ssm/while/", text)
    # the tied head contracts the embedding where it lies
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    assert not [s for s in _pool_sized_strays(text, {emb: "emb"})
                if s[1] in ("copy", "transpose")]


def test_granite_pages_of_unpacked_heads_are_padded_and_copied(
        one_chip, monkeypatch):
    """The layout that was NOT kept: pages of 8 K/V heads of 64, one
    head a stored row.  At the configuration's 961 pages (1.0 GB of
    K/V) the step plans gigabytes of temporaries:
    the compiler pads a row's 64 lanes to 128 and copies the pools to
    that layout around the kernel's calls, where two heads a 128-lane
    row plan the arguments + 88 MB (the case above).  When this fails the compiler's choice has changed
    and ``heads_a_row`` can be looked at again."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _granite_cell(
        one_chip, monkeypatch, pack=1)
    assert pool.shape[3:] == (8, 64)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    kv = 2 * math.prod(pool.shape) * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes > 2 * kv


def test_granite_top_prefill_fits_beside_weights_states_and_pages(
        one_chip, monkeypatch):
    """The 1,920-row prefill bucket (a sequence's capacity; the
    traffic's 1,200-row prompt runs in it): the plan fits the chip
    beside 6.38 GB of weights, 4.97 GB of states and 1.01 GB of pages
    (961: 64 sequences of 15 pages and the null page, all that the 65
    state entries can ever seat; the configuration's
    ``planned_bytes`` is this plan with the tail pool of a row an
    entry, 6 MB more); all four buffers are aliased; the four
    attention layers run the flash kernel at heads of 64; the entry is
    written whole by one dynamic-update-slice a pool, the pages by two
    scatters, and nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _granite_cell(
        one_chip, monkeypatch)
    bucket = 1920
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
    assert planned == GRANITE_PLANS[bucket] < 15.0e9, planned
    # the configuration's figure dates from the tail pool of a row an
    # entry, 65 rows a slab padded to 80: 6 MB over since PR 42
    assert 0 <= cfg["generate"]["planned_bytes"] - planned < 8 << 20
    g = cfg["generate"]
    assert g["num_pages"] == g["slots"] * g["pages_per_seq"] + 1
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    flash = _kernel_op_names(text)
    assert len(flash) == 4 and all(
        "_prefill_bucket)/blk_mixer/attn_full/" in op
        and "flash_attention_fwd" in op
        for op in flash)
    for scope in ("ssm/ssm_scan", "ssm/ssm_conv"):
        assert f"jit(_prefill_bucket)/{_under(scope)}/" in text, scope


# -- latent attention (PR 45) -------------------------------------------------

# memory_analysis() of the two programs at the configuration's 3,971
# pages: what perf/configs/kanana-2-30b-a3b.json records as planned
KANANA_PLANS = {"decode": 14_053_559_296, 8192: 14_998_513_664,
                "8192 kernel": 14_996_863_488}
KANANA_PARAMS = 1_802_973_056


def _kanana_cell(one_chip, monkeypatch):
    """The ``kanana-2-30b-a3b`` generate configuration at its real
    sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, pool, placeholder, block, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import kanana_mla as km

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "kanana-2-30b-a3b.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    block = km.KananaMlaBlock(
        nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        held=(0, cfg["n_routed_experts"]))
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            km.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layers=L, first_dense=cfg["first_k_dense_replace"], dtype=dtype,
            d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            nope=block.nope, rope_dim=block.rope_dim, v_dim=block.v_dim,
            rank=block.rank, dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=(cfg["n_shared_experts"]
                          * cfg["moe_intermediate_size"]),
            router_width=cfg["n_routed_experts_published"],
            held=cfg["n_routed_experts"])))
    assert block.width == g["row_lanes_stored"] == 640
    pool = sds((L, g["num_pages"], g["page_size"], block.width), dtype)
    return cfg, params, pool, sds((L, 1), dtype), block, sds


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


def test_kanana_decode_step_reads_the_latent_rows_in_place(one_chip,
                                                           monkeypatch):
    """The decode step of the ``kanana-2-30b-a3b`` configuration at its
    real sizes (layer 0 + 15, 16 held experts of 768 beside a shared
    one of 1,536, 32 heads, 3,971 pages of 128 rows x 640 lanes, 64
    slots): ONE ``latent_paged_attention`` call a layer under
    ``attn_latent`` and no other custom call, the pool aliased input to
    output, written by a scatter of 64 rows a layer and nothing else of its size or of a
    layer's slab (no copy, no relayout), the 64 rows through the 16
    held experts as batched matmuls with no matrix copied or
    transposed, 1,802,973,056 parameters, a plan of the arguments + 34 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, placeholder, block, sds = _kanana_cell(
        one_chip, monkeypatch)
    g, L, S = cfg["generate"], cfg["num_hidden_layers"], 64
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == KANANA_PARAMS
    per_layer = [sum(math.prod(a.shape) for a in jax.tree.leaves(lp))
                 for lp in params["layers"]]
    assert per_layer[:2] == [64_098_816, 111_547_008]
    compiled = dm._decode_step.lower(
        params, pool, placeholder, sds((S, g["pages_per_seq"]), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block).compile()
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    m = compiled.memory_analysis()
    pool_bytes = math.prod(pool.shape) * 2
    assert pool_bytes == 10_409_738_240
    assert m.alias_size_in_bytes >= pool_bytes
    planned = _planned_bytes(compiled)
    assert planned == KANANA_PLANS["decode"] == g["planned_bytes"] \
        - (KANANA_PLANS[8192] - KANANA_PLANS["decode"]), planned
    text = compiled.as_text()
    sizes = {math.prod(pool.shape): "latent",
             math.prod(pool.shape[1:]): "latent slab"}
    assert not _pool_sized_strays(text, sizes)
    flat = f"[{math.prod(pool.shape[:3])},{block.width}]"
    # (the compiler clones one layer's scatter of 64 rows: 17 of them)
    assert sum(" scatter(" in ln and flat in ln.split(" scatter(")[0]
               for ln in text.splitlines()) in (L, L + 1)
    ops = _kernel_op_names(text)
    assert len(ops) == L
    assert all("_decode_step)/blk_mixer/attn_latent/" in op
               and "latent_paged_attention" in op for op in ops)
    # the 64 rows take the dense pass over the 16 held experts; no
    # matrix of theirs is transposed or copied (the compiler prefetches
    # two layers' down matrices in slices, which is neither)
    assert "ragged-dot" not in text
    assert not [op for op in _kernel_op_names(text) if "grouped_gemm" in op]
    experts = (cfg["n_routed_experts"] * cfg["hidden_size"]
               * cfg["moe_intermediate_size"])
    assert not [s for s in _pool_sized_strays(text, {experts: "experts"})
                if s[1] in ("copy", "transpose")]
    for scope in ("attn_latent/attn_latent_down",
                  "attn_latent/attn_latent_absorb", "moe_shared",
                  "moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope
    assert "attn_latent_expand" not in text


def test_kanana_top_prefill_fits_beside_weights_and_latent_rows(
        one_chip, monkeypatch):
    """The 8,192-row prefill bucket (a sequence's capacity; the cell's
    5,000- and 7,000-row prompts run in it): expanded, the flash kernel
    once a layer under ``attn_latent`` on heads of 192 (the values
    padded to the keys' head size), the pool aliased and written by ONE
    scatter of all 16 layers' rows into the pool seen flat (indexed a
    layer, the compiler laid the pool out layers-innermost and copied
    its 10.4 GB: the first form this PR tried); the plan is what set
    ``num_pages``: the most pages that leave it at or under 15.0 GB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, placeholder, block, sds = _kanana_cell(
        one_chip, monkeypatch)
    g, L, bucket = cfg["generate"], cfg["num_hidden_layers"], 8192
    compiled = dm._prefill_bucket.lower(
        params, pool, placeholder, sds((bucket,), jnp.int32),
        sds((bucket,), jnp.int32), sds((), jnp.int32),
        heads=cfg["num_attention_heads"], block=block).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= math.prod(pool.shape) * 2
    planned = _planned_bytes(compiled)
    # the plan that set ``num_pages`` is the configuration's (PR 45,
    # with ``ragged_dot``); with the grouped-GEMM kernel the compiler's
    # schedule leaves 1,650,176 bytes fewer alive at the peak (PR 47;
    # the configuration is a benchmark file, not this PR's to edit):
    # under the 15.0 GB the pages were counted against (one page more
    # would fit now: 2,621,440 bytes a page)
    assert KANANA_PLANS[bucket] == g["planned_bytes"]
    assert planned == KANANA_PLANS["8192 kernel"] \
        == g["planned_bytes"] - 1_650_176, planned
    page_bytes = L * g["page_size"] * block.width * 2
    # not a page more, by the plan the pages were counted with
    assert planned <= g["planned_bytes"] <= 15.0e9 \
        < g["planned_bytes"] + page_bytes
    text = compiled.as_text()
    sizes = {math.prod(pool.shape): "latent",
             math.prod(pool.shape[1:]): "latent slab"}
    assert not _pool_sized_strays(text, sizes)
    ops = _kernel_op_names(text)
    flash = [op for op in ops if "flash_attention_fwd" in op
             or "_flash_fwd_impl" in op]
    assert len(flash) == L and all(
        "_prefill_bucket)/blk_mixer/attn_latent/" in op for op in flash)
    assert not [op for op in ops if "latent_paged_attention" in op]
    for scope in ("attn_latent_down", "attn_latent_expand"):
        assert (f"_prefill_bucket)/blk_mixer/attn_latent/{scope}/"
                in text), scope
    assert "attn_latent_absorb" not in text
    # thousands of rows: the experts keep the grouped GEMM
    _assert_grouped_gemm_kernel(text, L - 1, looped=True)


# -- a decoder-hybrid-decoder (PR 48) -----------------------------------------

# memory_analysis() of the two programs at the configuration's 7,041
# pages.  The bucket's was 14,930,227,200, which
# perf/configs/phi-4-mini-flash-reasoning.json records, while nine
# layers' bucket-long K/V lived to the program's end and were stacked
# there; a window layer keeps its ring's five pages alone now (PR 52;
# the configuration is a benchmark file, not that PR's to edit).  The
# step's was 12,723,929,088 (and is in that file's ``planned_how``)
# while the rings were gathered, turned and widened
PHI4_PLANS = {"decode": 12_623_595_520, 12288: 14_596_378_112}
PHI4_PARAMS = 3_852_562_944


def _phi4_cell(one_chip, monkeypatch):
    """The ``phi-4-mini-flash-reasoning`` generate configuration at its
    real sizes, as shapes on the described chip, built as its gen_config
    builds the model: (cfg, params, K/V pool, (state_pool, conv_pool),
    block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import phi4_flash as pf

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    g, L, sizes = cfg["generate"], cfg["num_hidden_layers"], \
        cfg["assumed_sizes"]
    assert cfg["reduced"] == [] and L == 32
    dtype = jnp.dtype(g["dtype"])
    types = pf.layer_kinds(L, cfg["mb_per_layer"])
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["hidden_size"])
    dh, C, N = sizes["head_dim"], sizes["mamba_expand"] * d, \
        sizes["mamba_d_state"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            pf.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, heads=H, kv_heads=KV, head_dim=dh, layer_types=types,
            width=cfg["intermediate_size"], d_inner=C, d_state=N,
            dt_rank=sizes["mamba_dt_rank"], conv=sizes["mamba_d_conv"],
            dtype=dtype)))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(params)) \
        == PHI4_PARAMS
    rings = sum(t == pf.WINDOW for t in types)
    ring_pages = cfg["sliding_window"] // g["page_size"] + 1
    assert (rings, ring_pages) == (8, g["ring_pages"]) == (8, 5)
    block = pf.Phi4FlashBlock(
        layer_types=types, kv_heads=KV, head_dim=dh,
        window=cfg["sliding_window"], d_inner=C, d_state=N,
        dt_rank=sizes["mamba_dt_rank"], eps=cfg["layer_norm_eps"],
        full_pages=g["pages_per_seq"], ring_pages=ring_pages,
        page_size=g["page_size"])
    # a K/V PAIR a stored row of 128 lanes, a page's ten stored heads
    # outside its rows: whole tiles whatever the head count
    pool = sds((1, g["num_pages"], KV // 2, g["page_size"], 2 * dh), dtype)
    E, mamba = g["state_entries"], sum(t == pf.MAMBA for t in types)
    assert E == g["slots"] + 1 and mamba == 9
    extra = (sds((mamba, E, N, C), jnp.float32),
             sds((mamba, E, *tail_shape(sizes["mamba_d_conv"], C)), dtype))
    assert extra[1].shape[2:] == (120, 128)
    width = g["pages_per_seq"] + rings * ring_pages + 1
    return cfg, params, pool, extra, block, width, sds


def test_phi4_decode_step_moves_states_rings_and_the_one_run_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``phi-4-mini-flash-reasoning``
    configuration at its real sizes (7,041 bf16 pages of 10 stored heads
    x 128 rows x 128 lanes, 65 state entries of nine (16, 5,120) float32
    states, 64 slots, a table row of 96 + 40 + 1 columns): the four
    cache buffers are aliased input to output and the plan is the
    arguments + 42 MB; every Mamba-1 layer advances the slots' states
    by ONE ``s6_step`` call under ``ssm/ssm_state`` (the pool its
    in-place operand) after ONE ``conv_step`` call under
    ``ssm/ssm_conv``; the page run's owner and the seven cross layers
    run the grouped paged kernel on the heads-major pages under
    ``attn_shared``, EIGHT calls, and only TWO scatters lie under it:
    the owner's K and V row; a cross layer writes nothing; each of the
    eight window layers writes its row (two scatters) and reads its
    ring's five pages where they lie by ONE ``ring_paged_attention``
    call under ``attn_window``, with no gathered copy of a ring beside
    it.  Nothing has
    a pool's size but the pools (this is the probe that chose the
    layout: with the ten heads inside a page's rows, ``(N, 128, 10,
    128)``, the same step planned 5.6 GB of copies of the pool, 1.6
    times its bytes each)."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _phi4_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    assert width == 137
    before = _ring_dispatches()
    compiled = dm._decode_step.lower(
        params, pool, pool, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    after = _ring_dispatches()
    assert {p: after[p] - before[p] for p in after} == {
        "compiled": 8, "interpret": 0, "reference": 0}
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    # the buffers alone are handed back: what layer 16 hands the GMUs
    # is no output
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == PHI4_PLANS["decode"] < 15.0e9, planned
    assert m.temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    # the 18 MB tail pool is small enough that the compiler moves it to
    # fast memory and back round the conv kernels (copy-start / -done to
    # S(1)): no layout copy, and not held here
    sizes = _hybrid_sizes(pool, extra)
    del sizes[math.prod(extra[1].shape)]
    assert not _pool_sized_strays(text, sizes)
    scatters = [ln for ln in text.splitlines() if " scatter(" in ln]
    assert sum("/attn_shared/" in ln for ln in scatters) == 2
    assert all("/attn_shared/" in ln or "/attn_window/" in ln
               for ln in scatters)
    kernels = _kernel_op_names(text)
    gqa = [op for op in kernels if "ragged_paged_attention_gqa/" in op]
    assert len(gqa) == 8 and all("_decode_step)/blk_mixer/attn_shared/" in op
                                 for op in gqa)
    ring = [op for op in kernels if "ring_paged_attention/" in op]
    assert len(ring) == 8 and all("_decode_step)/blk_mixer/attn_window/" in op
                                  for op in ring)
    assert sum("/attn_window/" in ln for ln in scatters) == 16
    # a slot's ring is 5 pages of 10 heads x 128 rows: no gathered copy
    assert not re.search(r"\[64,5,10,128,128\]|\[64,5,128,10,128\]"
                         r"|\[64,640,10,128\]", text)
    step = [op for op in kernels if "s6_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(step) == len(conv) == 9 and len(kernels) == 34
    assert all("_decode_step)/blk_mixer/ssm/ssm_state/" in op for op in step)
    assert all("_decode_step)/blk_mixer/ssm/ssm_conv/" in op for op in conv)
    # each writes the pool it was given as its output 1: the states
    # operand 6 (entries, dt, x, A, B, C, pool), the tails operand 4
    for name, operand in (("s6_step/", 6), ("conv_step/", 4)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 9, name
    assert not re.search(r"/ssm/while/", text)
    for scope in ("attn_window", "gmu"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope


def test_phi4_top_prefill_fits_beside_weights_states_rings_and_the_run(
        one_chip, monkeypatch):
    """The 12,288-row prefill bucket (a sequence's capacity; the
    traffic's 10,500-row prompts run in it): the plan, 14.60 GB, is
    under the configuration's ``planned_bytes`` and fits 15.0 GB beside
    7.71 GB of weights, 4.61 GB of pages and 0.21 GB of state entries;
    all four buffers are aliased; what the prompt leaves in the pools
    is written under ``blk_store`` by ONE scatter a pool of 136 whole
    pages (eight rings' five and the run's 96), and no window layer's
    bucket-long K/V reach it; the selective scan materialises no ``rows x
    5,120 x 16`` tensor (4 GB at this bucket): it is a loop under
    ``ssm/ssm_scan`` whose body holds a state; the layers from the full
    one on run on ONE row (no 12,288-row instruction lies under ``gmu``,
    and the only ones under ``attn_shared`` are the K/V rows'); and
    nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, extra, block, width, sds = _phi4_cell(
        one_chip, monkeypatch)
    bucket = 12288
    compiled = dm._prefill_bucket.lower(
        params, pool, pool, sds((bucket,), jnp.int32),
        (sds((9, bucket), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool, pool) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == PHI4_PLANS[bucket], planned
    assert planned <= cfg["generate"]["planned_bytes"] < 15.0e9
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _hybrid_sizes(pool, extra))
    stores = [ln for ln in text.splitlines()
              if " scatter(" in ln and "/blk_store/" in ln
              and ln.lstrip().startswith("ROOT")]
    pages = [ln for ln in stores if "bf16[7041,10,128,128]" in ln]
    assert len(pages) == 2 and all(
        "update_window_dims={1,2,3}, inserted_window_dims={0}" in ln
        for ln in pages), stores
    assert re.search(r"s32\[136\]\S* [a-z]+\(.*/blk_store/", text)
    assert len(stores) == 2        # the entry's two are slices in place
    # the rows of all nine layers' K/V, stacked: 9 x 12,288
    assert not re.search(r"\[9,12288,10,128\]|\[110592,10,128\]", text)
    assert "12288,16,5120" not in text and "12288,5120,16" not in text
    assert re.search(r"_prefill_bucket\)/blk_mixer/ssm/ssm_scan/while", text)
    for scope in ("ssm/ssm_conv", "attn_window", "attn_shared", "gmu"):
        assert f"jit(_prefill_bucket)/{_under(scope)}/" in text, scope
    for ln in text.splitlines():
        if "/gmu/" in ln:
            assert "12288" not in ln.split("metadata")[0], ln


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


# -- sparse latent attention (PR 53) ------------------------------------------

# memory_analysis() of the programs at the configuration's 4,757 pages.
# perf/configs/glm-5.json records PR 53's (the bucket 14,486,642,176, the
# chunk 14,999,422,464: what set ``num_pages``); since PR 54 the
# selection's int32 keys and masks live in VMEM and both plan less; since
# PR 56 the step fetches no rows (12,539,195,392 with the fetch)
GLM_PLANS = {"decode": 12_521_984_512, 8192: 14_350_835_712,
             "chunk over 25600": 14_998_438_912}
GLM_PARAMS = 3_909_632_768


def _glm_cell(one_chip, monkeypatch):
    """The ``glm-5`` generate configuration at its real sizes, as shapes
    on the described chip, built as its gen_config builds the model:
    (cfg, params, latent pool, index pool, block, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.models import glm_dsa as gd

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", "glm-5.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    block = gd.GlmDsaBlock(
        nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
        eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"],
        held=(0, cfg["n_routed_experts"]),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_rope=cfg["qk_rope_head_dim"], index_topk=cfg["index_topk"])
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            gd.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layers=L, first_dense=cfg["leading_dense_layers"], dtype=dtype,
            d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
            nope=block.nope, rope_dim=block.rope_dim, v_dim=block.v_dim,
            rank=block.rank, q_rank=cfg["q_lora_rank"],
            index_heads=block.index_heads, index_dim=block.index_dim,
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=(cfg["n_shared_experts"]
                          * cfg["moe_intermediate_size"]),
            router_width=cfg["n_routed_experts_published"],
            held=cfg["n_routed_experts"])))
    assert block.width == g["row_lanes_stored"] == 640
    assert block.index_dim == g["index_row_lanes"] == 128
    pages = (L, g["num_pages"], g["page_size"])
    return (cfg, params, sds(pages + (block.width,), dtype),
            sds(pages + (block.index_dim,), dtype), block, sds)


def _glm_pool_sizes(pool, index_pool):
    return {math.prod(pool.shape): "latent",
            math.prod(pool.shape[1:]): "latent slab",
            math.prod(index_pool.shape): "index",
            math.prod(index_pool.shape[1:]): "index slab"}


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


def test_glm_decode_step_walks_the_live_pages_under_the_selected_sets(
        one_chip, monkeypatch):
    """The decode step of the ``glm-5`` configuration at its real sizes
    (1 dense + 4 routed layers, 64 heads, 4,757 pages of 128 rows x (640
    + 128) lanes, 32 slots of 200 table columns): a layer walks the
    slots' live latent pages (``latent_paged_attention``) either way;
    where a slot is over 2,048 rows it first scores the slots' index
    rows (ONE ``paged_index_scores`` call), makes the 2,048 best a slot
    a bias from one read of the 32 x 25,600 scores (``selection_bias``,
    one grid step) and walks under it (``attn_sparse``): no ``top_k``,
    no sort, no gather under the mixer, nothing of the 32 x 2,048
    fetched rows' size; both pools aliased input to output, nothing of a
    pool's or a slab's size copied; 3,909,632,768 parameters; a plan of
    the arguments + 24 MB."""
    from paddle_tpu.decode import model as dm
    from paddle_tpu.observability import metrics

    cfg, params, pool, index_pool, block, sds = _glm_cell(
        one_chip, monkeypatch)
    g, L, S = cfg["generate"], cfg["num_hidden_layers"], 32
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == GLM_PARAMS
    per_layer = [sum(math.prod(a.shape) for a in jax.tree.leaves(lp))
                 for lp in params["layers"]]
    assert per_layer[:2] == [400_898_816, 817_708_032]
    count = metrics.REGISTRY.get("pallas_dispatch_total").value
    kernels = ("paged_index_scores", "selection_bias",
               "latent_paged_attention")
    before = [count(kernel=k, path="compiled") for k in kernels]
    compiled = dm._decode_step.lower(
        params, pool, index_pool, sds((S, g["pages_per_seq"]), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block).compile()
    assert [count(kernel=k, path="compiled") - b
            for k, b in zip(kernels, before)] == [L, L, 2 * L]
    _assert_step_outputs(compiled, S, cfg["vocab_size"])
    m = compiled.memory_analysis()
    pools = (math.prod(pool.shape) + math.prod(index_pool.shape)) * 2
    assert pools == 4757 * 983_040
    assert m.alias_size_in_bytes >= pools
    assert _planned_bytes(compiled) == GLM_PLANS["decode"]
    text = compiled.as_text()
    fetched = {S * cfg["index_topk"] * block.width: "fetched rows"}
    assert not _pool_sized_strays(
        text, {**_glm_pool_sizes(pool, index_pool), **fetched})
    mixer = [line for line in text.splitlines() if "/attn_latent/" in line]
    assert mixer and not [
        line for line in mixer
        if re.search(r"\b(sort|gather|topk)\(|top_k|TopK", line)]
    ops = [op for op in _kernel_op_names(text) if "grouped_gemm" not in op]
    under = "_decode_step)/blk_mixer/attn_latent/cond/"
    for scope, kernel in (("attn_index", "paged_index_scores"),
                          ("attn_index_select", "selection_bias"),
                          ("attn_sparse", "latent_paged_attention")):
        assert sum(under in op and f"/{scope}/" in op and kernel in op
                   for op in ops) == L, scope
    assert sum(under in op and "attn_sparse" not in op
               and "latent_paged_attention" in op for op in ops) == L
    assert len(ops) == 4 * L
    for scope in ("attn_latent/attn_latent_down", "attn_latent/attn_index",
                  "moe_shared", "moe_router"):
        assert f"jit(_decode_step)/{_under(scope)}/" in text, scope
    # 32 rows send a held expert one row in the mean: the grouped path
    assert "jit(_decode_step)/blk_mlp/while/body/moe_experts/" in text
    # the absorbed products stay where the read's seconds leave them out
    assert re.search(
        r"attn_latent/cond/\w+/attn_sparse/attn_latent_absorb/", text)


@pytest.mark.parametrize("program", [8192, "chunk over 25600"])
def test_glm_prefill_programs_fit_beside_weights_and_both_pools(
        one_chip, monkeypatch, program):
    """The 8,192-row top bucket, and a 4,096-row chunk over a sequence's
    whole 25,600 rows (the largest program any request can run: its plan
    set ``num_pages``, the most pages that leave it at or under 15.0
    GB): ``index_scores``, ``selection_bias`` and
    ``selected_flash_attention`` once a layer, under ``attn_index``,
    ``attn_index_select`` and ``attn_sparse``, and no loop of XLA's under
    the selection's scope (the bisection runs inside the kernel); no
    causal flash call (every row past the 2,048th selects); both pools
    aliased and nothing of their size copied; the experts keep the
    grouped GEMM."""
    import functools

    from paddle_tpu.decode import model as dm
    from paddle_tpu.models import glm_dsa as gd
    from paddle_tpu.observability import metrics

    cfg, params, pool, index_pool, block, sds = _glm_cell(
        one_chip, monkeypatch)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"]
    engaged = functools.partial(
        metrics.REGISTRY.get("pallas_dispatch_total").value,
        kernel="selection_bias", path="compiled")
    before = engaged()
    if program == 8192:
        name = "_prefill_bucket"
        compiled = dm._prefill_bucket.lower(
            params, pool, index_pool, sds((8192,), jnp.int32),
            sds((8192,), jnp.int32), sds((), jnp.int32), heads=heads,
            block=block).compile()
    else:
        name = "_prefill_bucket_chunk"
        compiled = gd._prefill_bucket_chunk.lower(
            params, pool, index_pool, sds((g["pages_per_seq"],), jnp.int32),
            sds((), jnp.int32), sds((g["chunk_rows"],), jnp.int32),
            sds((), jnp.int32), heads=heads, page_size=g["page_size"],
            block=block, extent=g["pages_per_seq"]).compile()
    assert engaged() - before == L
    m = compiled.memory_analysis()
    pools = (math.prod(pool.shape) + math.prod(index_pool.shape)) * 2
    assert m.alias_size_in_bytes >= pools
    planned = _planned_bytes(compiled)
    assert planned == GLM_PLANS[program], planned
    page_bytes = L * g["page_size"] * (block.width + block.index_dim) * 2
    # not a page more by the plan the configuration records (PR 53's:
    # the file is the benchmark's); today's largest is 983,552 B under it
    assert max(GLM_PLANS.values()) <= g["planned_bytes"] <= 15.0e9 \
        < g["planned_bytes"] + page_bytes
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _glm_pool_sizes(pool, index_pool))
    ops = _kernel_op_names(text)
    under = f"{name})/blk_mixer/attn_latent/"
    assert sum(under + "attn_index/" in op and "index_scores" in op
               for op in ops) == L
    assert sum(under + "attn_index_select/" in op
               and "selection_bias" in op for op in ops) == L
    assert sum(under + "attn_sparse/" in op
               and "selected_flash_attention" in op for op in ops) == L
    assert not [op for op in ops if "flash_attention_fwd" in op
                or "latent_paged_attention" in op]
    assert f"{under}attn_index_select/while" not in text
    # thousands of rows: the experts keep the grouped GEMM
    gemm = [op for op in ops if "grouped_gemm" in op]
    assert len(gemm) == 2 * (L - 1) and all(
        f"{name})/blk_mlp/while/body/moe_experts/" in op for op in gemm)
    assert "ragged-dot" not in text


# -- Ling-3.0-flash: KDA entries beside a latent page run ---------------------


def _ling_cell(one_chip, monkeypatch):
    """The ``ling-3.0-flash`` generate configuration at its real sizes,
    as shapes on the described chip, built as its gen_config builds the
    model: (cfg, params, the latent pool, the placeholder, (state_pool,
    conv_pool), block, table width, sds)."""
    import functools
    import json

    from paddle_tpu import pallas as pk
    from paddle_tpu.decode.state_entry import tail_shape
    from paddle_tpu.models import ling_hybrid as lh

    monkeypatch.setitem(pk._STATE, "mode", "on")
    monkeypatch.setitem(pk._STATE, "interpret", False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "ling-3.0-flash.json")) as f:
        cfg = json.load(f)
    g, L = cfg["generate"], cfg["num_hidden_layers"]
    dtype = jnp.dtype(g["dtype"])
    types = lh.layer_types_of(L, cfg["layer_group_size"])
    assert list(types) == cfg["layer_types"]
    H, Hl = cfg["num_attention_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            lh.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            layer_types=types, first_dense=cfg["first_k_dense_replace"],
            dtype=dtype, d=cfg["hidden_size"], heads=H,
            nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"], lin_heads=Hl,
            d_k=dk, d_v=dv, conv=cfg["short_conv_kernel_size"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            shared_width=cfg["moe_shared_expert_intermediate_size"],
            router_width=cfg["num_experts_published"],
            held=cfg["num_experts"])))
    # the parameters the configuration's file states, recounted
    assert sum(math.prod(a.shape)
               for a in jax.tree.leaves(params)) == 3_639_533_344
    block = lh.LingHybridBlock(
        layer_types=types,
        latent=lh.LingLatentBlock(
            nope=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"], rank=cfg["kv_lora_rank"],
            eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"])),
        lin_heads=Hl, d_k=dk, d_v=dv,
        lower_bound=float(cfg["kda_lower_bound"]), eps=cfg["rms_norm_eps"],
        top_k=cfg["num_experts_per_tok"],
        scale=cfg["routed_scaling_factor"], held=(0, cfg["num_experts"]),
        groups=(cfg["n_group"], cfg["topk_group"]),
        full_pages=g["pages_per_seq"])
    assert block.latent.width == g["row_lanes_stored"] == 640
    pool = sds((1, g["num_pages"], g["page_size"], 640), dtype)
    E = g["state_entries"]
    tail = tail_shape(cfg["short_conv_kernel_size"], Hl * (2 * dk + dv))
    assert tail == (288, 128)
    extra = (sds((L - 1, E, Hl, dv, 128), jnp.float32),
             sds((L - 1, E, *tail), dtype))
    return (cfg, params, pool, sds((1, 1), dtype), extra, block,
            g["pages_per_seq"] + 1, sds)


def _ling_sizes(pool, extra):
    return {math.prod(pool.shape): "latent",
            math.prod(extra[0].shape): "state",
            math.prod(extra[1].shape): "conv"}


# memory_analysis() for a described v5e: arguments + outputs +
# temporaries - aliased, at the configuration's 8,193 pages
LING_PLANS = {"decode": 10_057_012_224, 8192: 11_521_017_856}


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


def test_ling_decode_step_moves_states_and_latent_rows_in_place(
        one_chip, monkeypatch):
    """The decode step of the ``ling-3.0-flash`` configuration at its
    real sizes (5 KDA layers + 1 latent layer, 8,193 bf16 pages of 128
    latent rows at 640 lanes, 129 state entries, 128 slots): the latent
    pool, the placeholder and both entry pools are aliased input to
    output; the latent layer runs ``latent_paged_attention`` under
    ``attn_latent``; every KDA layer advances the slots' states by ONE
    ``kda_step`` call under ``lin_attn/lin_attn_state`` after ONE
    ``conv_step`` call under ``lin_attn/lin_attn_conv``, each pool its
    kernel's in-place operand, no loop over the slots; the gate stands
    under ``lin_attn_gate`` and the router's group step under
    ``moe_dispatch/moe_group``; nothing else has the state pool's or the
    latent pool's size; the plan is arguments + 15 MB."""
    from paddle_tpu.decode import model as dm

    cfg, params, pool, placeholder, extra, block, width, sds = _ling_cell(
        one_chip, monkeypatch)
    g, S = cfg["generate"], cfg["generate"]["slots"]
    compiled = dm._decode_step.lower(
        params, pool, placeholder, sds((S, width), jnp.int32),
        sds((S,), jnp.int32), sds((S,), jnp.int32),
        heads=cfg["num_attention_heads"], page_size=g["page_size"],
        block=block, extra=extra).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert (out[0].shape, out[0].dtype) == ((S, cfg["vocab_size"]),
                                            jnp.float32)
    assert [o.shape for o in out[-2:]] == [e.shape for e in extra]
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool,) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LING_PLANS["decode"] < 15.0e9, planned
    text = compiled.as_text()
    # no copy of the 1.35 GB state pool nor of the 1.34 GB latent pool;
    # the 47.5 MB tail pool the compiler moves into its fast memory
    # space and back round two of the five ``conv_step`` calls (async
    # copies it hides under the experts' matmuls: 190 MB of a step's
    # ~9.5 GB; Olmo-Hybrid's 40.6 MB pool it leaves where it is): PERF.md
    # section 7
    strays = _pool_sized_strays(text, _ling_sizes(pool, extra))
    assert {(op, which) for _, op, which in strays} <= {("copy-done", "conv")}
    assert len(strays) <= 4, strays
    kernels = _kernel_op_names(text)
    latent = [op for op in kernels if "latent_paged_attention" in op]
    step = [op for op in kernels if "kda_step/" in op]
    conv = [op for op in kernels if "conv_step/" in op]
    assert len(latent) == 1 and len(step) == len(conv) == 5
    assert len(kernels) == 11
    assert "_decode_step)/blk_mixer/attn_latent/" in latent[0]
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_state/" in op
               for op in step)
    assert all("_decode_step)/blk_mixer/lin_attn/lin_attn_conv/" in op
               for op in conv)
    for name, operand in (("kda_step/", 6), ("conv_step/", 3)):
        aliased = f"output_to_operand_aliasing={{{{1}}: ({operand}, {{}})}}"
        assert sum(name in ln and aliased in ln
                   for ln in text.splitlines()) == 5, name
    assert not re.search(r"/lin_attn/while/", text)
    for scope in ("blk_mixer/lin_attn_gate/", "blk_mlp/moe_dispatch/"
                  "moe_group/", "blk_mlp/moe_shared/"):
        assert f"jit(_decode_step)/{scope}" in text, scope
    # 128 rows x 8 of 512: the dense pass over the held experts
    assert "ragged-dot" not in text and "grouped_gemm" not in text


def test_ling_top_prefill_fits_beside_weights_states_and_latent_rows(
        one_chip, monkeypatch):
    """The 8,192-row top bucket (the traffic's 6,000-row prompts run
    it): the plan, which is the configuration's ``planned_bytes``, fits
    the chip beside 7.28 GB of weights, 1.34 GB of latent pages and
    1.40 GB of entries; the pools are aliased; every KDA layer runs ONE
    ``kda_chunked`` call under ``lin_attn/lin_attn_scan``, the latent
    layer the flash kernel under ``attn_latent``, the four routed layers
    the grouped GEMM in a loop over blocks of what is held; the entry
    is written whole, and nothing else has a pool's size."""
    from paddle_tpu.decode import model as dm

    bucket = 8192
    cfg, params, pool, placeholder, extra, block, width, sds = _ling_cell(
        one_chip, monkeypatch)
    compiled = dm._prefill_bucket.lower(
        params, pool, placeholder, sds((bucket,), jnp.int32),
        (sds((bucket,), jnp.int32), sds((), jnp.int32)),
        sds((), jnp.int32), heads=cfg["num_attention_heads"], block=block,
        extra=extra).compile()
    m = compiled.memory_analysis()
    buffers = sum(math.prod(a.shape) * a.dtype.itemsize
                  for a in (pool,) + extra)
    assert m.alias_size_in_bytes >= buffers
    planned = _planned_bytes(compiled)
    assert planned == LING_PLANS[bucket] < 15.0e9, planned
    assert cfg["generate"]["planned_bytes"] == planned
    text = compiled.as_text()
    assert not _pool_sized_strays(text, _ling_sizes(pool, extra))
    kernels = _kernel_op_names(text)
    flash = [op for op in kernels if "flash_attention_fwd" in op]
    scan = [op for op in kernels if "kda_chunked/" in op]
    assert len(flash) == 1 and len(scan) == 5
    assert "_prefill_bucket)/blk_mixer/attn_latent/" in flash[0]
    assert all("_prefill_bucket)/blk_mixer/lin_attn/lin_attn_scan/" in op
               for op in scan)
    assert not re.search(r"/lin_attn_scan/while", text)
    _assert_grouped_gemm_kernel(text, layers=4, looped=True)
    assert len(kernels) == 1 + 5 + 8
