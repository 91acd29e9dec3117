"""What the ``tests/test_chip_compile_*.py`` files share; pytest collects
nothing here.  Those files compile the main path's Pallas kernels and
each generate configuration's programs at real widths for a DESCRIBED
TPU v5e (no chip attached): what the chip's compiler refuses — a dot
form Mosaic does not take, a misaligned slice, too much VMEM — fails
there, at no chip time.  Interpret-mode tests cannot see any of that.
A compile that passes is not a chip run: nothing executes.

One file a configuration (its ``_X_cell`` builder, its ``*_PLANS`` and
its cases), so that ``--dist loadfile`` spreads the compiles over the
workers: a new configuration adds a file and edits none.  Here are the
described chip and the readers of a compiled program's text and plan.

The topology is described inside a module-scoped fixture, which every
file imports by name: the call runs only after a test of that file has
started.  Only one process may load libtpu unless the command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, as the driver's and ``scripts/tier1.sh``
do for their six workers; without it the files that land on a second
worker skip.  The compiles run in the test's own process, and JAX's
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


def _walk_dispatches():
    """``pallas_dispatch_total{kernel="ragged_paged_attention_gqa"}`` by
    path: one decision a layer on a page run a traced program, and which
    body the walk took there (PR 63: ``compiled_stored`` a row-major
    bfloat16 page consumed as it is stored, ``compiled`` the widened
    body)."""
    from paddle_tpu import pallas as pk

    return {p: pk._M_DISPATCH.value(kernel="ragged_paged_attention_gqa",
                                    path=p)
            for p in ("compiled", "compiled_stored", "interpret",
                      "interpret_stored", "reference")}


def _walks_took(before, **paths):
    """What ``_walk_dispatches`` counted since ``before``: ``paths`` and
    nothing else."""
    after = _walk_dispatches()
    assert {p: after[p] - before[p] for p in after} == {
        p: paths.get(p, 0) for p in after}


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


def _kernel_grids(text):
    """[(op_name, grid)] of every Pallas custom call of a compiled
    program: the ``iteration_bounds`` of the Mosaic module each call
    carries (its serialized body, parsed as generic MLIR)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    grids = []
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        for line in text.splitlines():
            body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
            if MARKER not in line or not body:
                continue
            asm = ir.Module.parse(base64.b64decode(
                body.group(1))).operation.get_asm(enable_debug_info=False)
            bounds = re.search(r"iteration_bounds = array<i64(?:: ([\d, ]+))?>",
                               asm)
            grids.append((
                re.search(r'op_name="([^"]*)"', line).group(1),
                tuple(int(n) for n in (bounds.group(1) or "").split(",")
                      if n.strip())))
    return grids


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
