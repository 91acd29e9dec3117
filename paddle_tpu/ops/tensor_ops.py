"""Tensor creation / movement ops.

Reference: paddle/operators/{fill_constant,fill_zeros_like,assign,cast,
uniform_random,gaussian_random,increment,concat,split,reshape,transpose,
expand,gather,scatter,fill_constant_batch_size_like,...}_op.cc
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.lod import LoDArray, rewrap, unwrap
from paddle_tpu.ops.common import jnp_dtype, unary
from paddle_tpu.registry import SkipInferShape, infer_same_shape, register_op


# ---------------------------------------------------------------------------
# infer_shape rules (registry-audit ratchet: tensor-movement / gather
# family).  Same contract as the conv/pool rules in nn_ops.py: backfill
# missing output metadata, SkipInferShape when statically unknowable.
# ---------------------------------------------------------------------------


def _shape_var(block, name):
    v = block.find_var(name) if name else None
    if v is None:
        raise SkipInferShape
    return v


def _one_in_out(op, block, in_slot="X", out_slot="Out"):
    ins = op.inputs.get(in_slot, [])
    outs = op.outputs.get(out_slot, [])
    if len(ins) != 1 or len(outs) != 1:
        raise SkipInferShape
    xv, ov = _shape_var(block, ins[0]), _shape_var(block, outs[0])
    if xv.shape is None:
        raise SkipInferShape
    return xv, ov


def _infer_concat_shape(op, block):
    ins = op.inputs.get("X", [])
    outs = op.outputs.get("Out", [])
    if not ins or len(outs) != 1:
        raise SkipInferShape
    xvs = [_shape_var(block, n) for n in ins]
    ov = _shape_var(block, outs[0])
    if any(v.shape is None for v in xvs):
        raise SkipInferShape
    axis = op.attr("axis", 0) % max(1, len(xvs[0].shape))
    base = list(xvs[0].shape)
    if axis >= len(base):
        raise SkipInferShape
    dims = [v.shape[axis] if axis < len(v.shape) else -1 for v in xvs]
    base[axis] = -1 if any(d < 0 for d in dims) else sum(dims)
    if ov.shape is None:
        ov.shape = tuple(base)
    if ov.lod_level == 0 and xvs[0].lod_level:
        ov.lod_level = xvs[0].lod_level


def _infer_split_shape(op, block):
    ins = op.inputs.get("X", [])
    outs = op.outputs.get("Out", [])
    if len(ins) != 1 or not outs:
        raise SkipInferShape
    xv = _shape_var(block, ins[0])
    if xv.shape is None or not xv.shape:
        raise SkipInferShape
    axis = op.attr("axis", 0) % len(xv.shape)
    sections = op.attr("sections", None)
    if sections and len(sections) != len(outs):
        raise SkipInferShape
    for i, name in enumerate(outs):
        ov = _shape_var(block, name)
        if ov.shape is not None:
            continue
        if sections:
            d = int(sections[i])
        elif xv.shape[axis] >= 0:
            d = xv.shape[axis] // max(1, len(outs))
        else:
            d = -1
        shape = list(xv.shape)
        shape[axis] = d
        ov.shape = tuple(shape)


def _infer_reshape_shape(op, block):
    xv, ov = _one_in_out(op, block)
    if ov.shape is not None:
        return
    shape = [int(s) for s in (op.attr("shape", ()) or ())]
    if not shape:
        raise SkipInferShape
    shape = [xv.shape[i] if s == 0 and i < len(xv.shape) else s
             for i, s in enumerate(shape)]
    if shape.count(-1) == 1 and all(d >= 0 for d in xv.shape):
        total = 1
        for d in xv.shape:
            total *= d
        known = 1
        for d in shape:
            if d > 0:
                known *= d
        if known > 0 and total % known == 0:
            shape[shape.index(-1)] = total // known
    ov.shape = tuple(shape)


def _infer_transpose_shape(op, block):
    xv, ov = _one_in_out(op, block)
    perm = op.attr("axis", None)
    if not perm or len(perm) != len(xv.shape):
        raise SkipInferShape
    if ov.shape is None:
        ov.shape = tuple(xv.shape[int(p)] for p in perm)


def _infer_expand_shape(op, block):
    xv, ov = _one_in_out(op, block)
    times = op.attr("expand_times", None)
    # only the matched-rank tile; rank-promoting tiles stay dynamic
    if not times or len(times) != len(xv.shape):
        raise SkipInferShape
    if ov.shape is None:
        ov.shape = tuple(d * int(t) if d >= 0 else -1
                         for d, t in zip(xv.shape, times))


def _infer_gather_shape(op, block):
    xv, ov = _one_in_out(op, block)
    idxs = op.inputs.get("Index", [])
    if len(idxs) != 1:
        raise SkipInferShape
    iv = _shape_var(block, idxs[0])
    if iv.shape is None:
        raise SkipInferShape
    if ov.shape is None:
        # jnp.take(x, idx, axis=0): idx dims replace x's leading dim
        ov.shape = tuple(iv.shape) + tuple(xv.shape[1:])


def _infer_scatter_shape(op, block):
    rv, ov = _one_in_out(op, block, "Ref", "Out")
    if ov.shape is None:
        ov.shape = tuple(rv.shape)


def _infer_shape_op_shape(op, block):
    xv, ov = _one_in_out(op, block, "Input", "Out")
    if ov.shape is None:
        ov.shape = (len(xv.shape),)


def _infer_one_hot_shape(op, block):
    xv, ov = _one_in_out(op, block)
    depth = op.attr("depth", None)
    if not depth:
        raise SkipInferShape
    if ov.shape is None:
        shape = tuple(xv.shape)
        if shape and shape[-1] == 1:   # trailing id dim is squeezed
            shape = shape[:-1]
        ov.shape = shape + (int(depth),)


def _infer_attr_shape(op, block):
    # source ops (no tensor inputs) whose static shape IS their "shape"
    # attribute: fill_constant, uniform_random, gaussian_random, ...
    outs = op.outputs.get("Out", [])
    if len(outs) != 1:
        raise SkipInferShape
    ov = _shape_var(block, outs[0])
    shape = op.attr("shape", None)
    if not shape:
        raise SkipInferShape
    if ov.shape is None:
        ov.shape = tuple(int(s) for s in shape)


def _infer_fill_bsl_shape(op, block):
    xv, ov = _one_in_out(op, block, in_slot="Input")
    shape = list(op.attr("shape", None) or [])
    in_idx = int(op.attr("input_dim_idx", 0) or 0)
    out_idx = int(op.attr("output_dim_idx", 0) or 0)
    if (not shape or in_idx >= len(xv.shape) or out_idx >= len(shape)):
        raise SkipInferShape
    shape[out_idx] = xv.shape[in_idx]
    if ov.shape is None:
        ov.shape = tuple(int(s) for s in shape)


def _infer_lookup_table_shape(op, block):
    # Ids (..., 1) int64 against W (V, D) -> Out (..., D); Out rides
    # Ids' LoD (sequence embedding keeps the sequence structure)
    ws = op.inputs.get("W", [])
    ids = op.inputs.get("Ids", [])
    outs = op.outputs.get("Out", [])
    if len(ws) != 1 or len(ids) != 1 or len(outs) != 1:
        raise SkipInferShape
    wv = _shape_var(block, ws[0])
    iv = _shape_var(block, ids[0])
    ov = _shape_var(block, outs[0])
    if wv.shape is None or iv.shape is None:
        raise SkipInferShape
    base = tuple(iv.shape)
    if base and base[-1] == 1:
        base = base[:-1]
    if ov.shape is None:
        ov.shape = base + (wv.shape[-1],)
    if ov.lod_level == 0 and iv.lod_level:
        ov.lod_level = iv.lod_level


@register_op("fill_constant", inputs=(), stop_gradient=True,
             infer_shape=_infer_attr_shape)
def _fill_constant(ctx):
    shape = tuple(ctx.attr("shape", ()))
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    value = ctx.attr("value", 0.0)
    ctx.set_output("Out", jnp.full(shape, value, dtype=dtype))


@register_op("fill_constant_batch_size_like", inputs=("Input",), stop_gradient=True,
             infer_shape=_infer_fill_bsl_shape)
def _fill_constant_bsl(ctx):
    ref = unwrap(ctx.input("Input"))
    shape = list(ctx.attr("shape"))
    in_idx = ctx.attr("input_dim_idx", 0)
    out_idx = ctx.attr("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    ctx.set_output("Out", jnp.full(tuple(shape), ctx.attr("value", 0.0), dtype=dtype))


@register_op("fill_zeros_like", inputs=("X",), stop_gradient=True, infer_shape=infer_same_shape)
def _fill_zeros_like(ctx):
    unary(ctx, jnp.zeros_like)


@register_op("assign", inputs=("X",), infer_shape=infer_same_shape)
def _assign(ctx):
    ctx.set_output("Out", ctx.input("X"))


@register_op("cast", inputs=("X",), infer_shape=infer_same_shape)
def _cast(ctx):
    dtype = jnp_dtype(ctx.attr("out_dtype", ctx.attr("dtype", "float32")))
    unary(ctx, lambda x: x.astype(dtype))


@register_op("uniform_random", inputs=(), stop_gradient=True,
             infer_shape=_infer_attr_shape)
def _uniform_random(ctx):
    shape = tuple(ctx.attr("shape"))
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    lo, hi = ctx.attr("min", -1.0), ctx.attr("max", 1.0)
    seed = ctx.attr("seed", 0)
    key = jax.random.key(seed) if seed else ctx.rng()
    ctx.set_output("Out", jax.random.uniform(key, shape, dtype=jnp.float32, minval=lo, maxval=hi).astype(dtype))


@register_op("gaussian_random", inputs=(), stop_gradient=True,
             infer_shape=_infer_attr_shape)
def _gaussian_random(ctx):
    shape = tuple(ctx.attr("shape"))
    dtype = jnp_dtype(ctx.attr("dtype", "float32"))
    mean, std = ctx.attr("mean", 0.0), ctx.attr("std", 1.0)
    seed = ctx.attr("seed", 0)
    key = jax.random.key(seed) if seed else ctx.rng()
    ctx.set_output("Out", (jax.random.normal(key, shape) * std + mean).astype(dtype))


@register_op("increment", inputs=("X",), stop_gradient=True, infer_shape=infer_same_shape)
def _increment(ctx):
    step = ctx.attr("step", 1.0)
    unary(ctx, lambda x: x + jnp.asarray(step, x.dtype))


@register_op("concat", inputs=("X",), infer_shape=_infer_concat_shape)
def _concat(ctx):
    xs = ctx.inputs("X")
    axis = ctx.attr("axis", 0)
    datas = [unwrap(x) for x in xs]
    ctx.set_output("Out", rewrap(xs[0], jnp.concatenate(datas, axis=axis)))


@register_op("split", inputs=("X",), infer_shape=_infer_split_shape)
def _split(ctx):
    x = unwrap(ctx.input("X"))
    axis = ctx.attr("axis", 0)
    num = ctx.attr("num", 0)
    sections = ctx.attr("sections", None)
    if sections:
        idx = []
        acc = 0
        for s in sections[:-1]:
            acc += s
            idx.append(acc)
        parts = jnp.split(x, idx, axis=axis)
    else:
        parts = jnp.split(x, num, axis=axis)
    ctx.set_outputs("Out", parts)


@register_op("reshape", inputs=("X",), infer_shape=_infer_reshape_shape)
def _reshape(ctx):
    x = unwrap(ctx.input("X"))
    shape = list(ctx.attr("shape"))
    # one -1 wildcard and 0 = copy-input-dim, as in the reference
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    ctx.set_output("Out", jnp.reshape(x, shape))


@register_op("transpose", inputs=("X",),
             infer_shape=_infer_transpose_shape)
def _transpose(ctx):
    x = unwrap(ctx.input("X"))
    ctx.set_output("Out", jnp.transpose(x, ctx.attr("axis")))


@register_op("expand", inputs=("X",), infer_shape=_infer_expand_shape)
def _expand(ctx):
    x = unwrap(ctx.input("X"))
    times = ctx.attr("expand_times")
    ctx.set_output("Out", jnp.tile(x, times))


@register_op("gather", inputs=("X", "Index"), diff_inputs=("X",),
             infer_shape=_infer_gather_shape)
def _gather(ctx):
    x = unwrap(ctx.input("X"))
    idx = unwrap(ctx.input("Index")).astype(jnp.int32)
    ctx.set_output("Out", jnp.take(x, idx, axis=0))


@register_op("scatter", inputs=("Ref", "Index", "Updates"),
             diff_inputs=("Ref", "Updates"),
             infer_shape=_infer_scatter_shape)
def _scatter(ctx):
    ref = unwrap(ctx.input("Ref"))
    idx = unwrap(ctx.input("Index")).astype(jnp.int32)
    upd = unwrap(ctx.input("Updates"))
    ctx.set_output("Out", ref.at[idx].set(upd))


def _lookup_table_grad_lower(ctx):
    """W@GRAD for lookup_table (reference: operators/lookup_table_op.cc
    LookupTableGradKernel).  With ``is_sparse`` the cotangent is kept as
    a static-shape SelectedRows (`paddle_tpu.sparse.SparseGrad`) — the
    (N, D) looked-up rows plus their indices — so no (vocab, D) dense
    gradient is ever built; otherwise a dense scatter-add."""
    from paddle_tpu.sparse import SparseGrad

    gname = ctx.op.outputs.get("W@GRAD", [""])[0]
    if not gname:
        return
    fwd_inputs = ctx.op.attr("__fwd_inputs__")
    fwd_attrs = ctx.op.attr("__fwd_attrs__")
    w = unwrap(ctx.values[fwd_inputs["W"][0]])
    ids_data = unwrap(ctx.values[fwd_inputs["Ids"][0]]).astype(jnp.int32)
    flat = ids_data[..., 0] if ids_data.shape[-1] == 1 else ids_data
    g = unwrap(ctx.input("Out@GRAD"))
    rows = flat.reshape(-1)
    vals = g.reshape(-1, g.shape[-1])
    padding_idx = fwd_attrs.get("padding_idx")
    if padding_idx is not None and padding_idx >= 0:
        vals = vals * (rows != padding_idx)[:, None].astype(vals.dtype)
    if fwd_attrs.get("is_sparse"):
        ctx.values[gname] = SparseGrad(rows, vals, w.shape[0])
    else:
        ctx.values[gname] = jnp.zeros_like(w).at[rows].add(vals.astype(w.dtype))


@register_op("lookup_table", inputs=("W", "Ids"), diff_inputs=("W",),
             grad_lower=_lookup_table_grad_lower,
             infer_shape=_infer_lookup_table_shape)
def _lookup_table(ctx):
    """Embedding lookup (reference: operators/lookup_table_op.cc).  Ids of
    shape (..., 1) int64; gradient w.r.t. W is a SelectedRows-style
    (rows, values) pair when ``is_sparse`` else a dense scatter-add."""
    w = unwrap(ctx.input("W"))
    ids = ctx.input("Ids")
    ids_data = unwrap(ids).astype(jnp.int32)
    squeeze = ids_data.shape[-1] == 1
    flat = ids_data[..., 0] if squeeze else ids_data
    padding_idx = ctx.attr("padding_idx", None)
    out = jnp.take(w, flat, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (flat != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    ctx.set_output("Out", rewrap(ids, out))


@register_op("shape", inputs=("Input",), stop_gradient=True,
             infer_shape=_infer_shape_op_shape)
def _shape(ctx):
    x = unwrap(ctx.input("Input"))
    ctx.set_output("Out", jnp.asarray(x.shape, dtype=jnp.int32))


@register_op("slice_tensor", inputs=("X",))
def _slice_tensor(ctx):
    x = unwrap(ctx.input("X"))
    axes = ctx.attr("axes")
    starts = ctx.attr("starts")
    ends = ctx.attr("ends")
    sl = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        sl[ax] = slice(st, en)
    ctx.set_output("Out", x[tuple(sl)])


@register_op("one_hot", inputs=("X",), stop_gradient=True,
             infer_shape=_infer_one_hot_shape)
def _one_hot(ctx):
    x = unwrap(ctx.input("X")).astype(jnp.int32)
    if x.ndim and x.shape[-1] == 1:
        x = x[..., 0]
    depth = ctx.attr("depth")
    ctx.set_output("Out", jax.nn.one_hot(x, depth, dtype=jnp.float32))


@register_op("reverse", inputs=("X",), infer_shape=infer_same_shape)
def _reverse(ctx):
    """Flip along `axis` (reference capability: RotateLayer's flip half;
    fluid gained a reverse op in later versions)."""
    x = unwrap(ctx.input("X"))
    axis = ctx.attr("axis", 0)
    ctx.set_output("Out", rewrap(ctx.input("X"), jnp.flip(x, axis=axis)))
