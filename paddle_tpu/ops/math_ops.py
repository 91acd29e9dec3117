"""Dense math ops.

Reference: paddle/operators/{mul,matmul,elementwise_*,sum,scale,sign,
clip,clip_by_norm,cos_sim,squared_l2_norm,squared_l2_distance,cast,
logical_*,compare}_op.cc — all lowered to jnp/lax so the MXU gets
large fused matmuls instead of per-op kernel launches.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from paddle_tpu.lod import rewrap, unwrap
from paddle_tpu.ops.common import broadcast_to_x, elementwise, unary
from paddle_tpu.registry import SkipInferShape, infer_same_shape, register_op


def _dim_known(d) -> bool:
    return d is not None and d >= 0


def _static_numel(shape):
    """Product of dims, or None if any is dynamic."""
    n = 1
    for d in shape:
        if not _dim_known(d):
            return None
        n *= d
    return n


def _infer_mul_shape(op, block):
    """mul flattens X to 2-D at x_num_col_dims and Y at y_num_col_dims
    (reference: operators/mul_op.cc InferShape): Out keeps X's leading
    dims and Y's trailing dims.  Validates the contracted extents when
    both are static; backfills Out's shape when missing."""
    xv = block.find_var(op.input("X")[0]) if op.input("X") else None
    yv = block.find_var(op.input("Y")[0]) if op.input("Y") else None
    ov = block.find_var(op.output("Out")[0]) if op.output("Out") else None
    if xv is None or yv is None or ov is None:
        raise SkipInferShape
    if xv.shape is None or yv.shape is None:
        raise SkipInferShape
    xn = op.attr("x_num_col_dims", 1)
    yn = op.attr("y_num_col_dims", 1)
    if not (0 < xn <= len(xv.shape) and 0 < yn <= len(yv.shape)):
        raise ValueError(
            f"num_col_dims ({xn}, {yn}) out of range for shapes "
            f"{xv.shape} x {yv.shape}")
    k_x = _static_numel(xv.shape[xn:])
    k_y = _static_numel(yv.shape[:yn])
    if k_x is not None and k_y is not None and k_x != k_y:
        raise ValueError(
            f"contracted extents differ: X{list(xv.shape)} flattened at "
            f"{xn} gives K={k_x}, Y{list(yv.shape)} flattened at {yn} "
            f"gives K={k_y}")
    if ov.shape is None:
        ov.shape = tuple(xv.shape[:xn]) + tuple(yv.shape[yn:])


def _infer_matmul_shape(op, block):
    """Batched matmul: Out is (batch..., M, N) after transpose attrs.
    Validates the inner extents when static; backfills Out's shape."""
    xv = block.find_var(op.input("X")[0]) if op.input("X") else None
    yv = block.find_var(op.input("Y")[0]) if op.input("Y") else None
    ov = block.find_var(op.output("Out")[0]) if op.output("Out") else None
    if xv is None or yv is None or ov is None:
        raise SkipInferShape
    if xv.shape is None or yv.shape is None:
        raise SkipInferShape
    xs, ys = list(xv.shape), list(yv.shape)
    if len(xs) < 2 or len(ys) < 2:
        raise SkipInferShape  # 1-D operands follow numpy promotion rules
    if op.attr("transpose_X", False):
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if op.attr("transpose_Y", False):
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if _dim_known(xs[-1]) and _dim_known(ys[-2]) and xs[-1] != ys[-2]:
        raise ValueError(
            f"inner extents differ: {xv.shape} @ {yv.shape} "
            f"(K={xs[-1]} vs {ys[-2]})")
    if ov.shape is None:
        # numpy-style broadcast over the leading batch dims
        xb, yb = xs[:-2], ys[:-2]
        if len(xb) < len(yb):
            xb = [1] * (len(yb) - len(xb)) + xb
        else:
            yb = [1] * (len(xb) - len(yb)) + yb
        batch = []
        for a, b in zip(xb, yb):
            if a == 1:
                batch.append(b)
            elif b == 1:
                batch.append(a)
            elif not _dim_known(a) or not _dim_known(b):
                batch.append(-1)
            elif a == b:
                batch.append(a)
            else:
                raise ValueError(
                    f"batch dims do not broadcast: {xv.shape} @ {yv.shape}")
        ov.shape = tuple(batch) + (xs[-2], ys[-1])


def _infer_sum_shape(op, block):
    """sum's Out mirrors the first X operand with a known shape."""
    outs = op.output("Out")
    if len(outs) != 1 or not outs[0]:
        raise SkipInferShape
    ov = block.find_var(outs[0])
    if ov is None:
        raise SkipInferShape
    for name in op.input("X"):
        xv = block.find_var(name) if name else None
        if xv is not None and xv.shape is not None:
            if ov.shape is None:
                ov.shape = tuple(xv.shape)
            if ov.lod_level == 0 and xv.lod_level:
                ov.lod_level = xv.lod_level
            return
    raise SkipInferShape


def _pref():
    from paddle_tpu import amp

    return amp.preferred_acc()


def _flatten2d(x, num_col_dims):
    lead = 1
    for s in x.shape[:num_col_dims]:
        lead *= s
    rest = 1
    for s in x.shape[num_col_dims:]:
        rest *= s
    return jnp.reshape(x, (lead, rest))


@register_op("mul", inputs=("X", "Y"), infer_shape=_infer_mul_shape)
def _mul(ctx):
    """Flattening matmul (reference: operators/mul_op.cc): X flattened to
    2-D at x_num_col_dims, Y at y_num_col_dims."""
    x = unwrap(ctx.input("X"))
    y = unwrap(ctx.input("Y"))
    xn = ctx.attr("x_num_col_dims", 1)
    yn = ctx.attr("y_num_col_dims", 1)
    from paddle_tpu import amp

    out_dt = amp.out_dtype(x)
    x2, y2 = amp.cast_operands(_flatten2d(x, xn), _flatten2d(y, yn))
    out = jnp.dot(x2, y2, preferred_element_type=_pref()).astype(out_dt)
    out_shape = x.shape[:xn] + y.shape[yn:]
    ctx.set_output("Out", rewrap(ctx.input("X"), jnp.reshape(out, out_shape)))


@register_op("matmul", inputs=("X", "Y"), infer_shape=_infer_matmul_shape)
def _matmul(ctx):
    x = unwrap(ctx.input("X"))
    y = unwrap(ctx.input("Y"))
    if ctx.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if ctx.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    from paddle_tpu import amp

    out_dt = amp.out_dtype(x)
    x, y = amp.cast_operands(x, y)
    out = jnp.matmul(x, y, preferred_element_type=_pref()).astype(out_dt)
    ctx.set_output("Out", out)


for name, fn in [
    ("elementwise_add", jnp.add),
    ("elementwise_sub", jnp.subtract),
    ("elementwise_mul", jnp.multiply),
    ("elementwise_div", jnp.divide),
    ("elementwise_max", jnp.maximum),
    ("elementwise_min", jnp.minimum),
    ("elementwise_pow", jnp.power),
]:
    # Out mirrors X: the reference broadcast rule aligns Y's dims to a
    # run of X's, so X's shape is always the output shape
    register_op(name, inputs=("X", "Y"), infer_shape=infer_same_shape)(
        functools.partial(lambda ctx, f: elementwise(ctx, f), f=fn))


@register_op("sum", inputs=("X",), infer_shape=_infer_sum_shape)
def _sum(ctx):
    from paddle_tpu.sparse import SparseGrad, concat_sparse

    raw = ctx.inputs("X")
    if all(isinstance(v, SparseGrad) for v in raw):
        # Sum of SelectedRows = row concatenation (reference:
        # operators/sum_op.h SelectedRows branch) — stays sparse.
        ctx.set_output("Out", concat_sparse(raw))
        return
    xs = [unwrap(v) for v in raw]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    template = next((v for v in raw if not isinstance(v, SparseGrad)), raw[0])
    ctx.set_output("Out", rewrap(template, out))


@register_op("scale", inputs=("X",), infer_shape=infer_same_shape)
def _scale(ctx):
    s = ctx.attr("scale", 1.0)
    b = ctx.attr("bias", 0.0)
    unary(ctx, lambda x: x * jnp.asarray(s, x.dtype) + jnp.asarray(b, x.dtype))


@register_op("sign", inputs=("X",), stop_gradient=True,
             infer_shape=infer_same_shape)
def _sign(ctx):
    unary(ctx, jnp.sign)


@register_op("clip", inputs=("X",), infer_shape=infer_same_shape)
def _clip(ctx):
    lo, hi = ctx.attr("min"), ctx.attr("max")
    unary(ctx, lambda x: jnp.clip(x, lo, hi))


@register_op("clip_by_norm", inputs=("X",), infer_shape=infer_same_shape)
def _clip_by_norm(ctx):
    max_norm = ctx.attr("max_norm")
    def f(x):
        norm = jnp.sqrt(jnp.sum(jnp.square(x)))
        scale = jnp.minimum(max_norm / jnp.maximum(norm, 1e-12), 1.0)
        return x * scale
    unary(ctx, f)


def _slot_var(op, block, slot, inputs=True, need_shape=False):
    names = (op.inputs if inputs else op.outputs).get(slot, [])
    if len(names) != 1 or not names[0]:
        raise SkipInferShape
    v = block.find_var(names[0])
    if v is None or (need_shape and v.shape is None):
        raise SkipInferShape
    return v


def _set_shape(v, shape):
    if v.shape is None:
        v.shape = tuple(int(s) for s in shape)


def _infer_squared_l2_norm_shape(op, block):
    _slot_var(op, block, "X", need_shape=True)
    _set_shape(_slot_var(op, block, "Out", inputs=False), (1,))


def _infer_squared_l2_distance_shape(op, block):
    xv = _slot_var(op, block, "X", need_shape=True)
    _set_shape(_slot_var(op, block, "sub_result", inputs=False), xv.shape)
    _set_shape(_slot_var(op, block, "Out", inputs=False),
               (xv.shape[0], 1))


def _infer_cos_sim_shape(op, block):
    # size-K form (Y holds K stacked vectors of X's width) yields K
    # similarities per row; the plain form yields one
    xv = _slot_var(op, block, "X", need_shape=True)
    yv = _slot_var(op, block, "Y", need_shape=True)
    if not xv.shape or not yv.shape or not xv.shape[-1]:
        raise SkipInferShape
    k = (1 if yv.shape[-1] == xv.shape[-1]
         else yv.shape[-1] // xv.shape[-1])
    _set_shape(_slot_var(op, block, "Out", inputs=False),
               tuple(xv.shape[:-1]) + (k,))
    _set_shape(_slot_var(op, block, "XNorm", inputs=False),
               tuple(xv.shape[:-1]) + (1,))
    _set_shape(_slot_var(op, block, "YNorm", inputs=False),
               tuple(yv.shape[:-1]) + (k if k > 1 else 1,))


def _infer_bilinear_shape(op, block):
    xv = _slot_var(op, block, "X", need_shape=True)
    wv = _slot_var(op, block, "Weight", need_shape=True)
    _set_shape(_slot_var(op, block, "Out", inputs=False),
               (xv.shape[0], wv.shape[0]))


@register_op("squared_l2_norm", inputs=("X",),
             infer_shape=_infer_squared_l2_norm_shape)
def _squared_l2_norm(ctx):
    unary(ctx, lambda x: jnp.sum(jnp.square(x)).reshape(1))


@register_op("squared_l2_distance", inputs=("X", "Y"), outputs=("sub_result", "Out"),
             infer_shape=_infer_squared_l2_distance_shape)
def _squared_l2_distance(ctx):
    x = unwrap(ctx.input("X"))
    y = broadcast_to_x(x, ctx.input("Y"), 0)
    sub = x - y
    ctx.set_output("sub_result", sub)
    ctx.set_output("Out", jnp.sum(jnp.square(sub), axis=tuple(range(1, sub.ndim))).reshape(-1, 1))


@register_op("cos_sim", inputs=("X", "Y"), outputs=("Out", "XNorm", "YNorm"),
             infer_shape=_infer_cos_sim_shape)
def _cos_sim(ctx):
    x = unwrap(ctx.input("X"))
    y = unwrap(ctx.input("Y"))
    if y.shape[-1] != x.shape[-1]:
        # reference CosSimLayer size>1: Y holds K stacked vectors of
        # X's width; output is the K similarities (gserver
        # CosSimLayer.cpp with config size = K)
        k = y.shape[-1] // x.shape[-1]
        y = y.reshape(y.shape[:-1] + (k, x.shape[-1]))
        x = x[..., None, :]
        xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1))
        yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1))
        out = jnp.sum(x * y, axis=-1) / (xn * yn + 1e-12)
        ctx.set_output("Out", out)
        ctx.set_output("XNorm", xn)
        ctx.set_output("YNorm", yn)
        return
    xn = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True))
    out = jnp.sum(x * y, axis=-1, keepdims=True) / (xn * yn + 1e-12)
    ctx.set_output("Out", out)
    ctx.set_output("XNorm", xn)
    ctx.set_output("YNorm", yn)


def _register_compare(name, fn):
    @register_op(name, inputs=("X", "Y"), stop_gradient=True,
                 infer_shape=infer_same_shape)
    def _cmp(ctx, fn=fn):
        x = ctx.input("X")
        y = ctx.input("Y")
        out = fn(unwrap(x), broadcast_to_x(x, y, ctx.attr("axis", -1)))
        ctx.set_output("Out", rewrap(x, out))


for name, fn in [
    ("less_than", jnp.less),
    ("less_equal", jnp.less_equal),
    ("greater_than", jnp.greater),
    ("greater_equal", jnp.greater_equal),
    ("equal", jnp.equal),
    ("not_equal", jnp.not_equal),
    ("logical_and", jnp.logical_and),
    ("logical_or", jnp.logical_or),
    ("logical_xor", jnp.logical_xor),
]:
    _register_compare(name, fn)


@register_op("logical_not", inputs=("X",), stop_gradient=True,
             infer_shape=infer_same_shape)
def _logical_not(ctx):
    unary(ctx, jnp.logical_not)


@register_op("minus", inputs=("X", "Y"), infer_shape=infer_same_shape)
def _minus(ctx):
    x = ctx.input("X")
    ctx.set_output("Out", rewrap(x, unwrap(x) - unwrap(ctx.input("Y"))))


@register_op("bilinear_tensor_product", inputs=("X", "Y", "Weight", "Bias"),
             infer_shape=_infer_bilinear_shape)
def _bilinear_tensor_product(ctx):
    x = unwrap(ctx.input("X"))  # (B, M)
    y = unwrap(ctx.input("Y"))  # (B, N)
    w = unwrap(ctx.input("Weight"))  # (K, M, N)
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    if ctx.has_input("Bias"):
        out = out + unwrap(ctx.input("Bias"))
    ctx.set_output("Out", out)
