"""NN layer ops: conv, pool, norm, dropout, softmax.

Reference: paddle/operators/{conv,pool,batch_norm,dropout,softmax,lrn,
conv_transpose,maxout}_op.cc.  All NCHW (the reference layout); XLA's
layout assignment maps them onto the MXU/VPU natively, so no cudnn-style
per-op algorithm choice exists here — the whole block fuses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.lod import rewrap, unwrap
from paddle_tpu.registry import (SkipInferShape, infer_same_shape,
                                 register_op)


def _pref():
    from paddle_tpu import amp

    return amp.preferred_acc()


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v, v)


# ---------------------------------------------------------------------------
# infer_shape rules (registry-audit ratchet: conv/pool family).  Same
# contract as the elementwise/matmul rules in math_ops.py: backfill
# missing output metadata, SkipInferShape when statically unknowable,
# ValueError only for shapes the lowering would also reject.
# ---------------------------------------------------------------------------


def _io_vars(op, block, in_slot, out_slot):
    ins = op.inputs.get(in_slot, [])
    outs = op.outputs.get(out_slot, [])
    if len(ins) != 1 or len(outs) != 1 or not ins[0] or not outs[0]:
        raise SkipInferShape
    xv = block.find_var(ins[0])
    ov = block.find_var(outs[0])
    if xv is None or ov is None or xv.shape is None:
        raise SkipInferShape
    return xv, ov


def _conv_extent(size, k, p, s, d=1):
    if size < 0:
        return -1
    out = (size + 2 * p - ((k - 1) * d + 1)) // s + 1
    if out < 1:
        raise ValueError(
            f"conv/pool output extent {out} < 1 (input {size}, kernel {k}, "
            f"pad {p}, stride {s}, dilation {d})")
    return out


def _nd(op, name, default, n):
    v = op.attr(name, default)
    v = tuple(v) if isinstance(v, (list, tuple)) else (v,) * n
    if len(v) != n:
        raise SkipInferShape
    return v


def _make_conv_infer(spatial: int, transpose: bool = False):
    def infer(op, block):
        xv, ov = _io_vars(op, block, "Input", "Output")
        fs = op.inputs.get("Filter", [])
        wv = block.find_var(fs[0]) if len(fs) == 1 and fs[0] else None
        if (wv is None or wv.shape is None or ov.shape is not None
                or len(xv.shape) != 2 + spatial
                or len(wv.shape) != 2 + spatial):
            if ov.shape is not None:
                return
            raise SkipInferShape
        ones = (1,) * spatial
        zeros = (0,) * spatial
        strides = _nd(op, "strides", ones, spatial)
        pads = _nd(op, "paddings", zeros, spatial)
        dils = _nd(op, "dilations", ones, spatial)
        if transpose:
            # filter (Cin, Cout, *k).  Match what lax.conv_transpose
            # with transpose_kernel=True actually emits:
            # (in-1)*s + 2p - (k-1)*d + 1 (verified empirically across
            # stride/pad/dilation combos).  NB the layer builder stamps
            # the Paddle-paper convention ((in-1)*s - 2p + (k-1)*d + 1)
            # at build time — the two agree exactly when
            # p == (k-1)*d/2 (every shipped config); this rule only
            # backfills missing metadata, so built programs keep the
            # builder's value.
            out_c = wv.shape[1]

            def _t_extent(i):
                size = xv.shape[2 + i]
                if size < 0:
                    return -1
                out = (size - 1) * strides[i] + 2 * pads[i] \
                    - (wv.shape[2 + i] - 1) * dils[i] + 1
                if out < 1:
                    raise ValueError(
                        f"conv_transpose output extent {out} < 1 "
                        f"(input {size}, kernel {wv.shape[2 + i]}, "
                        f"pad {pads[i]}, stride {strides[i]}, "
                        f"dilation {dils[i]})")
                return out

            sp = tuple(_t_extent(i) for i in range(spatial))
        else:
            out_c = wv.shape[0]
            sp = tuple(_conv_extent(xv.shape[2 + i], wv.shape[2 + i],
                                    pads[i], strides[i], dils[i])
                       for i in range(spatial))
        ov.shape = (xv.shape[0], out_c) + sp

    return infer


def _make_pool_infer(spatial: int, out_slot: str = "Out",
                     default_strides=None, also: tuple = ()):
    def infer(op, block):
        xv, ov = _io_vars(op, block, "X", out_slot)
        if len(xv.shape) != 2 + spatial:
            raise SkipInferShape
        if ov.shape is None:
            if op.attr("global_pooling", False):
                sp = (1,) * spatial
            else:
                ks = _nd(op, "ksize", (2,) * spatial, spatial)
                st_default = (ks if default_strides == "ksize"
                              else default_strides or (1,) * spatial)
                st = _nd(op, "strides", st_default, spatial)
                pd = _nd(op, "paddings", (0,) * spatial, spatial)
                ceil = op.attr("ceil_mode", False)
                sp = []
                for i in range(spatial):
                    size = xv.shape[2 + i]
                    if size < 0:
                        sp.append(-1)
                        continue
                    from paddle_tpu.layers.nn import pool_out_extent

                    sp.append(pool_out_extent(size, ks[i], pd[i], st[i],
                                              ceil_mode=ceil))
                sp = tuple(sp)
            ov.shape = tuple(xv.shape[:2]) + sp
        for slot in also:   # e.g. the with_index Mask mirrors Out
            extra = op.outputs.get(slot, [])
            if len(extra) == 1 and extra[0]:
                ev = block.find_var(extra[0])
                if ev is not None and ev.shape is None:
                    ev.shape = tuple(ov.shape)

    return infer


def _infer_mirror_x(*out_slots, in_slot="X"):
    """Every named output mirrors the (single) ``in_slot`` input."""

    def infer(op, block):
        ins = op.inputs.get(in_slot, [])
        if len(ins) != 1 or not ins[0]:
            raise SkipInferShape
        xv = block.find_var(ins[0])
        if xv is None or xv.shape is None:
            raise SkipInferShape
        hit = False
        for slot in out_slots:
            outs = op.outputs.get(slot, [])
            if len(outs) != 1 or not outs[0]:
                continue
            ov = block.find_var(outs[0])
            if ov is None:
                continue
            hit = True
            if ov.shape is None:
                ov.shape = tuple(xv.shape)
            if ov.lod_level == 0 and xv.lod_level:
                ov.lod_level = xv.lod_level
        if not hit:
            raise SkipInferShape

    return infer


def _infer_batch_norm_shape(op, block):
    xv, ov = _io_vars(op, block, "X", "Y")
    if ov.shape is None:
        ov.shape = tuple(xv.shape)
    if len(xv.shape) < 2:
        return
    c = xv.shape[1]
    if c < 0:
        return
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        outs = op.outputs.get(slot, [])
        if len(outs) == 1 and outs[0]:
            sv = block.find_var(outs[0])
            if sv is not None and sv.shape is None:
                sv.shape = (c,)


def _infer_maxout_shape(op, block):
    xv, ov = _io_vars(op, block, "X", "Out")
    if ov.shape is not None or len(xv.shape) != 4:
        raise SkipInferShape
    groups = op.attr("groups", None)
    if not groups:
        raise SkipInferShape
    n, c, h, w = xv.shape
    if c >= 0 and c % groups != 0:
        raise ValueError(f"maxout: channels {c} not divisible by "
                         f"groups {groups}")
    ov.shape = (n, c // groups if c >= 0 else -1, h, w)


def _infer_pad_shape(op, block):
    xv, ov = _io_vars(op, block, "X", "Out")
    if ov.shape is not None:
        return
    paddings = op.attr("paddings", None)
    if not paddings or len(paddings) != 2 * len(xv.shape):
        raise SkipInferShape
    ov.shape = tuple(
        -1 if d < 0 else d + paddings[2 * i] + paddings[2 * i + 1]
        for i, d in enumerate(xv.shape))


def _infer_bilinear_shape(op, block):
    xv, ov = _io_vars(op, block, "X", "Out")
    if ov.shape is not None or len(xv.shape) != 4:
        raise SkipInferShape
    oh, ow = op.attr("out_h", None), op.attr("out_w", None)
    if not oh or not ow:
        raise SkipInferShape
    ov.shape = (xv.shape[0], xv.shape[1], int(oh), int(ow))


@register_op("conv2d", inputs=("Input", "Filter"), outputs=("Output",),
             infer_shape=_make_conv_infer(2))
def _conv2d(ctx):
    """NCHW conv, filter (O, I/groups, H, W), groups supported
    (reference: operators/conv_op.cc)."""
    from paddle_tpu import amp

    x = unwrap(ctx.input("Input"))
    w = unwrap(ctx.input("Filter"))
    strides = _pair(ctx.attr("strides", (1, 1)))
    pads = _pair(ctx.attr("paddings", (0, 0)))
    dilations = _pair(ctx.attr("dilations", (1, 1)))
    groups = ctx.attr("groups", 1)
    out_dt = amp.out_dtype(x)
    x, w = amp.cast_operands(x, w)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=_pref(),
    ).astype(out_dt)
    ctx.set_output("Output", out)


@register_op("conv3d", inputs=("Input", "Filter"), outputs=("Output",),
             infer_shape=_make_conv_infer(3))
def _conv3d(ctx):
    x = unwrap(ctx.input("Input"))
    w = unwrap(ctx.input("Filter"))
    strides = tuple(ctx.attr("strides", (1, 1, 1)))
    pads = tuple(ctx.attr("paddings", (0, 0, 0)))
    dilations = tuple(ctx.attr("dilations", (1, 1, 1)))
    groups = ctx.attr("groups", 1)
    out = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        preferred_element_type=_pref(),
    ).astype(x.dtype)
    ctx.set_output("Output", out)


@register_op("conv2d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",),
             infer_shape=_make_conv_infer(2, transpose=True))
def _conv2d_transpose(ctx):
    """Gradient-of-conv as a forward op (reference:
    operators/conv_transpose_op.cc).  Filter layout (I, O, H, W)."""
    x = unwrap(ctx.input("Input"))
    w = unwrap(ctx.input("Filter"))
    strides = _pair(ctx.attr("strides", (1, 1)))
    pads = _pair(ctx.attr("paddings", (0, 0)))
    dilations = _pair(ctx.attr("dilations", (1, 1)))
    # paddle filter layout (Cin, Cout, H, W) is the OIHW layout of the
    # forward conv being transposed, which is exactly what
    # transpose_kernel=True expects (it swaps I/O and flips spatials);
    # declaring it IOHW only type-checked when Cin == Cout
    out = lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    ).astype(x.dtype)
    ctx.set_output("Output", out)


@register_op("pool2d", inputs=("X",), infer_shape=_make_pool_infer(2))
def _pool2d(ctx):
    x = unwrap(ctx.input("X"))
    ptype = ctx.attr("pooling_type", "max")
    ksize = _pair(ctx.attr("ksize", (2, 2)))
    strides = _pair(ctx.attr("strides", (1, 1)))
    pads = _pair(ctx.attr("paddings", (0, 0)))
    if ctx.attr("global_pooling", False):
        ksize = x.shape[2:4]
        strides = (1, 1)
        pads = (0, 0)
    # ceil_mode (reference: config_parser cnn_output_size with
    # caffe_mode=False, the v1 img_pool default): output extent uses
    # ceil, implemented as extra high-side padding; windows there are
    # clipped to the real image exactly like the reference loop bounds
    # (Matrix.cpp avgPoolForward hend=min(.., imgSize)), because the
    # extra cells are -inf for max and excluded from avg counts below
    extra = (0, 0)
    if ctx.attr("ceil_mode", False):
        from paddle_tpu.layers.nn import pool_extra_padding

        extra = (pool_extra_padding(x.shape[2], ksize[0], pads[0], strides[0]),
                 pool_extra_padding(x.shape[3], ksize[1], pads[1], strides[1]))
    window = (1, 1) + ksize
    strides4 = (1, 1) + strides
    padding = ((0, 0), (0, 0), (pads[0], pads[0] + extra[0]),
               (pads[1], pads[1] + extra[1]))
    # max/sum windows are separable: two 1-D passes do kh+kw work per
    # output instead of kh*kw (a 32x32 stride-1 pool drops from 1024 to
    # 64 ops/element — the XLA CPU backend at low opt levels does not
    # perform this rewrite itself).  Only worth it for LARGE windows:
    # for the common 2x2/3x3 pools the split doubles the backward's
    # select-and-scatter passes (measured +8% on the GoogLeNet step)
    # while saving almost nothing forward.
    separable = ksize[0] > 1 and ksize[1] > 1 and ksize[0] * ksize[1] >= 32

    def _sep(v, init, op):
        h = lax.reduce_window(v, init, op, (1, 1, ksize[0], 1),
                              (1, 1, strides[0], 1),
                              ((0, 0), (0, 0), padding[2], (0, 0)))
        return lax.reduce_window(h, init, op, (1, 1, 1, ksize[1]),
                                 (1, 1, 1, strides[1]),
                                 ((0, 0), (0, 0), (0, 0), padding[3]))

    if ptype == "max":
        init = -jnp.inf
        if separable:
            out = _sep(x, init, lax.max)
        else:
            out = lax.reduce_window(x, init, lax.max, window, strides4,
                                    padding)
    else:
        xf = x.astype(jnp.float32)
        summed = (_sep(xf, 0.0, lax.add) if separable else
                  lax.reduce_window(xf, 0.0, lax.add, window, strides4,
                                    padding))
        if ctx.attr("exclusive", False):
            ones = jnp.ones_like(x, dtype=jnp.float32)
            counts = (_sep(ones, 0.0, lax.add) if separable else
                      lax.reduce_window(ones, 0.0, lax.add, window,
                                        strides4, padding))
            out = (summed / counts).astype(x.dtype)
        else:
            out = (summed / (ksize[0] * ksize[1])).astype(x.dtype)
    ctx.set_output("Out", out)


@register_op("batch_norm",
             inputs=("X", "Scale", "Bias", "Mean", "Variance", "Length"),
             outputs=("Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"),
             diff_inputs=("X", "Scale", "Bias"),
             infer_shape=_infer_batch_norm_shape)
def _batch_norm(ctx):
    """Training/inference BN over NCHW channel axis 1 (reference:
    operators/batch_norm_op.cc).  MeanOut/VarianceOut are the running
    statistics (written back to the same persistable vars, functionally)."""
    x = unwrap(ctx.input("X"))
    scale = unwrap(ctx.input("Scale"))
    bias = unwrap(ctx.input("Bias"))
    mean = unwrap(ctx.input("Mean"))
    var = unwrap(ctx.input("Variance"))
    eps = ctx.attr("epsilon", 1e-5)
    momentum = ctx.attr("momentum", 0.9)
    is_test = ctx.attr("is_test", False)
    layout = ctx.attr("data_layout", "NCHW")
    seq_mode = ctx.has_input("Length") and x.ndim == 3
    # padded sequence frames (B, T, C): channel is the LAST axis
    c_axis = (x.ndim - 1 if (seq_mode or layout != "NCHW") else 1)
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        new_mean, new_var = mean, var
    elif seq_mode:
        # statistics over the REAL frames only (the reference's LoD
        # rows carry no padding — gserver BatchNormBaseLayer sees
        # packed frames)
        _lens = unwrap(ctx.input("Length")).reshape(-1).astype(jnp.int32)
        _valid = (jnp.arange(x.shape[1])[None, :] < _lens[:, None]
                  ).astype(jnp.float32)[:, :, None]           # (B, T, 1)
        n = jnp.maximum(jnp.sum(_valid), 1.0)
        xf = x.astype(jnp.float32) * _valid
        use_mean = jnp.sum(xf, axis=(0, 1)) / n
        use_var = (jnp.sum(jnp.square(xf), axis=(0, 1)) / n
                   - jnp.square(use_mean))
        saved_mean, saved_var = use_mean, use_var
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * var + (1 - momentum) * use_var
    else:
        # f32-accumulated statistics regardless of activation dtype (the
        # convert fuses into the reduction, so bf16 activations are read
        # once, not materialized in f32)
        use_mean = jnp.mean(x, axis=red_axes, dtype=jnp.float32)
        use_var = (jnp.mean(jnp.square(x.astype(jnp.float32)), axis=red_axes)
                   - jnp.square(use_mean))
        saved_mean, saved_var = use_mean, use_var
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * var + (1 - momentum) * use_var

    inv = lax.rsqrt(use_var + eps)
    _seq_valid = None
    if seq_mode:
        # preserve the zero-padding invariant downstream ops rely on
        _lens = unwrap(ctx.input("Length")).reshape(-1).astype(jnp.int32)
        _seq_valid = (jnp.arange(x.shape[1])[None, :] < _lens[:, None]
                      )[:, :, None]
    if x.dtype == jnp.bfloat16:
        # normalize in bf16 (stats stay f32): halves the HBM traffic of
        # the normalize pass, measured +6% on the ResNet-50 train step.
        # Fold the per-channel affine in f32 first so the bf16 rounding
        # happens once, and the per-element work is one mul + one add.
        a = (scale.astype(jnp.float32) * inv)
        b = bias.astype(jnp.float32) - use_mean * a
        y = x * a.astype(x.dtype).reshape(bshape) \
            + b.astype(x.dtype).reshape(bshape)
        if _seq_valid is not None:
            y = y * _seq_valid.astype(y.dtype)
        ctx.set_output("Y", y)
    else:
        xf = x.astype(jnp.float32)
        y = (xf - use_mean.reshape(bshape)) * inv.reshape(bshape)
        y = y * scale.reshape(bshape) + bias.reshape(bshape)
        if _seq_valid is not None:
            y = y * _seq_valid
        ctx.set_output("Y", y.astype(x.dtype))
    ctx.set_output("MeanOut", new_mean)
    ctx.set_output("VarianceOut", new_var)
    ctx.set_output("SavedMean", saved_mean)
    ctx.set_output("SavedVariance", saved_var)


def _dropout_grad_lower(ctx):
    """d(out)/d(x) = mask (already scaled)."""
    gout = ctx.input("Out@GRAD")
    mask = ctx.values[ctx.op.attr("__fwd_outputs__")["Mask"][0]]
    gname = ctx.op.outputs["X@GRAD"][0]
    from paddle_tpu.lod import LoDArray

    g = unwrap(gout) * mask
    ctx.values[gname] = rewrap(gout, g)


@register_op("dropout", inputs=("X",), outputs=("Out", "Mask"),
             infer_shape=_infer_mirror_x("Out", "Mask"),
             grad_lower=_dropout_grad_lower)
def _dropout(ctx):
    x = ctx.input("X")
    xd = unwrap(x)
    p = ctx.attr("dropout_prob", 0.5)
    if ctx.attr("is_test", False):
        ctx.set_output("Out", x)
        ctx.set_output("Mask", jnp.ones_like(xd))
        return
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, xd.shape)
    # inverted dropout: scale at train time
    mask = keep.astype(xd.dtype) / jnp.asarray(1.0 - p, xd.dtype)
    ctx.set_output("Out", rewrap(x, xd * mask))
    ctx.set_output("Mask", mask)


@register_op("softmax", inputs=("X",), infer_shape=infer_same_shape)
def _softmax(ctx):
    unary_in = ctx.input("X")
    x = unwrap(unary_in)
    from paddle_tpu import pallas as pk

    if x.ndim == 2 and pk.use_softmax(x.shape[0], x.shape[1]):
        ctx.set_output("Out", rewrap(
            unary_in, pk.pallas_softmax(x, interpret=pk.interpret_mode())))
        return
    ctx.set_output("Out", rewrap(unary_in, jax.nn.softmax(x, axis=-1)))


@register_op("lrn", inputs=("X",), outputs=("Out", "MidOut"),
             infer_shape=_infer_mirror_x("Out", "MidOut"))
def _lrn(ctx):
    """Local response norm across channels (reference: operators/lrn_op.cc)."""
    x = unwrap(ctx.input("X"))
    n = ctx.attr("n", 5)
    k = ctx.attr("k", 2.0)
    alpha = ctx.attr("alpha", 1e-4)
    beta = ctx.attr("beta", 0.75)
    sq = jnp.square(x.astype(jnp.float32))
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i : i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    ctx.set_output("MidOut", mid)
    ctx.set_output("Out", (x / jnp.power(mid, beta)).astype(x.dtype))


@register_op("maxout", inputs=("X",), infer_shape=_infer_maxout_shape)
def _maxout(ctx):
    x = unwrap(ctx.input("X"))
    groups = ctx.attr("groups")
    n, c, h, w = x.shape
    ctx.set_output("Out", jnp.max(x.reshape(n, c // groups, groups, h, w), axis=2))


@register_op("pad", inputs=("X",), infer_shape=_infer_pad_shape)
def _pad(ctx):
    x = unwrap(ctx.input("X"))
    paddings = ctx.attr("paddings")
    val = ctx.attr("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    ctx.set_output("Out", jnp.pad(x, cfg, constant_values=val))


@register_op("crop", inputs=("X", "Y"))
def _crop(ctx):
    """Crop X to a target shape from ``axis`` onward (reference:
    operators/crop_op.cc + CropLayer axis semantics: dims before
    ``axis`` are kept whole; offsets default to 0)."""
    x = unwrap(ctx.input("X"))
    axis = ctx.attr("axis", 0)
    offsets = list(ctx.attr("offsets") or [])
    if ctx.has_input("Y"):
        tgt = list(unwrap(ctx.input("Y")).shape)
        if len(tgt) == x.ndim:
            shape = tgt[axis:]
        else:
            shape = tgt
    else:
        shape = list(ctx.attr("shape"))
        if len(shape) == x.ndim:
            axis, shape = 0, shape
    if len(offsets) == x.ndim:
        axis = 0
    if not offsets:
        offsets = [0] * len(shape)
    if len(offsets) != len(shape):
        raise ValueError(
            f"crop: offsets rank {len(offsets)} != target rank "
            f"{len(shape)} (axis={axis}); silent truncation would crop "
            "the wrong dimensions")
    sl = [slice(None)] * axis + [
        slice(o, o + s) for o, s in zip(offsets, shape)]
    ctx.set_output("Out", x[tuple(sl)])


@register_op("conv3d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",),
             infer_shape=_make_conv_infer(3, transpose=True))
def _conv3d_transpose(ctx):
    """3-D transposed conv (reference: operators/conv_transpose_op.cc
    3-D registration).  Filter layout (I, O, D, H, W)."""
    x = unwrap(ctx.input("Input"))
    w = unwrap(ctx.input("Filter"))
    strides = tuple(ctx.attr("strides", (1, 1, 1)))
    pads = tuple(ctx.attr("paddings", (0, 0, 0)))
    dilations = tuple(ctx.attr("dilations", (1, 1, 1)))
    # (Cin, Cout, D, H, W) = the forward conv's OIDHW; see the 2-D twin
    out = lax.conv_transpose(
        x, w, strides=strides,
        padding=[(p, p) for p in pads],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        transpose_kernel=True,
    ).astype(x.dtype)
    ctx.set_output("Output", out)


@register_op("bilinear_interp", inputs=("X",),
             infer_shape=_infer_bilinear_shape)
def _bilinear_interp(ctx):
    """Bilinear resize over NCHW spatial dims (reference:
    operators/bilinear_interp_op.cc / BilinearInterpLayer)."""
    x = unwrap(ctx.input("X"))
    oh = ctx.attr("out_h")
    ow = ctx.attr("out_w")
    n, c = x.shape[0], x.shape[1]
    out = jax.image.resize(x.astype(jnp.float32), (n, c, oh, ow),
                           method="bilinear").astype(x.dtype)
    ctx.set_output("Out", out)
