"""Plain float32 reference of the MiMo-V2.5 decoder (XiaomiMiMo/
MiMo-V2.5, ``model_type`` mimo_v2: the MiMo-V2-Flash language model), as
one chip of an expert-parallel group holds it, or (``held`` = all) the
whole layer.

Straightforward ``jax.numpy``: no kernel, no cache, no page, no ring, no
bucket and no chunk of a prompt, no batching, no sorting of rows by
expert; every matmul under ``jax.default_matmul_precision("highest")``.
Written from the equations, not from the block under test:

    d 4096, Hq 64, q/k heads of dk 192, v heads of dv 128, r 64 rotated
    channels, window W 128, eps 1e-5, value scale c 0.707
    F(x; Wg, Wu, Wd) = Wd (silu(Wg x) * Wu x);  x_0 = E[token]
    1. h = RMSNorm(x; g1);  q = Wq h -> (Hq, dk), k = Wk h -> (Hkv, dk),
       v = c Wv h -> (Hkv, dv); no bias.  Hkv = 4 on a full layer, 8 on
       a window layer
    2. rotate-half RoPE on channels 0..r-1 of each q and k head at the
       row's absolute position (channel i pairs with i + r/2, i < r/2;
       frequencies theta^(-2i/r)); channels r..dk-1 are not rotated;
       theta 1e7 on a full layer, 1e4 on a window layer
    3. query head i reads K/V head i // (Hq / Hkv); s = q.k dk^-1/2,
       causal; a window layer also hides every key with pos_q - pos_k
       >= W, and its softmax has a sink b_i a query head:
       p_j = exp(s_j - m) / (exp(b_i - m) + sum_k exp(s_k - m)),
       which carries no value; a full layer's softmax has none
    4. x <- x + Wo concat(heads' dv numbers)
    5. m = RMSNorm(x; g2).  Layer 0: x <- x + F(m; dense).  A routed
       layer: s = sigmoid(Wr m) over ALL published experts; the top-k of
       s + b (a tie to the lower index); w_e = scale * s_e / sum over the
       k chosen of s (unbiased s, all k, held or not); x <- x + sum over
       e chosen AND held of w_e F(m; e).  No shared expert
    6. after the last layer RMSNorm(x; gf), logits = H x over the held
       rows of the untied head

Departures from the published description, all named in the
configuration's file: the three MTP layers, the vision tower and the
audio encoder are not instantiated; one chip's share of the experts and
of the vocabulary.  The pre-norm form, which channels rotate, that the
value scale multiplies v, the sink's form and the bias used for choosing
only are from the family's public modelling code, not from
``config.json``: the configuration's file lists them as assumed.

It takes the system's parameter pytree (``paddle_tpu/models/
mimo_v2.py``: ``emb``, ``lm_head``, ``w_f``, ``layers`` of ``w_in w_post
wq wk wv wo [sink]`` and either the dense ``w_gate w_up w_down`` (d, F)
or ``wr b`` and the held experts' stacked ``w_gate w_up`` (C, d, f),
``w_down`` (C, f, d)) in whatever dtype it is served in and widens a
piece at a time to float32.  A sequence runs ``ROW_BLOCK`` rows at a
time (padded on the right to whole blocks; every mixer is causal, so the
padding reaches no real row): a layer's K and V rows for the whole
sequence first, then block by block its queries against them, one K/V
head's group of query heads at a time, the projection and the
feed-forward, so that 32,000 rows fit on a chip beside the pools.

``ablate`` changes one piece: "sink" (dropped), "sink_on_full" (a full
layer's softmax takes the sinks of the window layer after it), "window"
(the full mask on every layer), "partial_rope" (the whole head rotated),
"theta" (the two kinds' thetas swapped), "v_scale" (c = 1), "gqa" (query
head i reads K/V head i % Hkv), "kv_heads_swapped" (the grouping of the
other kind: on a window layer head i reads K/V head i // 16, the first
four of its eight; on a full layer (i // 8) % 4), "sigmoid" (softmax
scores in its place), "no_renorm" (w = scale * s), "bias_in_weights"
(weighs by s + b), "fp8" (every weight rounded to float8_e4m3fn first:
the nearest precision below the bfloat16 the configuration serves in).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_GROUP = 4
DENSE_SLICE = 4096
ROW_BLOCK = 512
WINDOW = "sliding_attention"
ABLATIONS = ("sink", "sink_on_full", "window", "partial_rope", "theta",
             "v_scale", "gqa", "kv_heads_swapped", "sigmoid", "no_renorm",
             "bias_in_weights", "fp8")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def rope(x, pos, theta, rotary):
    """x (T, H, dk) at positions ``pos`` (T,): the first ``rotary``
    channels rotated, channel i pairing with i + rotary / 2."""
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None, None] * inv_freq          # (T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def top_k_mask(p, k):
    """(T, E) bool: the k largest of each row; of equal values the
    lower index ranks first."""
    e = jnp.arange(p.shape[-1])
    ahead = ((p[:, None, :] > p[:, :, None])
             | ((p[:, None, :] == p[:, :, None])
                & (e[None, None, :] < e[None, :, None])))
    return jnp.sum(ahead, axis=-1) < k


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "head_dim", "value_dim", "rotary", "eps", "theta",
    "value_scale"))
def _keys_values(w_in, wk, wv, x, pos, *, kv_heads, head_dim, value_dim,
                 rotary, eps, theta, value_scale):
    """One block of rows' K (rotated) and V (scaled)."""
    with jax.default_matmul_precision("highest"):
        B = x.shape[0]
        h = rms_norm(x, w_in.astype(F32), eps)
        k = (h @ wk.astype(F32)).reshape(B, kv_heads, head_dim)
        v = (h @ wv.astype(F32)).reshape(B, kv_heads, value_dim)
        return rope(k, pos, theta, rotary), v * value_scale


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "head_dim", "rotary", "eps", "theta", "window", "grouping"))
def _attend(w_in, wq, wo, w_post, sink, x, start, k, v, *, num_heads,
            head_dim, rotary, eps, theta, window, grouping):
    """One block of query rows (positions ``start`` ..) against the
    whole sequence's K and V (T, Hkv, ..), one K/V head's query heads at
    a time -> (the rows after the attention's residual, their post-norm).
    ``window`` 0: causal alone.  ``sink`` (Hq,) or None.  ``grouping``:
    "div" (head i reads K/V head i // G), "mod" (i % Hkv), or an int n
    (head i reads K/V head (i // (Hq / n)) % Hkv: the grouping of a
    layer with n K/V heads)."""
    with jax.default_matmul_precision("highest"):
        B, (T, Hkv, _) = x.shape[0], k.shape
        pos = start + jnp.arange(B)
        h = rms_norm(x, w_in.astype(F32), eps)
        q = rope((h @ wq.astype(F32)).reshape(B, num_heads, head_dim), pos,
                 theta, rotary)
        heads = jnp.arange(num_heads)
        if grouping == "div":
            reads = heads // (num_heads // Hkv)
        elif grouping == "mod":
            reads = heads % Hkv
        else:
            reads = (heads // (num_heads // grouping)) % Hkv
        back = pos[:, None] - jnp.arange(T)[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)

        def one_head(i):
            """Query head ``i`` over its K/V head: (B, dv)."""
            s = (q[:, i] @ k[:, reads[i]].T) * head_dim ** -0.5
            s = jnp.where(seen, s, -jnp.inf)
            if sink is None:
                p = jax.nn.softmax(s, axis=-1)
            else:
                b = sink[i].astype(F32)
                m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
                e = jnp.exp(s - m)
                p = e / (jnp.sum(e, axis=-1, keepdims=True)
                         + jnp.exp(b - m))
            return p @ v[:, reads[i]]

        a = jax.lax.map(one_head, heads)                    # (Hq, B, dv)
        a = jnp.moveaxis(a, 0, 1).reshape(B, -1)
        out = x + a @ wo.astype(F32)
        return out, rms_norm(out, w_post.astype(F32), eps)


@jax.jit
def _swiglu(m, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(m @ w_gate.astype(F32))
                * (m @ w_up.astype(F32))) @ w_down.astype(F32)


def dense_ffn(m, w_gate, w_up, w_down):
    """F(m) a slice of the width at a time (it is a sum over the
    width)."""
    y = jnp.zeros_like(m)
    for f0 in range(0, w_gate.shape[1], DENSE_SLICE):
        sl = slice(f0, f0 + DENSE_SLICE)
        y = y + _swiglu(m, w_gate[:, sl], w_up[:, sl], w_down[sl])
    return y


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "ablate"))
def _router(wr, b, m, *, top_k, scale, ablate):
    """-> (weights (T, E): w_e where e is chosen, else 0; the (T, E)
    chosen mask)."""
    with jax.default_matmul_precision("highest"):
        logits = m @ wr.astype(F32)
        s = (jax.nn.softmax(logits, axis=-1) if ablate == "sigmoid"
             else jax.nn.sigmoid(logits))
        biased = s + b.astype(F32)
        mask = top_k_mask(biased, top_k)
        weigh = biased if ablate == "bias_in_weights" else s
        chosen = jnp.where(mask, weigh, 0.0)
        if ablate != "no_renorm":
            chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return chosen * scale, mask


@jax.jit
def _expert_group(m, weight, w_gate, w_up, w_down):
    """sum over the experts of one group of weight * F(m; e): every
    expert of the group on every row."""
    with jax.default_matmul_precision("highest"):
        g = jnp.einsum("td,edf->tef", m, w_gate.astype(F32))
        u = jnp.einsum("td,edf->tef", m, w_up.astype(F32))
        out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                         w_down.astype(F32))
        return jnp.einsum("te,ted->td", weight, out)


def held_experts(m, weight, held, w_gate, w_up, w_down):
    """The held experts' part of the routed sum: ``weight`` (T, E) over
    all published experts, the stacked matrices those of experts
    ``held[0] .. held[0] + held[1] - 1``."""
    first, count = held
    weight = weight[:, first:first + count]
    y = jnp.zeros_like(m)
    for e0 in range(0, count, EXPERT_GROUP):
        sl = slice(e0, e0 + EXPERT_GROUP)
        y = y + _expert_group(m, weight[:, sl], w_gate[sl], w_up[sl],
                              w_down[sl])
    return y


def feed_forward(lp, m, *, top_k, scale, held, ablate):
    """Step 5 after the norm -> (what is added to the residual, the
    (T, E) chosen mask or None for a dense layer)."""
    if "wr" not in lp:
        return dense_ffn(m, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    weight, mask = _router(
        lp["wr"], lp["b"], m, top_k=top_k, scale=scale,
        ablate=ablate if ablate in ("sigmoid", "bias_in_weights",
                                    "no_renorm") else None)
    return held_experts(m, weight, held, lp["w_gate"], lp["w_up"],
                        lp["w_down"]), mask


def layer(lp, blocks, *, kind, sink_after, num_heads, kv_heads,
          window_kv_heads, head_dim, value_dim, rotary, window, top_k, scale,
          held, eps, theta, window_theta, value_scale, ablate):
    """One layer over a sequence's ``blocks`` of ``ROW_BLOCK`` rows ->
    (the blocks after it, each block's chosen mask or None)."""
    sliding = kind == WINDOW
    hkv = window_kv_heads if sliding else kv_heads
    th = window_theta if sliding else theta
    if ablate == "theta":
        th = theta if sliding else window_theta
    rot = head_dim if ablate == "partial_rope" else rotary
    k, v = zip(*(
        _keys_values(lp["w_in"], lp["wk"], lp["wv"], x,
                     i * ROW_BLOCK + jnp.arange(ROW_BLOCK), kv_heads=hkv,
                     head_dim=head_dim, value_dim=value_dim, rotary=rot,
                     eps=eps, theta=th,
                     value_scale=1.0 if ablate == "v_scale" else value_scale)
        for i, x in enumerate(blocks)))
    k, v = jnp.concatenate(k), jnp.concatenate(v)
    sink = lp.get("sink") if ablate != "sink" else None
    if not sliding and ablate == "sink_on_full":
        sink = sink_after
    grouping = "div"
    if ablate == "gqa":
        grouping = "mod"
    elif ablate == "kv_heads_swapped":
        grouping = kv_heads if sliding else window_kv_heads
    out, masks = [], []
    for i, x in enumerate(blocks):
        h, m = _attend(
            lp["w_in"], lp["wq"], lp["wo"], lp["w_post"], sink, x,
            i * ROW_BLOCK, k, v, num_heads=num_heads, head_dim=head_dim,
            rotary=rot, eps=eps, theta=th,
            window=window if sliding and ablate != "window" else 0,
            grouping=grouping)
        y, mask = feed_forward(lp, m, top_k=top_k, scale=scale, held=held,
                               ablate=ablate)
        out.append(h + y)
        masks.append(mask)
    return out, masks


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(w_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w_f.astype(F32), eps) @ lm_head.astype(F32)


def forward(params, tokens, *, layer_types, num_heads, kv_heads,
            window_kv_heads, head_dim, value_dim, rotary, window, top_k,
            scale, held, eps=1e-5, theta=1e7, window_theta=1e4,
            value_scale=0.707, ablate=None, rows=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), masks: a (T, E)
    chosen mask per routed layer, stacked)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    T = tokens.shape[0]
    padded = jnp.pad(tokens, (0, -T % ROW_BLOCK))
    x = round8(params["emb"][padded]).astype(F32)
    blocks = [x[i:i + ROW_BLOCK] for i in range(0, x.shape[0], ROW_BLOCK)]
    layers = params["layers"]
    masks = []
    for li, (kind, lp) in enumerate(zip(layer_types, layers)):
        after = [l["sink"] for l in layers[li + 1:] if "sink" in l]
        blocks, chosen = layer(
            round8(lp), blocks, kind=kind,
            sink_after=after[0] if after else None, num_heads=num_heads,
            kv_heads=kv_heads, window_kv_heads=window_kv_heads,
            head_dim=head_dim, value_dim=value_dim, rotary=rotary,
            window=window, top_k=top_k, scale=scale, held=tuple(held),
            eps=eps, theta=theta, window_theta=window_theta,
            value_scale=value_scale, ablate=ablate)
        if chosen[0] is not None:
            masks.append(jnp.concatenate(chosen)[:T])
    x = jnp.concatenate(blocks)[:T]
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),
            jnp.stack(masks) if masks else None)


def rel_rms(a, b):
    """RMS of ``a - b`` over the RMS of ``b``."""
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)))
                 / jnp.sqrt(jnp.mean(jnp.square(b))))


@jax.jit
def _round_fp8(tree):
    """Every leaf rounded to float8_e4m3fn (kept in its own dtype).
    The barrier keeps the compiler from dropping the round trip as
    excess precision it is allowed to keep."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.optimization_barrier(
            a.astype(jnp.float8_e4m3fn)).astype(a.dtype), tree)
