"""Plain float32 reference of the K-EXAONE decoder (LGAI-EXAONE/
K-EXAONE-236B-A23B, ``model_type`` exaone_moe), as one chip of an
expert-parallel group holds it, or (``held`` = all) the whole layer.

Straightforward ``jax.numpy``: no kernel, no cache, no ring, no
batching, no sorting of rows by expert; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, not from the block under test:

    d 6144, Hq 64, Hkv 8, dh 128, window W 128, eps 1e-5, theta 1e6,
    F(x; Wg, Wu, Wd) = Wd (silu(Wg x) * Wu x);  x_0 = E[token]
    1. h = RMSNorm(x; g1);  q = Wq h -> (Hq, dh), k = Wk h, v = Wv h ->
       (Hkv, dh); no bias
    2. q <- RMSNorm over the dh channels of each head (one scale of dh),
       k likewise
    3. sliding layers only: rotate-half RoPE on q and k at the row's
       absolute position; full layers: no rotation at all
    4. query head i reads K/V head i // (Hq / Hkv); scores q.k/sqrt(dh),
       causal; a sliding layer also hides every key with pos_q - pos_k
       >= W; softmax in f32; x <- x + Wo concat(heads)
    5. m = RMSNorm(x; g2).  A dense layer: x <- x + F(m; dense).  A
       routed layer: s = sigmoid(Wr m) over ALL published experts; the
       top-k of s + b (a tie to the lower index); w_e = scale * s_e /
       sum over the k chosen of s (unbiased s, all k, held or not);
       x <- x + F(m; shared) + sum over e chosen AND held of w_e F(m; e)
    6. after the last layer RMSNorm(x; gf), logits = H x over the held
       rows of the untied head

Pre-norm residuals, the per-head q/k norm, RoPE on sliding layers
only, the bias used for choosing and not for weighing, and rotate-half
pairing are from the family's public modelling code and report, not
from ``config.json``: the configuration's file lists them as assumed.

It takes the system's parameter pytree (``paddle_tpu/models/
exaone_moe.py``: ``emb``, ``lm_head``, ``w_f``, ``layers`` of ``w_in
w_post w_qn w_kn wq wk wv wo`` and either the dense ``w_gate w_up
w_down`` (d, F) or ``wr b ws_gate ws_up ws_down`` and the held experts'
stacked ``w_gate w_up`` (C, d, f), ``w_down`` (C, f, d)) in whatever
dtype it is served in and widens a piece at a time to float32: one
K/V head's group of query heads, a slice of the dense width, a group
of ``EXPERT_GROUP`` experts.

``ablate`` changes one piece: "window" (the full mask on every layer),
"nope" (RoPE on the full layers too), "qk_norm" (dropped), "sigmoid"
(softmax scores in its place), "bias_in_weights" (weighs by s + b),
"no_renorm" (w = scale * s), "no_scale" (scale 1), "shared" (dropped),
"gqa" (query head i reads K/V head i % Hkv), "fp8" (every weight
rounded to float8_e4m3fn first: the nearest precision below the
bfloat16 the configuration serves in).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_GROUP = 4
DENSE_SLICE = 4608
SLIDING = "sliding_attention"
ABLATIONS = ("window", "nope", "qk_norm", "sigmoid", "bias_in_weights",
             "no_renorm", "no_scale", "shared", "gqa", "fp8")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x (T, H, dh) at positions 0..T-1; channel i pairs with i + dh/2."""
    T, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]    # (T, 1, dh)
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def top_k_mask(p, k):
    """(T, E) bool: the k largest of each row; of equal values the
    lower index ranks first."""
    e = jnp.arange(p.shape[-1])
    ahead = ((p[:, None, :] > p[:, :, None])
             | ((p[:, None, :] == p[:, :, None])
                & (e[None, None, :] < e[None, :, None])))
    return jnp.sum(ahead, axis=-1) < k


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "kv_heads", "head_dim", "sliding", "eps", "theta",
    "ablate"))
def _qkv(w, x, *, num_heads, kv_heads, head_dim, sliding, eps, theta,
         ablate):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        T = x.shape[0]
        h = rms_norm(x, w["w_in"], eps)
        q = (h @ w["wq"]).reshape(T, num_heads, head_dim)
        k = (h @ w["wk"]).reshape(T, kv_heads, head_dim)
        v = (h @ w["wv"]).reshape(T, kv_heads, head_dim)
        if ablate != "qk_norm":
            q = rms_norm(q, w["w_qn"], eps)
            k = rms_norm(k, w["w_kn"], eps)
        if sliding or ablate == "nope":
            q, k = rope(q, theta), rope(k, theta)
        return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def _one_kv_head(q, k, v, *, window):
    """The query heads (T, G, dh) that read one K/V head (T, dh):
    causal, and banded when ``window``."""
    with jax.default_matmul_precision("highest"):
        T, _, dh = q.shape
        s = jnp.einsum("tgd,sd->gts", q, k) * dh ** -0.5
        back = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, axis=-1), v)


def attention(q, k, v, window, ablate):
    """q (T, Hq, dh) on k, v (T, Hkv, dh), one K/V head at a time (the
    scores of all heads at once would be Hq T^2 numbers)."""
    T, Hq, dh = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    if ablate == "gqa":        # head i reads K/V head i % Hkv
        groups = [q[:, h::Hkv] for h in range(Hkv)]
    else:                      # head i reads K/V head i // G
        groups = [q[:, h * G:(h + 1) * G] for h in range(Hkv)]
    outs = [_one_kv_head(groups[h], k[:, h], v[:, h], window=window)
            for h in range(Hkv)]
    if ablate == "gqa":
        out = jnp.stack(outs, axis=2).reshape(T, Hq, dh)   # (T, G, Hkv)
    else:
        out = jnp.concatenate(outs, axis=1)
    return out.reshape(T, Hq * dh)


@functools.partial(jax.jit, static_argnames=("eps",))
def _project_and_norm(wo, w_post, x, a, *, eps):
    with jax.default_matmul_precision("highest"):
        h = x + a @ wo.astype(F32)
        return h, rms_norm(h, w_post.astype(F32), eps)


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
        return chosen * (1.0 if ablate == "no_scale" else scale), mask


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
    weight, mask = _router(lp["wr"], lp["b"], m, top_k=top_k, scale=scale,
                           ablate=ablate)
    y = held_experts(m, weight, held, lp["w_gate"], lp["w_up"],
                     lp["w_down"])
    if ablate != "shared":
        y = y + _swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, mask


def layer(lp, x, *, kind, num_heads, kv_heads, head_dim, window, top_k,
          scale, held, eps, theta, ablate):
    sliding = kind == SLIDING
    q, k, v = _qkv({n: lp[n] for n in ("w_in", "wq", "wk", "wv", "w_qn",
                                      "w_kn")},
                   x, num_heads=num_heads, kv_heads=kv_heads,
                   head_dim=head_dim, sliding=sliding, eps=eps, theta=theta,
                   ablate=ablate)
    banded = window if sliding and ablate != "window" else 0
    h, m = _project_and_norm(lp["wo"], lp["w_post"], x,
                             attention(q, k, v, banded, ablate), eps=eps)
    y, mask = feed_forward(lp, m, top_k=top_k, scale=scale, held=held,
                           ablate=ablate)
    return h + y, mask


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(w_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w_f.astype(F32), eps) @ lm_head.astype(F32)


def forward(params, tokens, *, layer_types, num_heads, kv_heads, head_dim,
            window, top_k, scale, held, eps=1e-5, theta=1e6, ablate=None,
            rows=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), masks: a (T, E)
    chosen mask per routed layer, stacked)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    masks = []
    for kind, lp in zip(layer_types, params["layers"]):
        x, mask = layer(round8(lp), x, kind=kind, num_heads=num_heads,
                        kv_heads=kv_heads, head_dim=head_dim, window=window,
                        top_k=top_k, scale=scale, held=tuple(held), eps=eps,
                        theta=theta, ablate=ablate)
        if mask is not None:
            masks.append(mask)
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
