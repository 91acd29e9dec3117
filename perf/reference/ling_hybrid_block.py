"""Plain float32 reference of the Ling-3.0-flash decoder (inclusionAI/
Ling-3.0-flash, ``model_type`` bailing_hybrid), as one chip of an
expert-parallel group holds the layers of its pipeline stage, or
(``held`` = all) the whole layers.

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no state
pool, no chunking of the recurrence (a ``lax.scan`` over the rows, one
row a step, as the equations are written), latent attention UN-absorbed
as published, the router's group step by full sorts, no sorting of rows
by expert; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, not from the block under test:

    d 2560, 32 heads, eps 1e-6;  x_0 = E[token];  pre-norm residuals
    F(x; Wg, Wu, Wd) = Wd (silu(Wg x) * Wu x)
    layer i is latent attention where (i + 1) % 6 == 0, KDA otherwise
    KDA layer (u = RMSNorm(x; g1)), heads of d_k = d_v = 128:
    1. z = [W_q u; W_k u; W_v u] (3 x 4096); causal depthwise conv of
       width 4 over each channel (taps on rows t-3..t, zeros before row
       0, no bias); SiLU; split to q_t, k_t, v_t (32 x 128 each)
    2. q_t <- q_t / |q_t| * d_k^-1/2, k_t <- k_t / |k_t| (the norms
       with 1e-6 under the root)
    3. beta_t = sigmoid(W_b u) (a head); the log-decay a head A KEY
       CHANNEL: g_t = lower_bound * sigmoid(exp(A_log) * (W_f u +
       dt_bias)), lower_bound -5;  alpha_t = exp(g_t)
    4. S'_t = S_{t-1} Diag(alpha_t);  S_t = S'_t + beta_t (v_t - S'_t
       k_t) k_t^T, S_{-1} = 0 (d_v x d_k);  o_t = S_t q_t
    5. x <- x + W_o [RMSNorm_{128}(o_t; w_on) * sigmoid(w_g . u)], the
       gate ONE number a head
    latent layer (h = RMSNorm(x; g1)): nope 128, rope 64, v 128, rank
       512, theta 6e6
    6. q = Wq h -> H x [q^n ; q^r];  [c ; k^r] = Wkva h;  c <-
       RMSNorm(c; gc);  q^r, k^r rotated at the row's position, channel
       2i paired with 2i + 1;  k^r ONE row shared by all heads
    7. [k^n_h ; v_h] = Wkvb c;  scores (q^n.k^n + q^r.k^r) / sqrt(192),
       causal, softmax in f32;  o_h = sum p v_h
    8. x <- x + Wo concat_h(o_h * sigmoid(w_g . h)_h)   (head-wise gate)
    feed-forward (m = RMSNorm(x; g2)):
    9. layers 0, 1: x <- x + F(m; dense).  Later layers: s =
       sigmoid(Wr m) over ALL published experts, ranked by s + b; the
       experts lie in n_group groups side by side; a group's score is
       the sum of its two largest s + b; the topk_group best groups
       stay (a tie to the lower group); the top-k of s + b among their
       experts (a tie to the lower index); w_e = scale * s_e / sum over
       the k chosen of s;  x <- x + F(m; shared) + sum over e chosen
       AND held of w_e F(m; e)
    10. after the last layer RMSNorm(x; gf), logits = H x over the held
       rows of the untied head

It takes the system's parameter pytree (``emb lm_head w_f``, ``layers``
of ``w_in w_post`` and either ``w_qkv w_conv w_f dt_bias A_log w_b w_g
w_on w_o`` or ``w_cn wq w_kva w_uk w_uv w_g wo`` (``Wkvb`` as its two
per-head halves, which step 7 puts back together), and either the dense
``w_gate w_up w_down`` (d, F) or ``wr b ws_gate ws_up ws_down`` and the
held experts' stacked ``w_gate w_up`` (C, d, f), ``w_down`` (C, f, d))
in whatever dtype it is served in and widens a piece at a time to
float32: one head's scores, a few held experts at a time.  The dense
and the held experts' SwiGLUs, the head and the float8 rounding are the
sibling reference's (``exaone_moe_block.py``, no part of the program),
as Kanana's reference takes them; the router, with its group step, is
this file's.

``ablate`` changes one piece: "decay_head_mean" (the log-decay averaged
over a head's key channels: one number a head, the scalar rule),
"unbounded_gate" (g = -exp(A_log) softplus(W_f u + dt_bias), no lower
bound), "no_conv" (the conv replaced by the identity; the SiLU stays),
"no_l2norm", "beta_1", "kda_gate_off" and "latent_gate_off" (the
head-wise output gate dropped), "no_group" (the top-k of all experts),
"topk_group3" (one group fewer kept), "shared_off", "dense_layer0_off"
(layer 0's feed-forward dropped), "latent_scale_rsqrt128" (nope^-1/2
for (nope + rope)^-1/2); "fp8" (every weight rounded to float8_e4m3fn
first), "latent_fp8" (the rows a page would hold, ``[c ; k^r]`` after
the norm and the rotation, rounded to float8_e4m3fn), "state_bf16"
(every KDA state rounded to bfloat16 after every row): the nearest
precision below what the configuration states for its weights, its
latent rows and its states.
"""

import functools

import jax
import jax.numpy as jnp

from perf.reference import exaone_moe_block as moe_ref
from perf.reference.exaone_moe_block import (F32, _head, _round_fp8,  # noqa: F401
                                             rel_rms, rms_norm)

LINEAR = "linear_attention"
ABLATIONS = ("decay_head_mean", "unbounded_gate", "no_conv", "no_l2norm",
             "beta_1", "kda_gate_off", "latent_gate_off", "no_group",
             "topk_group3", "shared_off", "dense_layer0_off",
             "latent_scale_rsqrt128")
PRECISIONS = ("fp8", "latent_fp8", "state_bf16")


def _round(a, dtype):
    """``a`` rounded to ``dtype`` and widened again.  The barrier keeps
    the compiler from dropping the round trip as excess precision it is
    allowed to keep."""
    return jax.lax.optimization_barrier(a.astype(dtype)).astype(a.dtype)


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


# -- the KDA layer -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("state_bf16",))
def _recurrence(q, k, v, alpha, beta, *, state_bf16):
    """Step 4, row by row: q, k, alpha (T, H, d_k), v (T, H, d_v), beta
    (T, H) -> (o (T, H, d_v), the state after the last row (H, d_v,
    d_k))."""
    with jax.default_matmul_precision("highest"):
        def row(S, r):
            q_t, k_t, v_t, a_t, b_t = r
            decayed = S * a_t[:, None, :]
            read = jnp.einsum("hvk,hk->hv", decayed, k_t)
            S = decayed + (b_t[:, None] * (v_t - read))[:, :, None] \
                * k_t[:, None, :]
            if state_bf16:
                S = _round(S, jnp.bfloat16)
            return S, jnp.einsum("hvk,hk->hv", S, q_t)

        H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
        last, o = jax.lax.scan(row, jnp.zeros((H, dv, dk), F32),
                               (q, k, v, alpha, beta))
        return o, last


def kda_mixer(lp, x, *, heads, d_k, d_v, lower_bound, eps, ablate):
    T = x.shape[0]
    u = rms_norm(x, lp["w_in"].astype(F32), eps)
    z = _matmul(u, lp["w_qkv"])
    if ablate != "no_conv":
        w = lp["w_conv"].astype(F32)                       # (taps, C)
        taps = w.shape[0]
        zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1]), F32), z])
        z = sum(w[j] * zp[j:j + T] for j in range(taps))
    z = jax.nn.silu(z)
    q = z[:, :heads * d_k].reshape(T, heads, d_k)
    k = z[:, heads * d_k:2 * heads * d_k].reshape(T, heads, d_k)
    v = z[:, 2 * heads * d_k:].reshape(T, heads, d_v)
    if ablate != "no_l2norm":
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * d_k ** -0.5
    beta = (jnp.ones((T, heads), F32) if ablate == "beta_1"
            else jax.nn.sigmoid(_matmul(u, lp["w_b"])))
    f = (_matmul(u, lp["w_f"]) + lp["dt_bias"].astype(F32)).reshape(
        T, heads, d_k)
    rate = jnp.exp(lp["A_log"].astype(F32))[None, :, None]
    if ablate == "unbounded_gate":
        g = -rate * jax.nn.softplus(f)
    else:
        g = lower_bound * jax.nn.sigmoid(rate * f)
    if ablate == "decay_head_mean":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    o, last = _recurrence(q, k, v, jnp.exp(g), beta,
                          state_bf16=ablate == "state_bf16")
    o = rms_norm(o, lp["w_on"].astype(F32), eps)
    if ablate != "kda_gate_off":
        o = o * jax.nn.sigmoid(_matmul(u, lp["w_g"]))[:, :, None]
    return _matmul(o.reshape(T, heads * d_v), lp["w_o"]), last


# -- the latent layer --------------------------------------------------------


def rope(x, theta):
    """x (T, n, dr) at positions 0..T-1: channel 2i pairs with 2i + 1."""
    T, _, dr = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "nope", "rope_dim", "eps", "theta", "latent_fp8"))
def _latent_qkv(w, x, *, num_heads, nope, rope_dim, eps, theta, latent_fp8):
    """Steps 6 and the first half of 7 -> (h, q (T, H, nope + rope), k
    (T, H, nope + rope), v (T, H, v), the rows a page would hold ``[c ;
    k^r]`` (T, rank + rope))."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        T, H = x.shape[0], num_heads
        rank = w["w_cn"].shape[0]
        h = rms_norm(x, w["w_in"], eps)
        q = (h @ w["wq"]).reshape(T, H, nope + rope_dim)
        qn, qr = q[..., :nope], rope(q[..., nope:], theta)
        kva = h @ w["w_kva"]
        c = rms_norm(kva[:, :rank], w["w_cn"], eps)
        kr = rope(kva[:, None, rank:], theta)                  # (T, 1, r)
        if latent_fp8:
            c, kr = _round(c, jnp.float8_e4m3fn), _round(
                kr, jnp.float8_e4m3fn)
        # W_kvb as published: (rank, H x (nope + v)), head h's columns
        # [k^n ; v]
        w_kvb = jnp.concatenate(
            [jnp.swapaxes(w["w_uk"], 1, 2), w["w_uv"]], axis=-1)  # (H, c, .)
        kv = jnp.einsum("tc,hcn->thn", c, w_kvb)
        rows = jnp.concatenate([c, kr[:, 0]], axis=-1)
        kr = jnp.broadcast_to(kr, (T, H, rope_dim))
        return (h, jnp.concatenate([qn, qr], -1),
                jnp.concatenate([kv[..., :nope], kr], -1), kv[..., nope:],
                rows)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attention(q, k, v, *, scale):
    """Step 7's heads, one at a time: (T, H, .) -> (T, H, v)."""
    def one_head(qkv):
        qh, kh, vh = qkv
        with jax.default_matmul_precision("highest"):
            T = qh.shape[0]
            s = (qh @ kh.T) * scale
            seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            s = jnp.where(seen, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 1, 0)
                                      for a in (q, k, v)))
    return jnp.moveaxis(out, 0, 1)


def latent_mixer(lp, x, *, heads, nope, rope_dim, eps, theta, ablate):
    names = ("w_in", "w_cn", "wq", "w_kva", "w_uk", "w_uv")
    h, q, k, v, rows = _latent_qkv(
        {n: lp[n] for n in names}, x, num_heads=heads, nope=nope,
        rope_dim=rope_dim, eps=eps, theta=theta,
        latent_fp8=ablate == "latent_fp8")
    width = nope if ablate == "latent_scale_rsqrt128" else nope + rope_dim
    o = _attention(q, k, v, scale=float(width) ** -0.5)
    if ablate != "latent_gate_off":
        o = o * jax.nn.sigmoid(_matmul(h, lp["w_g"]))[:, :, None]
    return _matmul(o.reshape(x.shape[0], -1), lp["wo"]), rows


# -- the feed-forward --------------------------------------------------------


def _ranks(p):
    """(T, E) int32: each entry's place among its row when sorted
    largest first; of equal values the lower index ranks first (a
    stable sort of the negated values)."""
    order = jnp.argsort(-p, axis=-1, stable=True)
    return jnp.argsort(order, axis=-1, stable=True)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "n_group", "topk_group"))
def _router(wr, b, m, *, top_k, scale, n_group, topk_group):
    """-> (weights (T, E): w_e where e is chosen, else 0; the (T, E)
    chosen mask; the (T, n_group) mask of the groups kept)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(m @ wr.astype(F32))
        biased = s + b.astype(F32)
        T, E = biased.shape
        grouped = biased.reshape(T, n_group, E // n_group)
        best_two = jnp.sort(grouped, axis=-1)[..., -2:]
        kept = _ranks(jnp.sum(best_two, axis=-1)) < topk_group  # (T, n)
        among = jnp.where(jnp.repeat(kept, E // n_group, axis=-1), biased,
                          -jnp.inf)
        mask = _ranks(among) < top_k
        chosen = jnp.where(mask, s, 0.0)
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        return chosen * scale, mask, kept


def feed_forward(lp, m, *, top_k, scale, held, n_group, topk_group, ablate):
    """Step 9 after the norm -> (what is added to the residual, the
    (T, E) chosen mask or None for a dense layer)."""
    if "wr" not in lp:
        return moe_ref.dense_ffn(m, lp["w_gate"], lp["w_up"],
                                 lp["w_down"]), None
    if ablate == "no_group":
        n_group = topk_group = 1
    elif ablate == "topk_group3":
        topk_group -= 1
    weight, mask, _ = _router(lp["wr"], lp["b"], m, top_k=top_k, scale=scale,
                              n_group=n_group, topk_group=topk_group)
    y = moe_ref.held_experts(m, weight, held, lp["w_gate"], lp["w_up"],
                             lp["w_down"])
    if ablate != "shared_off":
        y = y + moe_ref._swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y, mask


# -- the model ---------------------------------------------------------------


def layer(lp, x, *, index, kind, heads, nope, rope_dim, lin_heads, d_k, d_v,
          lower_bound, top_k, scale, held, n_group, topk_group, eps, theta,
          ablate):
    state = None
    if kind == LINEAR:
        y, state = kda_mixer(lp, x, heads=lin_heads, d_k=d_k, d_v=d_v,
                             lower_bound=lower_bound, eps=eps, ablate=ablate)
        x = x + y
    else:
        y, state = latent_mixer(lp, x, heads=heads, nope=nope,
                                rope_dim=rope_dim, eps=eps, theta=theta,
                                ablate=ablate)
        x = x + y
    if index == 0 and ablate == "dense_layer0_off":
        return x, None, state
    m = rms_norm(x, lp["w_post"].astype(F32), eps)
    y, mask = feed_forward(lp, m, top_k=top_k, scale=scale, held=held,
                           n_group=n_group, topk_group=topk_group,
                           ablate=ablate)
    return x + y, mask, state


def forward(params, tokens, *, layer_types, num_heads, nope, rope_dim,
            lin_heads, d_k, d_v, lower_bound, top_k, scale, held, n_group,
            topk_group, eps=1e-6, theta=6e6, ablate=None, rows=None,
            states=False):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), masks: a (T, E)
    chosen mask per routed layer, stacked) and, with ``states``, what
    the layers would keep of the sequence: the KDA layers' states after
    the last row (KDA layers, H, d_v, d_k) and the latent layers' rows
    (latent layers, T, rank + rope)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    masks, last, rows_kept = [], [], []
    for i, (kind, lp) in enumerate(zip(layer_types, params["layers"])):
        x, mask, state = layer(
            round8(lp), x, index=i, kind=kind, heads=num_heads,
            nope=nope, rope_dim=rope_dim, lin_heads=lin_heads, d_k=d_k,
            d_v=d_v, lower_bound=lower_bound, top_k=top_k, scale=scale,
            held=tuple(held), n_group=n_group, topk_group=topk_group,
            eps=eps, theta=theta, ablate=ablate)
        if mask is not None:
            masks.append(mask)
        if state is not None:
            (last if kind == LINEAR else rows_kept).append(state)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    out = (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),
           jnp.stack(masks) if masks else None)
    return out + (jnp.stack(last), jnp.stack(rows_kept)) if states else out
