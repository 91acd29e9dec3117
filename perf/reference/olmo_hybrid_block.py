"""Plain float32 reference of the Olmo-Hybrid decoder (allenai/
Olmo-Hybrid-7B, ``model_type`` olmo_hybrid), as one pipeline stage
holds it (its layers, the final norm and the untied head).

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no state
pool, no chunking of the recurrence: the gated delta rule is a
``lax.scan`` over the rows, one row a step, as the equations are
written; every matmul under ``jax.default_matmul_precision("highest")``.
Written from the equations, not from the block under test:

    d 3840, 30 heads; full layers: head size 128; linear layers: d_k 96,
    d_v 192, conv width 4; eps 1e-6;  x_0 = E[token]
    linear layer (u = the layer's input rows):
    1. z = [W_q u; W_k u; W_v u] (2880 + 2880 + 5760 channels); causal
       depthwise conv of width 4 over each channel (taps on rows
       t-3..t, zeros before row 0, no bias); SiLU; split to q_t, k_t
       (30 x 96) and v_t (30 x 192)
    2. q_t <- q_t / |q_t| * d_k^-1/2, k_t <- k_t / |k_t| (the norms
       with 1e-6 under the root)
    3. beta_t = 2 sigmoid(W_b u); g_t = -exp(A_log) softplus(W_a u +
       dt_bias); alpha_t = exp(g_t)   (one of each a head)
    4. S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T,
       S_{-1} = 0 (d_v x d_k);  o_t = S_t q_t
    5. y_t = W_o [RMSNorm_{d_v}(o_t; w_on) * silu(W_g u)]
    full layer: q, k, v = W u (3840 each); RMSNorm over the whole q and
       the whole k projection; 30 heads of 128; causal softmax at scale
       128^-1/2; no positional rotation; W_o
    block: x <- x + RMSNorm(mixer(x)); x <- x + RMSNorm(W_d (silu(W_g x)
       * W_u x)); after the last layer RMSNorm(x; w_f), logits = H x

It takes the system's parameter pytree (``paddle_tpu/models/
olmo_hybrid.py``: ``emb lm_head w_f``, ``layers`` of ``w_mix_norm
w_ff_norm w_gate w_up w_down`` and either ``wq wk wv wo w_qn w_kn`` or
``w_qkv w_conv w_g w_b w_a w_o w_on dt_bias A_log``) in whatever dtype
it is served in and widens a piece at a time to float32: one matrix,
one head's scores, a slice of the vocabulary.

``ablate`` changes one piece: "no_decay" (alpha 1), "no_delta" (drops
``- alpha S k k^T beta``: plain decayed linear attention), "beta_1x"
(beta = sigmoid, not twice it), "no_conv" (the conv replaced by the
identity; the SiLU stays), "no_l2norm" (q and k not normalised),
"no_out_gate" (the silu(W_g u) factor dropped), "rope_on_full"
(rotate-half RoPE, theta 5e5, on the full layers), "pre_norm" (x + f(
RMSNorm(x)) with the same scales), "state_bf16" (the state rounded to
bfloat16 after every row), "fp8" (every weight rounded to float8_e4m3fn
first: the nearest precision below the bfloat16 the configuration
serves in).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR = "linear_attention"
VOCAB_SLICE = 16384
ROPE_THETA = 5e5
ABLATIONS = ("no_decay", "no_delta", "beta_1x", "no_conv", "no_l2norm",
             "no_out_gate", "rope_on_full", "pre_norm", "state_bf16", "fp8")


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def rope(x, theta):
    """x (T, H, dh) at positions 0..T-1; channel i pairs with i + dh/2."""
    T, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


@functools.partial(jax.jit, static_argnames=("ablate",))
def _recurrence(q, k, v, alpha, beta, *, ablate):
    """Step 4, row by row: q, k (T, H, d_k), v (T, H, d_v), alpha, beta
    (T, H) -> o (T, H, d_v)."""
    with jax.default_matmul_precision("highest"):
        def row(S, r):
            q_t, k_t, v_t, a_t, b_t = r
            decayed = a_t[:, None, None] * S
            read = jnp.einsum("hvk,hk->hv", decayed, k_t)
            write = v_t if ablate == "no_delta" else v_t - read
            S = decayed + b_t[:, None, None] * write[:, :, None] \
                * k_t[:, None, :]
            if ablate == "state_bf16":
                # the barrier keeps the compiler from dropping the round
                # trip as excess precision it is allowed to keep
                S = jax.lax.optimization_barrier(
                    S.astype(jnp.bfloat16)).astype(F32)
            return S, jnp.einsum("hvk,hk->hv", S, q_t)

        H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
        _, o = jax.lax.scan(row, jnp.zeros((H, dv, dk), F32),
                            (q, k, v, alpha, beta))
        return o


def linear_mixer(lp, u, *, heads, d_k, d_v, eps, ablate):
    T = u.shape[0]
    z = _matmul(u, lp["w_qkv"])
    if ablate != "no_conv":
        w = lp["w_conv"].astype(F32)                       # (4, C)
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
    beta = jax.nn.sigmoid(_matmul(u, lp["w_b"]))
    if ablate != "beta_1x":
        beta = 2.0 * beta
    g = -jnp.exp(lp["A_log"].astype(F32)) * jax.nn.softplus(
        _matmul(u, lp["w_a"]) + lp["dt_bias"].astype(F32))
    alpha = jnp.ones_like(g) if ablate == "no_decay" else jnp.exp(g)
    o = _recurrence(q, k, v, alpha, beta, ablate=ablate)
    o = rms_norm(o, lp["w_on"].astype(F32), eps)
    if ablate != "no_out_gate":
        o = o * jax.nn.silu(_matmul(u, lp["w_g"])).reshape(T, heads, d_v)
    return _matmul(o.reshape(T, heads * d_v), lp["w_o"])


@jax.jit
def _one_head(q, k, v):
    """One head (T, dh): causal softmax attention."""
    with jax.default_matmul_precision("highest"):
        T, dh = q.shape
        s = (q @ k.T) * dh ** -0.5
        seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v


def full_mixer(lp, u, *, heads, head_dim, eps, ablate):
    T = u.shape[0]
    q = rms_norm(_matmul(u, lp["wq"]), lp["w_qn"].astype(F32), eps)
    k = rms_norm(_matmul(u, lp["wk"]), lp["w_kn"].astype(F32), eps)
    v = _matmul(u, lp["wv"])
    q, k, v = (a.reshape(T, heads, head_dim) for a in (q, k, v))
    if ablate == "rope_on_full":
        q, k = rope(q, ROPE_THETA), rope(k, ROPE_THETA)
    a = jnp.stack([_one_head(q[:, h], k[:, h], v[:, h])
                   for h in range(heads)], axis=1)
    return _matmul(a.reshape(T, heads * head_dim), lp["wo"])


def feed_forward(lp, x):
    return _matmul(jax.nn.silu(_matmul(x, lp["w_gate"]))
                   * _matmul(x, lp["w_up"]), lp["w_down"])


def layer(lp, x, *, kind, heads, head_dim, lin_heads, d_k, d_v, eps,
          ablate):
    mix_scale = lp["w_mix_norm"].astype(F32)
    ff_scale = lp["w_ff_norm"].astype(F32)

    def mixer(u):
        if kind == LINEAR:
            return linear_mixer(lp, u, heads=lin_heads, d_k=d_k, d_v=d_v,
                                eps=eps, ablate=ablate)
        return full_mixer(lp, u, heads=heads, head_dim=head_dim, eps=eps,
                          ablate=ablate)

    if ablate == "pre_norm":
        x = x + mixer(rms_norm(x, mix_scale, eps))
        return x + feed_forward(lp, rms_norm(x, ff_scale, eps))
    x = x + rms_norm(mixer(x), mix_scale, eps)
    return x + rms_norm(feed_forward(lp, x), ff_scale, eps)


def head(w_f, lm_head, x, eps):
    """The untied head a slice of the vocabulary at a time (the whole
    of it widened is 1.5 GB)."""
    n = rms_norm(x, w_f.astype(F32), eps)
    return jnp.concatenate(
        [_matmul(n, lm_head[:, v0:v0 + VOCAB_SLICE])
         for v0 in range(0, lm_head.shape[1], VOCAB_SLICE)], axis=-1)


def forward(params, tokens, *, layer_types, num_heads, head_dim, lin_heads,
            d_k, d_v, eps=1e-6, ablate=None, rows=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> logits (len(rows), V)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    for kind, lp in zip(layer_types, params["layers"]):
        x = layer(round8(lp), x, kind=kind, heads=num_heads,
                  head_dim=head_dim, lin_heads=lin_heads, d_k=d_k, d_v=d_v,
                  eps=eps, ablate=ablate)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(params["w_f"], round8(params["lm_head"]), x, eps)


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
