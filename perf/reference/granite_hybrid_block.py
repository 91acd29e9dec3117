"""Plain float32 reference of the Granite-4.0-H decoder (ibm-granite/
granite-4.0-h-micro, ``model_type`` granitemoehybrid): Mamba-2 layers
nine of ten, a NoPE grouped-query attention layer the tenth, a SwiGLU
feed-forward, Granite's four scalar multipliers, a tied head.

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no state
pool, no chunking of the recurrence: the state-space rule is a
``lax.scan`` over the rows, one row a step, as the equations are
written; every matmul under ``jax.default_matmul_precision("highest")``.
Written from the equations, not from the block under test:

    d 2048; eps 1e-5;  x_0 = 12 E[token]          (embedding_multiplier)
    layer:  h = x + 0.22 mixer(RMSNorm(x; w_in))   (residual_multiplier)
            x = h + 0.22 W_d (silu(W_g n) * W_u n),  n = RMSNorm(h; w_post)
    after the last layer: logits = E^T RMSNorm(x; w_f) / 8   (tied head,
            logits_scaling)
    attention layer (u the normed rows): q 32 heads, k and v 8 heads of
       64 (query head i reads K/V head i // 4), no bias, no q/k norm,
       no rotation; causal softmax of 0.015625 q.k
       (attention_multiplier, not 64^-1/2); W_o
    mamba layer: 64 heads of 64 channels, state size 128, one group:
    1. [z (4096); xBC (4352); dt (64)] = W_in u
    2. xBC_t <- silu(sum_{j=0..3} w_j xBC_{t-3+j} + b)  (depthwise,
       causal, zeros before row 0); x_t (64 x 64), B_t (128), C_t (128)
       = split(xBC_t)       (B and C shared by all heads)
    3. dt_t = softplus(dt_t + dt_bias); a_t = exp(-exp(A_log) dt_t)
       (one of each a head)
    4. S_t = a_t S_{t-1} + dt_t x_t B_t^T, S_{-1} = 0 (64 x 128 a head);
       y_t = S_t C_t + D x_t
    5. out = W_out (RMSNorm_4096(y_t * silu(z_t); w_norm))  (the gate
       first, then the norm over all 4,096 channels)

It takes the system's parameter pytree (``paddle_tpu/models/
granite_hybrid.py``: ``emb w_f``, ``layers`` of ``w_in w_post w_gate
w_up w_down`` and either ``wq wk wv wo`` or ``w_zxbcdt w_conv b_conv
dt_bias A_log D w_norm w_out``) in whatever dtype it is served in and
widens a piece at a time to float32: one matrix, one head's scores, a
slice of the vocabulary.  The K/V heads are read off ``wk``'s width.
The mamba layers' sizes arrive under their published names
(``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``); the four
multipliers default to the published ones.

``forward(..., states=True)`` also hands back each mamba layer's state
after the last row, for the comparison of the served state entries.

``ablate`` changes one piece: "no_decay" (a 1), "no_dt_on_input" (the
write is ``x B^T``, not ``dt x B^T``), "no_conv" (the conv and its bias
replaced by the identity; the SiLU stays), "no_conv_bias", "no_skip_D",
"no_gate" (the silu(z) factor dropped), "norm_before_gate" (RMSNorm(y)
* silu(z)), "softmax_scale_rsqrt" (64^-1/2 for 1/64),
"rope_on_attention" (rotate-half RoPE, the published ``rope_theta``
10,000, on q and k), "no_residual_multiplier" (1 for 0.22), "post_norm"
(x + 0.22 RMSNorm(f(x)) with the same scales), "state_bf16" (the state
rounded to bfloat16 after every row), "fp8" (every weight rounded to
float8_e4m3fn first: the nearest precision below the bfloat16 the
configuration serves in).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA = "mamba"
VOCAB_SLICE = 16384
ROPE_THETA = 1e4
ABLATIONS = ("no_decay", "no_dt_on_input", "no_conv", "no_conv_bias",
             "no_skip_D", "no_gate", "norm_before_gate",
             "softmax_scale_rsqrt", "rope_on_attention",
             "no_residual_multiplier", "post_norm", "state_bf16", "fp8")


def _only(ablate, *mine):
    """``ablate`` where it is one of ``mine``, else None: a piece is
    compiled for the ablations that change it, not once for each of the
    thirteen."""
    return ablate if ablate in mine else None


@functools.partial(jax.jit, static_argnames=("eps",))
def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(F32)


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


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "ablate"))
def _conv_and_split(zxbcdt, w_conv, b_conv, dt_bias, A_log, *, heads,
                    d_head, d_state, ablate):
    """Steps 2 and 3 on the projection's rows -> z (T, inner), x (T, H,
    P), B, C (T, N), a, dt (T, H)."""
    T, inner = zxbcdt.shape[0], heads * d_head
    z = zxbcdt[:, :inner]
    xBC = zxbcdt[:, inner:2 * inner + 2 * d_state]
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * d_state:]
                         + dt_bias.astype(F32))
    if ablate != "no_conv":
        w = w_conv.astype(F32)                             # (4, C)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xBC.shape[1]), F32), xBC])
        xBC = sum(w[j] * padded[j:j + T] for j in range(taps))
        if ablate != "no_conv_bias":
            xBC = xBC + b_conv.astype(F32)
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :inner].reshape(T, heads, d_head)
    B = xBC[:, inner:inner + d_state]
    C = xBC[:, inner + d_state:]
    a = jnp.exp(-jnp.exp(A_log.astype(F32)) * dt)
    if ablate == "no_decay":
        a = jnp.ones_like(a)
    return z, x, B, C, a, dt


@functools.partial(jax.jit, static_argnames=("ablate",))
def _recurrence(x, B, C, a, dt, *, ablate):
    """Step 4 less the skip, row by row: x (T, H, P), B, C (T, N), a,
    dt (T, H) -> (y (T, H, P), the state after the last row (H, P,
    N))."""
    with jax.default_matmul_precision("highest"):
        def row(S, r):
            x_t, B_t, C_t, a_t, dt_t = r
            write = x_t if ablate == "no_dt_on_input" \
                else dt_t[:, None] * x_t
            S = a_t[:, None, None] * S \
                + write[:, :, None] * B_t[None, None, :]
            if ablate == "state_bf16":
                # the barrier keeps the compiler from dropping the round
                # trip as excess precision it is allowed to keep
                S = jax.lax.optimization_barrier(
                    S.astype(jnp.bfloat16)).astype(F32)
            return S, jnp.einsum("hpn,n->hp", S, C_t)

        H, P, N = x.shape[1], x.shape[2], B.shape[1]
        S, y = jax.lax.scan(row, jnp.zeros((H, P, N), F32),
                            (x, B, C, a, dt))
        return y, S


@functools.partial(jax.jit, static_argnames=("eps", "ablate"))
def _skip_gate_norm(y, x, z, D, w_norm, *, eps, ablate):
    """The skip of step 4 and step 5 less its projection."""
    if ablate != "no_skip_D":
        y = y + D.astype(F32)[:, None] * x
    y = y.reshape(z.shape)
    if ablate == "no_gate":
        return rms_norm(y, w_norm, eps)
    if ablate == "norm_before_gate":
        return rms_norm(y, w_norm, eps) * jax.nn.silu(z)
    return rms_norm(y * jax.nn.silu(z), w_norm, eps)


def mamba_mixer(lp, u, *, heads, d_head, d_state, eps, ablate):
    z, x, B, C, a, dt = _conv_and_split(
        _matmul(u, lp["w_zxbcdt"]), lp["w_conv"], lp["b_conv"],
        lp["dt_bias"], lp["A_log"], heads=heads, d_head=d_head,
        d_state=d_state,
        ablate=_only(ablate, "no_conv", "no_conv_bias", "no_decay"))
    y, S = _recurrence(x, B, C, a, dt, ablate=_only(
        ablate, "no_dt_on_input", "state_bf16"))
    y = _skip_gate_norm(y, x, z, lp["D"], lp["w_norm"], eps=eps,
                        ablate=_only(ablate, "no_skip_D", "no_gate",
                                     "norm_before_gate"))
    return _matmul(y, lp["w_out"]), S


@functools.partial(jax.jit, static_argnames=("scale", "rotate"))
def _heads(q, k, v, *, scale, rotate):
    """q (T, H, dh), k, v (T, KV, dh) -> (T, H, dh): causal softmax
    attention at ``scale``, query head i on K/V head i // (H / KV), a
    head at a time."""
    with jax.default_matmul_precision("highest"):
        if rotate:
            q, k = rope(q, ROPE_THETA), rope(k, ROPE_THETA)
        T, group = q.shape[0], q.shape[1] // k.shape[1]
        seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

        def one(qkv):
            q_h, k_h, v_h = qkv                            # (T, dh)
            s = (q_h @ k_h.T) * scale
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                  axis=-1) @ v_h

        by_head = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
        a = jax.lax.map(one, (by_head(q),
                              jnp.repeat(by_head(k), group, axis=0),
                              jnp.repeat(by_head(v), group, axis=0)))
        return jnp.moveaxis(a, 0, 1)


def attention_mixer(lp, u, *, heads, head_dim, scale, ablate):
    T = u.shape[0]
    kv_heads = lp["wk"].shape[1] // head_dim
    q = _matmul(u, lp["wq"]).reshape(T, heads, head_dim)
    k = _matmul(u, lp["wk"]).reshape(T, kv_heads, head_dim)
    v = _matmul(u, lp["wv"]).reshape(T, kv_heads, head_dim)
    if ablate == "softmax_scale_rsqrt":
        scale = head_dim ** -0.5
    a = _heads(q, k, v, scale=float(scale),
               rotate=ablate == "rope_on_attention")
    return _matmul(a.reshape(T, heads * head_dim), lp["wo"]), None


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    return _matmul(jax.nn.silu(_matmul(x, w_gate)) * _matmul(x, w_up),
                   w_down)


def feed_forward(lp, x):
    return _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def layer(lp, x, *, kind, heads, head_dim, mamba_n_heads, mamba_d_head,
          mamba_d_state, eps, attention_multiplier, residual_multiplier,
          ablate):
    """One layer over all rows -> (the rows after it, a mamba layer's
    final state or None)."""
    r = 1.0 if ablate == "no_residual_multiplier" else residual_multiplier

    def mixer(u):
        if kind == MAMBA:
            return mamba_mixer(lp, u, heads=mamba_n_heads,
                               d_head=mamba_d_head, d_state=mamba_d_state,
                               eps=eps, ablate=ablate)
        return attention_mixer(lp, u, heads=heads, head_dim=head_dim,
                               scale=attention_multiplier, ablate=ablate)

    if ablate == "post_norm":
        m, S = mixer(x)
        x = x + r * rms_norm(m, lp["w_in"], eps)
        return x + r * rms_norm(feed_forward(lp, x), lp["w_post"], eps), S
    m, S = mixer(rms_norm(x, lp["w_in"], eps))
    x = x + r * m
    return x + r * feed_forward(lp, rms_norm(x, lp["w_post"], eps)), S


def head(w_f, emb, x, eps, logits_scaling):
    """The tied head a slice of the vocabulary at a time (the whole
    embedding widened is 0.8 GB)."""
    n = rms_norm(x, w_f, eps)
    return jnp.concatenate(
        [_matmul(n, emb[v0:v0 + VOCAB_SLICE].T)
         for v0 in range(0, emb.shape[0], VOCAB_SLICE)],
        axis=-1) / logits_scaling


def forward(params, tokens, *, layer_types, num_heads, head_dim,
            mamba_n_heads, mamba_d_head, mamba_d_state, eps=1e-5,
            embedding_multiplier=12.0,
            residual_multiplier=0.22, attention_multiplier=0.015625,
            logits_scaling=8.0, ablate=None, rows=None, states=False):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> logits (len(rows), V); with ``states``,
    (logits, each mamba layer's state after the last row, (mamba
    layers, H, P, N))."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    emb = round8(params["emb"])
    x = emb[tokens].astype(F32) * embedding_multiplier
    kept = []
    for kind, lp in zip(layer_types, params["layers"]):
        x, S = layer(round8(lp), x, kind=kind, heads=num_heads,
                     head_dim=head_dim, mamba_n_heads=mamba_n_heads,
                     mamba_d_head=mamba_d_head,
                     mamba_d_state=mamba_d_state, eps=eps,
                     attention_multiplier=attention_multiplier,
                     residual_multiplier=residual_multiplier,
                     ablate=ablate)
        if round8 is _round_fp8:
            # a layer's rounded copies go before the next's are made
            # (the loop runs 40 layers ahead of the device)
            x.block_until_ready()
        if S is not None:
            kept.append(S)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head(params["w_f"], emb, x, eps, logits_scaling)
    return (logits, jnp.stack(kept)) if states else logits


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
