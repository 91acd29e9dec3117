"""Plain float32 reference of the Phi-4-mini-flash-reasoning decoder
(microsoft/Phi-4-mini-flash-reasoning, ``model_type`` phi4flash: SambaY
with differential attention, arXiv:2507.06607).

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no packed
rows, no ring, no state pool, no chunking of the recurrence, no layer
skipped on any row: every layer runs on every row, the selective scan
is a ``lax.scan`` over the rows, one row a step, as the equations are
written, attention is four dense softmax products a pair of heads as
the published module forms them; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, not from the block under test:

    L layers, half = L / 2; d 2560; eps 1e-5;  x_0 = E[token]
    layer l:  h = x + mixer_l(LN(x; w_in, b_in))
              x = h + W_d (silu(W_g n) * W_u n),  n = LN(h; w_post, b_post)
    after the last layer: logits = E^T LN(x; w_f, b_f)        (tied head)
    LN: (x - mean) / sqrt(var + eps) * w + b   (no positions anywhere)

    l even, l <= half: MAMBA-1, C channels, state N, rank R, u the normed rows
      1. [xc; z] = W_in u                              (C each, no bias)
      2. xc_t <- silu(sum_{j=0..3} w_j xc_{t-3+j} + b_conv)   (depthwise,
         causal, zeros before row 0)
      3. [dt'; B_t; C_t] = W_x xc_t (R, N, N)
         dt_t = softplus(W_dt dt'_t + b_dt)
      4. S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * xc_t) B_t^T, S_{-1} = 0,
         A = -exp(A_log) (C x N);   y_t = S_t C_t + D * xc_t
      5. out = W_out (y_t * silu(z_t));  layer ``half`` also hands m = y
    l even, l > half: GMU   out = W_out (silu(W_in u) * m_t)     (same row)
    l odd: DIFFERENTIAL ATTENTION.  [q; k; v] = W_qkv u + b_qkv (H / H/2 /
      H/2 heads of dh); l > half + 1 (cross): q = W_q u + b_q, and k, v
      are layer half + 1's.  Query pair p = heads (2p, 2p + 1) reads K/V
      pair r = p // 2 = heads (2r, 2r + 1).  A row sees itself and the
      rows before it; where l < half only the newest ``window`` of them:
        a1 = softmax(q1 k1^T / sqrt(dh)) [v1 | v2]
        a2 = softmax(q2 k2^T / sqrt(dh)) [v1 | v2]            (2 dh wide)
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
        lam0(l) = 0.8 - 0.6 exp(-0.3 l)
        o_p = (1 - lam0(l)) RMSNorm_{2 dh}(a1 - lam a2) * w_sub
        out = W_o concat_p(o_p) + b_o

It takes the system's parameter pytree (``paddle_tpu/models/
phi4_flash.py``: ``emb w_f b_f``, ``layers`` of ``w_in b_in w_post
b_post w_gate w_up w_down`` and the mixer's own) in whatever dtype it is
served in and widens a piece at a time to float32.  Which layer is which
is reckoned here from its index.

``forward(..., states=True)`` also hands back each Mamba layer's state
after the last row, (C, N) a layer as published.

``ablate`` changes one piece: "no_diff_term" (lam 0), "lam0_constant"
(0.8 at every depth), "no_subln" (no RMSNorm, no w_sub),
"no_one_minus_lam0", "plain_gqa_pairing" (query head h scores K head h
// 2, as plain grouped heads would), "window_off" (a window layer sees
every earlier row), "cross_reads_window" (a cross layer sees the newest
``window`` rows only), "cross_rows_zero" (layer half + 1 and the cross
layers see, beside a row itself, only the sequence's last ``window``
rows: the run kept as a ring would keep it), "gmu_no_memory" (m 1),
"gmu_memory_after_gate" (m = y silu(z)), "no_decay" (exp(dt A) 1),
"no_dt_on_input" (the write is xc B^T), "no_conv" (the conv and its
bias the identity; the SiLU stays), "no_skip_D", "no_gate",
"rmsnorm_for_layernorm" (no mean, no bias), "rope_on_attention"
(rotate-half RoPE, theta 10,000, on q and k), "state_bf16" (the state
rounded to bfloat16 after every row), "fp8" (every weight rounded to
float8_e4m3fn first: the nearest precision below the bfloat16 the
configuration serves in).
"""

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
VOCAB_SLICE = 16384
ROW_BLOCK = 1024
ROPE_THETA = 1e4
ABLATIONS = ("no_diff_term", "lam0_constant", "no_subln",
             "no_one_minus_lam0", "plain_gqa_pairing", "window_off",
             "cross_reads_window", "cross_rows_zero", "gmu_no_memory",
             "gmu_memory_after_gate", "no_decay", "no_dt_on_input",
             "no_conv", "no_skip_D", "no_gate", "rmsnorm_for_layernorm",
             "rope_on_attention", "state_bf16", "fp8")


def _only(ablate, *mine):
    """``ablate`` where it is one of ``mine``, else None: a piece is
    compiled for the ablations that change it, not once for each."""
    return ablate if ablate in mine else None


def kind_of(idx, num_layers):
    half = num_layers // 2
    if idx % 2 == 0:
        return "mamba" if idx <= half else "gmu"
    return ("window" if idx < half else
            "full" if idx == half + 1 else "cross")


@functools.partial(jax.jit, static_argnames=("eps", "rms"))
def norm(x, w, b, *, eps, rms=False):
    if rms:
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                                 + eps) * w.astype(F32)
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * w.astype(F32) + b.astype(F32)


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


def _by_rows(fn, x):
    """``fn`` a block of rows at a time: a 5,000-row prompt's widest
    float32 intermediates stay a fifth of their size."""
    if x.shape[0] <= ROW_BLOCK:
        return fn(x)
    return jnp.concatenate([fn(x[r:r + ROW_BLOCK])
                            for r in range(0, x.shape[0], ROW_BLOCK)])


def rope(x, theta):
    """x (T, H, dh) at positions 0..T-1; channel i pairs with i + dh/2."""
    T, _, dh = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


# -- Mamba-1 ------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ablate",))
def _conv(xc, w_conv, b_conv, *, ablate):
    """Step 2."""
    if ablate != "no_conv":
        w = w_conv.astype(F32)                              # (4, C)
        taps, T = w.shape[0], xc.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xc.shape[1]), F32), xc])
        xc = sum(w[j] * padded[j:j + T] for j in range(taps)) \
            + b_conv.astype(F32)
    return jax.nn.silu(xc)


@functools.partial(jax.jit, static_argnames=("ablate",))
def _scan(xc, dt, B, C, A_log, *, ablate):
    """Step 4 less the skip, row by row: xc, dt (T, C), B, C (T, N) ->
    (y (T, C), the state after the last row (C, N))."""
    A = -jnp.exp(A_log.astype(F32))                         # (C, N)

    def row(S, r):
        x_t, dt_t, B_t, C_t = r
        decay = jnp.ones_like(S) if ablate == "no_decay" \
            else jnp.exp(dt_t[:, None] * A)
        write = x_t if ablate == "no_dt_on_input" else dt_t * x_t
        S = decay * S + write[:, None] * B_t[None, :]
        if ablate == "state_bf16":
            # the barrier keeps the compiler from dropping the round
            # trip as excess precision it is allowed to keep
            S = jax.lax.optimization_barrier(
                S.astype(jnp.bfloat16)).astype(F32)
        return S, jnp.sum(S * C_t[None, :], axis=-1)

    S, y = jax.lax.scan(row, jnp.zeros(A.shape, F32), (xc, dt, B, C))
    return y, S


def mamba_mixer(lp, u, *, ablate):
    C, N = lp["A_log"].shape
    R = lp["w_dt"].shape[0]
    xz = _matmul(u, lp["w_inproj"])
    xc = _conv(xz[:, :C], lp["w_conv"], lp["b_conv"],
               ablate=_only(ablate, "no_conv"))
    z = xz[:, C:]
    dbc = _matmul(xc, lp["w_x"])
    dt = jax.nn.softplus(_matmul(dbc[:, :R], lp["w_dt"])
                         + lp["b_dt"].astype(F32))
    y, S = _scan(xc, dt, dbc[:, R:R + N], dbc[:, R + N:], lp["A_log"],
                 ablate=_only(ablate, "no_decay", "no_dt_on_input",
                              "state_bf16"))
    if ablate != "no_skip_D":
        y = y + lp["D"].astype(F32) * xc
    gated = y if ablate == "no_gate" else y * jax.nn.silu(z)
    memory = gated if ablate == "gmu_memory_after_gate" else y
    return _matmul(gated, lp["w_out"]), S, memory


def gmu_mixer(lp, u, memory, *, ablate):
    g = jax.nn.silu(_matmul(u, lp["w_inproj"]))
    if ablate != "gmu_no_memory":
        g = g * memory
    return _matmul(g, lp["w_out"])


# -- differential attention ---------------------------------------------------


def lam0(idx, ablate):
    return 0.8 if ablate == "lam0_constant" else \
        0.8 - 0.6 * math.exp(-0.3 * idx)


@functools.partial(jax.jit, static_argnames=("near", "tail", "plain"))
def _pairs(q, k, v, *, near, tail, plain):
    """q (T, H, dh), k, v (T, H / 2, dh) -> a1, a2 (T, H / 2, 2 dh):
    the two softmaxes of every query pair, each times [v1 | v2], a pair
    at a time.  ``near``: a row sees only the newest ``near`` rows (0:
    all before it); ``tail``: beside itself, only the sequence's last
    ``tail`` rows (0: all)."""
    with jax.default_matmul_precision("highest"):
        T, H, dh = q.shape
        t = jnp.arange(T)
        back = t[:, None] - t[None, :]
        seen = back >= 0
        if near:
            seen &= back < near
        if tail:
            seen &= (t[None, :] >= T - tail) | (back == 0)
        scale = dh ** -0.5

        def softmax(qh, kh):
            return jax.nn.softmax(
                jnp.where(seen, (qh @ kh.T) * scale, -jnp.inf), axis=-1)

        def one(p):
            r = p // 2
            q1, q2 = q[:, 2 * p], q[:, 2 * p + 1]
            k1, k2 = (k[:, p], k[:, p]) if plain \
                else (k[:, 2 * r], k[:, 2 * r + 1])
            v1, v2 = v[:, 2 * r], v[:, 2 * r + 1]
            s1, s2 = softmax(q1, k1), softmax(q2, k2)
            return (jnp.concatenate([s1 @ v1, s1 @ v2], axis=-1),
                    jnp.concatenate([s2 @ v1, s2 @ v2], axis=-1))

        a1, a2 = jax.lax.map(one, jnp.arange(H // 2))
        return jnp.moveaxis(a1, 0, 1), jnp.moveaxis(a2, 0, 1)


@functools.partial(jax.jit, static_argnames=("init", "eps", "ablate"))
def _difference(a1, a2, lq1, lk1, lq2, lk2, w_sub, *, init, eps, ablate):
    lam = 0.0 if ablate == "no_diff_term" else (
        jnp.exp(jnp.sum(lq1.astype(F32) * lk1.astype(F32)))
        - jnp.exp(jnp.sum(lq2.astype(F32) * lk2.astype(F32))) + init)
    o = a1 - lam * a2
    if ablate != "no_subln":
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + eps) * w_sub.astype(F32)
    if ablate != "no_one_minus_lam0":
        o = o * (1.0 - init)
    return o.reshape(o.shape[0], -1)


def attention_mixer(lp, u, kv, *, idx, kind, heads, head_dim, window, eps,
                    ablate):
    """-> (the mixer's rows, this layer's (k, v) or None for a cross
    layer, which reads ``kv``)."""
    T = u.shape[0]
    if kind == "cross":
        q = _matmul(u, lp["wq"]) + lp["b_q"].astype(F32)
        k, v = kv
        own = None
    else:
        qkv = _matmul(u, lp["wqkv"]) + lp["b_qkv"].astype(F32)
        q, k, v = jnp.split(
            qkv, [heads * head_dim, heads * head_dim * 3 // 2], axis=-1)
        k = k.reshape(T, heads // 2, head_dim)
        v = v.reshape(T, heads // 2, head_dim)
        own = (k, v)
    q = q.reshape(T, heads, head_dim)
    if ablate == "rope_on_attention":
        # a cross layer's keys were rotated by the layer that made them
        q = rope(q, ROPE_THETA)
        if own is not None:
            k = rope(k, ROPE_THETA)
            own = (k, v)
    near = window if (kind == "window" and ablate != "window_off") or (
        kind == "cross" and ablate == "cross_reads_window") else 0
    tail = window if ablate == "cross_rows_zero" and kind != "window" else 0
    a1, a2 = _pairs(q, k, v, near=near, tail=tail,
                    plain=ablate == "plain_gqa_pairing")
    o = _difference(a1, a2, lp["lq1"], lp["lk1"], lp["lq2"], lp["lk2"],
                    lp["w_sub"], init=lam0(idx, ablate), eps=eps,
                    ablate=_only(ablate, "no_diff_term", "no_subln",
                                 "no_one_minus_lam0"))
    return _matmul(o, lp["wo"]) + lp["b_o"].astype(F32), own


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    return _matmul(jax.nn.silu(_matmul(x, w_gate)) * _matmul(x, w_up),
                   w_down)


def head(params, x, eps, rms):
    """The tied head a slice of the vocabulary at a time (the whole
    embedding widened is 2 GB)."""
    emb = params["emb"]
    n = norm(x, params["w_f"], params["b_f"], eps=eps, rms=rms)
    return jnp.concatenate(
        [_matmul(n, emb[v0:v0 + VOCAB_SLICE].T)
         for v0 in range(0, emb.shape[0], VOCAB_SLICE)], axis=-1)


def forward(params, tokens, *, num_heads, head_dim, window, eps=1e-5,
            ablate=None, rows=None, states=False):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> logits (len(rows), V); with ``states``,
    (logits, each Mamba layer's state after the last row, (Mamba
    layers, C, N))."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    rms = ablate == "rmsnorm_for_layernorm"
    L = len(params["layers"])
    emb = round8(params["emb"])
    x = emb[tokens].astype(F32)
    kept, memory, kv = [], None, None
    for idx, lp in enumerate(params["layers"]):
        lp, kind = round8(lp), kind_of(idx, L)
        u = norm(x, lp["w_in"], lp["b_in"], eps=eps, rms=rms)
        if kind == "mamba":
            m, S, y = mamba_mixer(lp, u, ablate=ablate)
            kept.append(S)
            if idx == L // 2:
                memory = y
        elif kind == "gmu":
            m = gmu_mixer(lp, u, memory, ablate=ablate)
        else:
            m, own = attention_mixer(
                lp, u, kv, idx=idx, kind=kind, heads=num_heads,
                head_dim=head_dim, window=window, eps=eps, ablate=ablate)
            if kind == "full":
                kv = own
        x = x + m
        n = norm(x, lp["w_post"], lp["b_post"], eps=eps, rms=rms)
        x = x + _by_rows(lambda r: _swiglu(r, lp["w_gate"], lp["w_up"],
                                           lp["w_down"]), n)
        if round8 is _round_fp8:
            # a layer's rounded copies go before the next's are made
            x.block_until_ready()
    if rows is not None:
        x = x[jnp.asarray(rows)]
    logits = head({**params, "emb": emb}, x, eps, rms)
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
