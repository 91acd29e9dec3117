"""Plain float32 reference of the LFM2-MoE decoder (LiquidAI/LFM2-8B-A1B,
``model_type`` lfm2_moe): gated short-conv layers three of four, a
RoPE grouped-query attention layer the fourth, two leading dense
SwiGLUs and then sigmoid-routed experts, a tied head.

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no state
entry, no bucket and no chunk of a prompt; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, not from the block under test:

    eps 1e-5;  x_0 = E[token]
    layer:  h = x + op(RMSNorm(x; w_in));  x' = h + ffn(RMSNorm(h; w_post))
    after the last layer: logits = E^T RMSNorm(x; w_f)     (tied head)
    conv layer (u the normed rows, d channels):
       [B; C; z] = W_bcz u (three chunks of d, in that order); g = B * z
       c_t = sum_{j=0..L-1} w[j] g_{t-(L-1)+j}  (depthwise, causal, zeros
       before row 0, no bias, no activation);  op = W_out (C * c)
    attention layer: q H heads, k and v KV heads of dh (query head i
       reads K/V head i // (H / KV)), no bias; q <- RoPE(RMSNorm_dh(q;
       w_qn)), k <- RoPE(RMSNorm_dh(k; w_kn)) (rotate-half over the whole
       head, theta); causal softmax of q.k dh^-1/2; W_o
    feed-forward: a layer without a router W_d (silu(W_g m) * W_u m); a
       routed one s = sigmoid(W_r m) (float32), the top-k of s + b (a tie
       to the lower index), weights scale * s_chosen / (sum of the chosen
       + route_eps), the weighted sum of the chosen experts' SwiGLUs

It takes the system's parameter pytree (``paddle_tpu/models/
lfm2_moe.py``: ``emb w_f``, ``layers`` of ``w_in w_post w_gate w_up
w_down [wr b]`` and either ``wq wk wv wo w_qn w_kn`` or ``w_bcz w_conv
w_out``) in whatever dtype it is served in and widens a piece at a time
to float32: one matrix, one expert, one head's scores over a block of
query rows, a slice of the vocabulary.  Everything that is a function of
a row alone runs ``ROW_BLOCK`` rows at a time (a sequence is padded on
the right to whole blocks; every mixer is causal, so the padding reaches
no real row), so that the reference fits on a chip beside the pools.

``forward`` hands back the logits, each routed layer's chosen sets (T,
E) bool and, with ``tails=True``, each conv layer's last ``L - 1`` gated
rows ``g`` (what a sequence's state entry must hold).

``ablate`` changes one piece: "conv_silu" (``silu`` of the conv's sum,
as the other hybrids' convs have it), "gate_b_off" (``g = z``),
"gate_c_off" (``op = W_out c``), "tail_zero_at_chunk" (rows from
``chunk_at`` on see zeros where the gated rows before ``chunk_at``
were: a chunk that starts from an empty tail), "no_qk_norm",
"norm_after_rope", "no_rope", "bias_off" (ranked by ``s`` alone),
"no_renorm" (weights ``scale * s_chosen``), "dense_layers_routed" (the
leading dense layers routed too, with the first routed layer's router
and experts: the published config names the dense layers by count
alone); and the precisions below the configuration's: "fp8" (every
weight rounded to float8_e4m3fn first), "kv_fp8" (the K, after the
rotation, and V rows rounded to float8), "tail_fp8" (the gated rows
``g`` rounded to float8 before the conv: what a float8 tail would
hold).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
F8 = jnp.float8_e4m3fn
CONV = "conv"
VOCAB_SLICE = 16384
ROW_BLOCK = 2048
ABLATIONS = ("conv_silu", "gate_b_off", "gate_c_off", "tail_zero_at_chunk",
             "no_qk_norm", "norm_after_rope", "no_rope", "bias_off",
             "no_renorm", "dense_layers_routed")
PRECISIONS = ("fp8", "kv_fp8", "tail_fp8")


def _only(ablate, *mine):
    """``ablate`` where it is one of ``mine``, else None: a piece is
    compiled for the ablations that change it, not once for each."""
    return ablate if ablate in mine else None


def _round8(x):
    """``x`` rounded to float8_e4m3fn, in its own dtype (the barrier
    keeps the compiler from dropping the round trip)."""
    return jax.lax.optimization_barrier(x.astype(F8)).astype(x.dtype)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(F32)


def rope(x, pos, theta):
    """x (T, H, dh) at positions ``pos`` (T,); channel i pairs with
    i + dh/2 (rotate-half)."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _matmul(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


_matmul_jit = jax.jit(_matmul)


def _by_rows(fn, *xs):
    """``fn`` over row blocks of ``ROW_BLOCK`` of the arrays ``xs``
    (each (T, ...), T one block or whole blocks) -> its outputs
    concatenated."""
    T = xs[0].shape[0]
    if T <= ROW_BLOCK:
        return fn(*xs)
    assert T % ROW_BLOCK == 0, T
    outs = [fn(*(x[r:r + ROW_BLOCK] for x in xs))
            for r in range(0, T, ROW_BLOCK)]
    if isinstance(outs[0], tuple):
        return tuple(jnp.concatenate(o) for o in zip(*outs))
    return jnp.concatenate(outs)


# -- the conv layer -----------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps", "ablate"))
def _gated(x, w_in, w_bcz, *, eps, ablate):
    """-> (g = B * z, the output gate's rows C)."""
    d = x.shape[-1]
    bcz = _matmul(rms_norm(x, w_in, eps), w_bcz)
    B, C, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    g = z if ablate == "gate_b_off" else B * z
    return (_round8(g) if ablate == "tail_fp8" else g), C


@functools.partial(jax.jit, static_argnames=("ablate", "chunk_at"))
def _short_conv(g, w, *, ablate, chunk_at):
    """c_t = sum_j w[j] g_{t-(L-1)+j}, zeros before row 0."""
    L, T = w.shape[0], g.shape[0]
    w = w.astype(F32)
    gp = jnp.concatenate([jnp.zeros((L - 1, g.shape[1]), F32), g])
    c = sum(gp[j:j + T] * w[j] for j in range(L))
    if ablate == "tail_zero_at_chunk":
        # rows from chunk_at on, with the rows before chunk_at zeroed
        t = jnp.arange(T + L - 1)[:, None] - (L - 1)
        cut = jnp.where(t >= chunk_at, gp, 0.0)
        c = jnp.where(jnp.arange(T)[:, None] >= chunk_at,
                      sum(cut[j:j + T] * w[j] for j in range(L)), c)
    return jax.nn.silu(c) if ablate == "conv_silu" else c


@functools.partial(jax.jit, static_argnames=("ablate",))
def _conv_out(c, C, w_out, *, ablate):
    return _matmul(c if ablate == "gate_c_off" else C * c, w_out)


def conv_mixer(lp, x, *, eps, ablate, chunk_at):
    g, C = _by_rows(functools.partial(
        _gated, w_in=lp["w_in"], w_bcz=lp["w_bcz"], eps=eps,
        ablate=_only(ablate, "gate_b_off", "tail_fp8")), x)
    c = _short_conv(g, lp["w_conv"],
                    ablate=_only(ablate, "conv_silu", "tail_zero_at_chunk"),
                    chunk_at=chunk_at)
    op = _by_rows(functools.partial(
        _conv_out, w_out=lp["w_out"], ablate=_only(ablate, "gate_c_off")),
        c, C)
    return op, g


# -- the attention layer ------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "eps", "theta", "ablate"))
def _qkv(x, pos, w_in, wq, wk, wv, w_qn, w_kn, *, heads, head_dim, eps,
         theta, ablate):
    T = x.shape[0]
    u = rms_norm(x, w_in, eps)
    q = _matmul(u, wq).reshape(T, heads, head_dim)
    k = _matmul(u, wk).reshape(T, -1, head_dim)
    v = _matmul(u, wv).reshape(T, -1, head_dim)

    def placed(a, w):
        if ablate == "no_qk_norm":
            return rope(a, pos, theta)
        if ablate == "norm_after_rope":
            return rms_norm(rope(a, pos, theta), w, eps)
        if ablate == "no_rope":
            return rms_norm(a, w, eps)
        return rope(rms_norm(a, w, eps), pos, theta)

    q, k = placed(q, w_qn), placed(k, w_kn)
    if ablate == "kv_fp8":
        k, v = _round8(k), _round8(v)
    return q, k, v


@jax.jit
def _heads(q, q_pos, k, v):
    """A block of query rows ``q`` (R, H, dh) at positions ``q_pos`` over
    ALL the sequence's keys ``k``, ``v`` (T, KV, dh): causal softmax of
    q.k dh^-1/2, a head at a time."""
    with jax.default_matmul_precision("highest"):
        group, scale = q.shape[1] // k.shape[1], q.shape[-1] ** -0.5
        seen = q_pos[:, None] >= jnp.arange(k.shape[0])[None, :]

        def one(qkv):
            q_h, k_h, v_h = qkv
            s = (q_h @ k_h.T) * scale
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                  axis=-1) @ v_h

        by_head = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
        a = jax.lax.map(one, (by_head(q),
                              jnp.repeat(by_head(k), group, axis=0),
                              jnp.repeat(by_head(v), group, axis=0)))
        return jnp.moveaxis(a, 0, 1)


def attention_mixer(lp, x, *, heads, head_dim, eps, theta, ablate):
    T = x.shape[0]
    pos = jnp.arange(T)
    q, k, v = _by_rows(functools.partial(
        _qkv, w_in=lp["w_in"], wq=lp["wq"], wk=lp["wk"], wv=lp["wv"],
        w_qn=lp["w_qn"], w_kn=lp["w_kn"], heads=heads, head_dim=head_dim,
        eps=eps, theta=theta,
        ablate=_only(ablate, "no_qk_norm", "norm_after_rope", "no_rope",
                     "kv_fp8")), x, pos)
    a = _by_rows(lambda q_b, p_b: _heads(q_b, p_b, k, v), q, pos)
    return _by_rows(lambda a_b: _matmul_jit(a_b, lp["wo"]),
                    a.reshape(T, heads * head_dim))


# -- the feed-forward ---------------------------------------------------------


@jax.jit
def _swiglu(m, w_gate, w_up, w_down):
    return _matmul(jax.nn.silu(_matmul(m, w_gate)) * _matmul(m, w_up),
                   w_down)


def top_k_mask(ranked, k):
    """(T, E) bool: each row's ``k`` largest of ``ranked``, a tie to the
    lower index, one maximum at a time."""
    def take(carry, _):
        left, mask = carry
        best = jnp.argmax(left, axis=-1)               # the first on a tie
        hit = jax.nn.one_hot(best, left.shape[-1], dtype=bool)
        return (jnp.where(hit, -jnp.inf, left), mask | hit), None

    (_, mask), _ = jax.lax.scan(
        take, (ranked, jnp.zeros(ranked.shape, bool)), None, length=k)
    return mask


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "route_eps",
                                             "ablate"))
def _routed(m, given, wr, b, w_gate, w_up, w_down, *, top_k, scale,
            route_eps, ablate):
    """-> (the weighted sum of each row's chosen experts, the chosen
    sets (R, E) bool).  Every expert over every row, one expert at a
    time, the weight 0 where a row did not choose it.  ``given`` (R, E)
    bool or None: the sets to take in the place of the router's own
    choice (the weights are still this router's scores)."""
    s = jax.nn.sigmoid(_matmul(m, wr))
    mask = given if given is not None else top_k_mask(
        s if ablate == "bias_off" else s + b.astype(F32), top_k)
    chosen = jnp.where(mask, s, 0.0)
    weight = scale * chosen
    if ablate != "no_renorm":
        weight = weight / (jnp.sum(chosen, -1, keepdims=True) + route_eps)

    def one(y, ew):
        w_e, g_e, u_e, d_e = ew
        return y + w_e[:, None] * _swiglu(m, g_e, u_e, d_e), None

    y, _ = jax.lax.scan(one, jnp.zeros(m.shape, F32),
                        (weight.T, w_gate, w_up, w_down))
    return y, mask


def feed_forward(lp, m, *, top_k, scale, route_eps, ablate, given=None):
    if "wr" not in lp:
        return _by_rows(functools.partial(
            _swiglu, w_gate=lp["w_gate"], w_up=lp["w_up"],
            w_down=lp["w_down"]), m), None
    routed = functools.partial(
        _routed, wr=lp["wr"], b=lp["b"], w_gate=lp["w_gate"],
        w_up=lp["w_up"], w_down=lp["w_down"], top_k=top_k, scale=scale,
        route_eps=route_eps, ablate=_only(ablate, "bias_off", "no_renorm"))
    if given is None:
        return _by_rows(lambda m_b: routed(m_b, None), m)
    return _by_rows(routed, m, given)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, *, eps):
    return rms_norm(x, w, eps)


def layer(lp, x, *, kind, heads, head_dim, top_k, scale, route_eps, eps,
          theta, ablate, chunk_at, given=None):
    """-> (the rows after the layer, the conv layer's gated rows or
    None, the routed layer's chosen sets or None)."""
    g = None
    if kind == CONV:
        op, g = conv_mixer(lp, x, eps=eps, ablate=ablate, chunk_at=chunk_at)
    else:
        op = attention_mixer(lp, x, heads=heads, head_dim=head_dim, eps=eps,
                             theta=theta, ablate=ablate)
    h = x + op
    m = _by_rows(functools.partial(_normed, w=lp["w_post"], eps=eps), h)
    y, mask = feed_forward(lp, m, top_k=top_k, scale=scale,
                           route_eps=route_eps, ablate=ablate, given=given)
    return h + y, g, mask


def head(w_f, emb, x, eps):
    """The tied head a slice of the vocabulary at a time."""
    n = rms_norm(x, w_f, eps)
    return jnp.concatenate(
        [_matmul_jit(n, emb[v0:v0 + VOCAB_SLICE].T)
         for v0 in range(0, emb.shape[0], VOCAB_SLICE)], axis=-1)


@jax.jit
def _round_tree(tree):
    return jax.tree_util.tree_map(_round8, tree)


def forward(params, tokens, *, layer_types, num_heads, head_dim, top_k,
            scale=1.0, route_eps=1e-6, eps=1e-5, theta=1e6, ablate=None,
            rows=None, tails=False, chunk_at=None, given=None):
    """Logits of one sequence of token ids (T,): all T rows, or the rows
    ``rows`` names -> (logits (len(rows), V), the routed layers' chosen
    sets (routed layers, T, E) bool); with ``tails`` a third, each conv
    layer's gated rows ``T - (L - 1) .. T - 1`` (conv layers, L - 1,
    d).  ``given`` (routed layers, T, E) bool: the sets each routed
    layer takes in the place of its router's choice (the system's, so
    that a row reads the system's arithmetic and not a flipped
    choice)."""
    T = tokens.shape[0]
    if ablate == "fp8":
        round8, ablate = _round_tree, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    # whole blocks, so that a function compiles for one shape whatever
    # the sequence (a toy sequence of a CPU test stays as it is)
    pad = -T % ROW_BLOCK if T > ROW_BLOCK // 2 else 0
    tokens = jnp.pad(tokens, (0, pad))
    if given is not None:
        given = jnp.pad(jnp.asarray(given), ((0, 0), (0, pad), (0, 0)))
    emb = round8(params["emb"])
    x = emb[tokens].astype(F32)
    layers = list(params["layers"])
    if ablate == "dense_layers_routed":
        first = next(lp for lp in layers if "wr" in lp)
        layers = [lp if "wr" in lp else
                  {**lp, **{k: first[k] for k in
                            ("wr", "b", "w_gate", "w_up", "w_down")}}
                  for lp in layers]
    kept, masks = [], []
    for kind, lp, own in zip(layer_types, layers, params["layers"]):
        x, g, mask = layer(
            round8(lp), x, kind=kind, heads=num_heads, head_dim=head_dim,
            top_k=top_k, scale=scale, route_eps=route_eps, eps=eps,
            theta=theta, ablate=ablate, chunk_at=chunk_at,
            given=(given[len(masks)] if given is not None and "wr" in own
                   else None))
        x.block_until_ready()       # a layer's widened copies go first
        if g is not None:
            L = lp["w_conv"].shape[0]
            kept.append(g[T - (L - 1):T])
        if "wr" in own:             # the layers the system routes
            masks.append(mask[:T])
    x = x[:T] if rows is None else x[jnp.asarray(rows)]
    out = (head(params["w_f"], emb, x, eps),
           jnp.stack(masks) if masks else None)
    return out + (jnp.stack(kept),) if tails else out


def rel_rms(a, b):
    """RMS of ``a - b`` over the RMS of ``b``."""
    a, b = jnp.asarray(a, F32), jnp.asarray(b, F32)
    return float(jnp.sqrt(jnp.mean(jnp.square(a - b)))
                 / jnp.sqrt(jnp.mean(jnp.square(b))))
