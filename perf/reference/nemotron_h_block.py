"""Plain float32 reference of the Nemotron-H decoder (nvidia/NVIDIA-
Nemotron-3-Nano-30B-A3B-BF16, ``model_type`` nemotron_h): ONE chip's
share of an expert-parallel deployment (a contiguous range ``held`` of
the published routed experts, a slice of the vocabulary), every layer.

Straightforward ``jax.numpy``: no kernel, no cache, no pages, no state
pool, no batching, no chunking of the recurrence (the state-space rule
is a ``lax.scan`` over the rows, one row a step, as the equations are
written); every matmul under ``jax.default_matmul_precision("highest")``.
Written from the published description, not from the block under test:

    d 2,688; eps 1e-5 (layer_norm_epsilon);  x_0 = E[token]
    52 layers, hybrid_override_pattern MEMEM*EMEMEM*E...: EVERY layer is
       x <- x + part(RMSNorm(x; w_in)),  the part by the layer's letter
    after the last layer: logits = W_head RMSNorm(x; w_f)  (untied head)

    M, Mamba-2: 64 heads of 64 channels, state 128, 8 groups (n_groups):
    1. [z (4,096); xBC (4,096 + 2 x 8 x 128 = 6,144); dt (64)] = W_in u
    2. xBC_t <- silu(sum_{j=0..3} w_j xBC_{t-3+j} + b)  (depthwise,
       causal, zeros before row 0; use_conv_bias true);
       x_t (64 x 64), B_t (8 x 128), C_t (8 x 128) = split(xBC_t)
    3. dt_t = softplus(dt_t + dt_bias); a_t = exp(-exp(A_log) dt_t)
       (one of each a head; time_step_limit unbounded)
    4. head h reads group g = h // 8:
       S_t = a_t S_{t-1} + dt_t x_t B_{g,t}^T, S_{-1} = 0 (64 x 128 a
       head);  y_t = S_t C_{g,t} + D x_t
    5. out = W_out (RMSNorm_group(y_t * silu(z_t)) * w_norm): the gate
       first, then the norm over each group's 4,096 / 8 = 512 channels
       on its own

    *, attention (u the normed rows): q 32 heads, k and v 2 heads of
       128 (query head i reads K/V head i // 16), no bias, no q/k norm,
       NO rotation (rope_theta and partial_rotary_factor stand in the
       config and no layer reads them); causal softmax of q.k x 128^-1/2;
       W_o

    E, experts (m the normed rows):
       s = sigmoid(W_r m) over the published 128 experts, float32
       chosen = the 6 largest of s + b  (b: the selection bias, used to
          choose only; n_group 1, topk_group 1: no group step; of equal
          values the lower index first)
       w_e = 2.5 s_e / (sum of the 6 chosen s + 1e-20)
          (norm_topk_prob, routed_scaling_factor)
       F(m; W_up, W_down) = W_down relu(W_up m)^2   (mlp_hidden_act relu2:
          TWO matrices an expert, no gate)
       y = F(m; shared, 3,712 wide) + sum over chosen e of w_e F(m; e,
          1,856 wide); the sum runs over the experts ``held`` alone (an
          expert-parallel chip's share: what the other chips' experts
          would add is not computed)

Departures from the published description, each noted where it is made:
random weights, and the held range of experts and of ids.  The experts'
matrices arrive as the system stores them, ``W_up`` with zero columns
and ``W_down`` with zero rows behind the published 1,856: ``relu(0)^2``
times a zero row adds exact zeros, so they are taken as they come.

It takes the system's parameter pytree (``paddle_tpu/models/
nemotron_h.py``: ``emb w_f lm_head``, ``layers`` of ``w_in`` and one of
``w_zxbcdt w_conv b_conv dt_bias A_log D w_norm w_out`` / ``wq wk wv
wo`` / ``wr b w_up w_down ws_up ws_down``) in whatever dtype it is
served in and widens a piece at a time to float32: one matrix, a group
of experts, a block of query rows, so that 52 layers' float32 pieces
fit beside the served model on one chip.

``forward(..., states=True)`` also hands back each Mamba-2 layer's
state after the last row; ``masks=True`` the (T, E) chosen mask of each
routed layer.

``ablate`` changes one piece: "one_group" (every head on group 0's B
and C), "whole_norm" (the norm over all 4,096 channels, not 8 x 512),
"norm_before_gate" (RMSNorm_group(y) * silu(z)), "no_decay" (a 1),
"no_dt_on_input" (the write is ``x B^T``), "no_conv" (the conv and its
bias replaced by the identity; the SiLU stays), "no_conv_bias",
"no_skip_D", "relu" (rectified, not squared), "silu" (in relu^2's
place), "no_shared", "no_scale" (2.5 -> 1), "no_renorm" (w = 2.5 s),
"sigmoid" (softmax scores in its place), "bias_in_weights" (weighs by
s + b), "gqa" (query head i on K/V head i % 2), "rope_on_attention"
(rotate-half RoPE at the config's ``rope_theta`` 10,000 on q and k),
"post_norm" (x + RMSNorm(part(x))), "shared_norm" (an ``E`` layer fed
the normed input of the mixer before it: the pairing every other model
has, one norm for a mixer and its feed-forward), "state_bf16" (the
state rounded to bfloat16 after every row), "fp8" (every weight rounded
to float8_e4m3fn first: the nearest precision below the bfloat16 the
configuration serves in).
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
EXPERT_GROUP = 4
ROW_BLOCK = 1024
ROPE_THETA = 1e4
ABLATIONS = ("one_group", "whole_norm", "norm_before_gate", "no_decay",
             "no_dt_on_input", "no_conv", "no_conv_bias", "no_skip_D",
             "relu", "silu", "no_shared", "no_scale", "no_renorm", "sigmoid",
             "bias_in_weights", "gqa", "rope_on_attention", "post_norm",
             "shared_norm", "state_bf16", "fp8")


def _only(ablate, *mine):
    """``ablate`` where it is one of ``mine``, else None: a piece is
    compiled for the ablations that change it, not once for each."""
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


def top_k_mask(p, k):
    """(T, E) bool: the k largest of each row; of equal values the
    lower index ranks first."""
    e = jnp.arange(p.shape[-1])
    ahead = ((p[:, None, :] > p[:, :, None])
             | ((p[:, None, :] == p[:, :, None])
                & (e[None, None, :] < e[None, :, None])))
    return jnp.sum(ahead, axis=-1) < k


@jax.jit
def _matmul(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


# -- M: Mamba-2 in groups ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "groups", "ablate"))
def _conv_and_split(zxbcdt, w_conv, b_conv, dt_bias, A_log, *, heads,
                    d_head, d_state, groups, ablate):
    """Steps 2 and 3 on the projection's rows -> z (T, inner), x (T, H,
    P), B, C (T, G, N), a, dt (T, H)."""
    T, inner, BC = zxbcdt.shape[0], heads * d_head, groups * d_state
    z = zxbcdt[:, :inner]
    xBC = zxbcdt[:, inner:2 * inner + 2 * BC]
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * BC:]
                         + dt_bias.astype(F32))
    if ablate != "no_conv":
        w = w_conv.astype(F32)                             # (4, channels)
        taps = w.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((taps - 1, xBC.shape[1]), F32), xBC])
        xBC = sum(w[j] * padded[j:j + T] for j in range(taps))
        if ablate != "no_conv_bias":
            xBC = xBC + b_conv.astype(F32)
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :inner].reshape(T, heads, d_head)
    B = xBC[:, inner:inner + BC].reshape(T, groups, d_state)
    C = xBC[:, inner + BC:].reshape(T, groups, d_state)
    a = jnp.exp(-jnp.exp(A_log.astype(F32)) * dt)
    if ablate == "no_decay":
        a = jnp.ones_like(a)
    return z, x, B, C, a, dt


@functools.partial(jax.jit, static_argnames=("ablate",))
def _recurrence(x, B, C, a, dt, *, ablate):
    """Step 4 less the skip, row by row: x (T, H, P), B, C (T, G, N), a,
    dt (T, H) -> (y (T, H, P), the state after the last row (H, P,
    N))."""
    (_, H, P), (_, G, N) = x.shape, B.shape
    # head h reads group h // (H / G); "one_group": every head group 0
    group = (jnp.zeros((H,), jnp.int32) if ablate == "one_group"
             else jnp.arange(H) // (H // G))
    with jax.default_matmul_precision("highest"):
        def row(S, r):
            x_t, B_t, C_t, a_t, dt_t = r
            write = x_t if ablate == "no_dt_on_input" \
                else dt_t[:, None] * x_t
            S = a_t[:, None, None] * S \
                + write[:, :, None] * B_t[group][:, None, :]
            if ablate == "state_bf16":
                # the barrier keeps the compiler from dropping the round
                # trip as excess precision it is allowed to keep
                S = jax.lax.optimization_barrier(
                    S.astype(jnp.bfloat16)).astype(F32)
            return S, jnp.einsum("hpn,hn->hp", S, C_t[group])

        S, y = jax.lax.scan(row, jnp.zeros((H, P, N), F32),
                            (x, B, C, a, dt))
        return y, S


@functools.partial(jax.jit, static_argnames=("eps", "groups", "ablate"))
def _skip_gate_norm(y, x, z, D, w_norm, *, eps, groups, ablate):
    """The skip of step 4 and step 5 less its projection."""
    if ablate != "no_skip_D":
        y = y + D.astype(F32)[:, None] * x
    y = y.reshape(z.shape)
    G = 1 if ablate == "whole_norm" else groups

    def norm(v):            # over each group's channels on its own
        T, inner = v.shape
        return rms_norm(v.reshape(T, G, inner // G),
                        w_norm.reshape(G, inner // G), eps).reshape(T, inner)

    if ablate == "norm_before_gate":
        return norm(y) * jax.nn.silu(z)
    return norm(y * jax.nn.silu(z))


def mamba_part(lp, u, *, heads, d_head, d_state, groups, eps, ablate):
    z, x, B, C, a, dt = _conv_and_split(
        _matmul(u, lp["w_zxbcdt"]), lp["w_conv"], lp["b_conv"],
        lp["dt_bias"], lp["A_log"], heads=heads, d_head=d_head,
        d_state=d_state, groups=groups,
        ablate=_only(ablate, "no_conv", "no_conv_bias", "no_decay"))
    y, S = _recurrence(x, B, C, a, dt, ablate=_only(
        ablate, "no_dt_on_input", "state_bf16", "one_group"))
    y = _skip_gate_norm(y, x, z, lp["D"], lp["w_norm"], eps=eps,
                        groups=groups,
                        ablate=_only(ablate, "no_skip_D", "whole_norm",
                                     "norm_before_gate"))
    return _matmul(y, lp["w_out"]), S


# -- *: attention ------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("start", "group", "modulo"))
def _attend_block(q, k, v, *, start, group, modulo):
    """A block of query rows ``start ..`` against keys ``0 ..`` of the
    same sequence: q (R, H, dh), k, v (T, KV, dh) -> (R, H, dh); causal
    softmax of q.k x dh^-1/2, query head i on K/V head i // group
    (``modulo``: i % KV, the wrong one), a head at a time."""
    with jax.default_matmul_precision("highest"):
        R, H, dh = q.shape
        T, KV = k.shape[0], k.shape[1]
        seen = (start + jnp.arange(R))[:, None] >= jnp.arange(T)[None, :]

        def one(i):
            j = i % KV if modulo else i // group
            s = (q[:, i] @ k[:, j].T) * dh ** -0.5
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf),
                                  axis=-1) @ v[:, j]

        return jnp.moveaxis(jax.lax.map(one, jnp.arange(H)), 0, 1)


def attention_part(lp, u, *, heads, head_dim, ablate):
    T = u.shape[0]
    kv_heads = lp["wk"].shape[1] // head_dim
    q = _matmul(u, lp["wq"]).reshape(T, heads, head_dim)
    k = _matmul(u, lp["wk"]).reshape(T, kv_heads, head_dim)
    v = _matmul(u, lp["wv"]).reshape(T, kv_heads, head_dim)
    if ablate == "rope_on_attention":
        q, k = rope(q, ROPE_THETA), rope(k, ROPE_THETA)
    a = jnp.concatenate([
        _attend_block(q[r0:r0 + ROW_BLOCK], k[:r0 + ROW_BLOCK],
                      v[:r0 + ROW_BLOCK], start=r0,
                      group=heads // kv_heads, modulo=ablate == "gqa")
        for r0 in range(0, T, ROW_BLOCK)])
    return _matmul(a.reshape(T, heads * head_dim), lp["wo"]), None


# -- E: experts --------------------------------------------------------------


def _act(u, ablate):
    if ablate == "relu":
        return jax.nn.relu(u)
    if ablate == "silu":
        return jax.nn.silu(u)
    return jnp.square(jax.nn.relu(u))


@functools.partial(jax.jit, static_argnames=("ablate",))
def _expert(m, w_up, w_down, *, ablate):
    """F(m; W_up, W_down) of ONE expert (the shared one)."""
    with jax.default_matmul_precision("highest"):
        return _act(m @ w_up.astype(F32), ablate) @ w_down.astype(F32)


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
            chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                               + 1e-20)
        return chosen * (1.0 if ablate == "no_scale" else scale), mask


@functools.partial(jax.jit, static_argnames=("ablate",))
def _expert_group(m, weight, w_up, w_down, *, ablate):
    """sum over the experts of one group of weight * F(m; e): every
    expert of the group on every row."""
    with jax.default_matmul_precision("highest"):
        h = _act(jnp.einsum("td,edf->tef", m, w_up.astype(F32)), ablate)
        out = jnp.einsum("tef,efd->ted", h, w_down.astype(F32))
        return jnp.einsum("te,ted->td", weight, out)


def held_experts(m, weight, held, w_up, w_down, ablate=None):
    """The held experts' part of the routed sum: ``weight`` (T, E) over
    all published experts, the stacked matrices those of experts
    ``held[0] .. held[0] + held[1] - 1``."""
    first, count = held
    weight = weight[:, first:first + count]
    y = jnp.zeros_like(m)
    for e0 in range(0, count, EXPERT_GROUP):
        sl = slice(e0, e0 + EXPERT_GROUP)
        y = y + _expert_group(m, weight[:, sl], w_up[sl], w_down[sl],
                              ablate=ablate)
    return y


def experts_part(lp, m, *, top_k, scale, held, ablate):
    """-> (what is added to the residual, the (T, E) chosen mask), a
    block of ``ROW_BLOCK`` rows at a time (rows are independent)."""
    act = _only(ablate, "relu", "silu")
    ys, masks = [], []
    for r0 in range(0, m.shape[0], ROW_BLOCK):
        rows = m[r0:r0 + ROW_BLOCK]
        weight, mask = _router(
            lp["wr"], lp["b"], rows, top_k=top_k, scale=scale,
            ablate=_only(ablate, "sigmoid", "bias_in_weights", "no_renorm",
                         "no_scale"))
        y = held_experts(rows, weight, held, lp["w_up"], lp["w_down"], act)
        if ablate != "no_shared":
            y = y + _expert(rows, lp["ws_up"], lp["ws_down"], ablate=act)
        ys.append(y)
        masks.append(mask)
    return jnp.concatenate(ys), jnp.concatenate(masks)


# -- the decoder -------------------------------------------------------------


def layer(lp, x, fed, *, kind, heads, head_dim, mamba_n_heads, mamba_d_head,
          mamba_d_state, mamba_n_groups, top_k, scale, held, eps, ablate):
    """One layer over all rows -> (the rows after it, what its part was
    fed, a Mamba-2 layer's final state or None, an ``E`` layer's chosen
    mask or None).  ``fed``: what the layer before was fed (the
    "shared_norm" ablation hands it to an ``E`` layer)."""

    def part(u):
        if kind == MAMBA:
            out, S = mamba_part(
                lp, u, heads=mamba_n_heads, d_head=mamba_d_head,
                d_state=mamba_d_state, groups=mamba_n_groups, eps=eps,
                ablate=ablate)
            return out, S, None
        if kind == ATTENTION:
            out, _ = attention_part(lp, u, heads=heads, head_dim=head_dim,
                                    ablate=ablate)
            return out, None, None
        out, mask = experts_part(lp, u, top_k=top_k, scale=scale, held=held,
                                 ablate=ablate)
        return out, None, mask

    if ablate == "post_norm":
        out, S, mask = part(x)
        return x + rms_norm(out, lp["w_in"], eps), x, S, mask
    u = rms_norm(x, lp["w_in"], eps)
    if ablate == "shared_norm" and kind == EXPERTS and fed is not None:
        u = fed
    out, S, mask = part(u)
    return x + out, u, S, mask


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(w_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w_f, eps) @ lm_head.astype(F32)


def forward(params, tokens, *, layer_types, num_heads, head_dim,
            mamba_n_heads, mamba_d_head, mamba_d_state, mamba_n_groups,
            top_k, scale, held, eps=1e-5, ablate=None, rows=None,
            states=False, masks=False):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> logits (len(rows), V); with ``states`` also
    each Mamba-2 layer's state after the last row (mamba layers, H, P,
    N); with ``masks`` also each routed layer's (T, E) chosen mask."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    kept, chosen, fed = [], [], None
    for kind, lp in zip(layer_types, params["layers"]):
        x, fed, S, mask = layer(
            round8(lp), x, fed, kind=kind, heads=num_heads,
            head_dim=head_dim, mamba_n_heads=mamba_n_heads,
            mamba_d_head=mamba_d_head, mamba_d_state=mamba_d_state,
            mamba_n_groups=mamba_n_groups, top_k=top_k, scale=scale,
            held=tuple(held), eps=eps, ablate=ablate)
        # a layer's widened (or rounded) copies go before the next's are
        # made: the loop runs 52 layers ahead of the device
        x.block_until_ready()
        if S is not None:
            kept.append(S)
        if mask is not None:
            chosen.append(mask)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    out = (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),)
    if states:
        out += (jnp.stack(kept),)
    if masks:
        out += (jnp.stack(chosen),)
    return out[0] if len(out) == 1 else out


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
