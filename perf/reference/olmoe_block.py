"""Plain float32 reference of the OLMoE decoder (allenai/OLMoE-1B-7B,
arXiv:2409.02060; config.json of OLMoE-1B-7B-0125-Instruct).

Straightforward ``jax.numpy``: no kernel, no cache, no batching, no
sorting of rows by expert — every expert is computed densely on every
row and masked by the row's top-k set — and every matmul under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes).  It follows the published block:

    n = RMSNorm(x; w_in);  q = RMSNorm(n Wq; w_qn), k = RMSNorm(n Wk; w_kn)
    over all channels, v = n Wv;  heads split;  q, k rotated by RoPE
    (theta, rotate-half pairing, absolute position);  causal
    softmax(q k^T / sqrt(dh)) v;  h = x + a Wo
    m = RMSNorm(h; w_post);  p = softmax(m Wr) over all experts;  the
    top-k of p with the weights p as they are (norm_topk_prob false);
    y = sum_e p_e W_down,e( silu(W_gate,e m) * W_up,e m );  x' = h + y
    after the last layer RMSNorm(x; w_f) and an untied lm_head; no bias.

The q/k RMSNorm is not in ``config.json``; it is from the model's
public ``modeling_olmoe.py`` (``q_norm``/``k_norm`` over the whole
projection, before the heads are split).

It takes the system's parameter pytree (``paddle_tpu/models/olmoe.py``:
``emb``, ``lm_head``, ``w_f``, ``layers`` of ``w_in wq wk wv w_qn w_kn wo
w_post wr w_gate w_up w_down``) in whatever dtype it is served in and
widens ONE LAYER AT A TIME to float32: a whole float32 copy of the
model would not fit beside it on the chip.

Departures from the published description: none in the mathematics.
Two in how it is evaluated, neither of which changes a value: the
experts are walked in groups of ``EXPERT_GROUP``, one program a group
(memory: in one program the compiler keeps every group's float32
copies alive at once, 5.4 GB at 1,116 rows), and a tie in
the router's probabilities goes to the lower expert index (what
``torch.topk`` does on equal values is unspecified).

``ablate`` drops or changes one piece ("rope", "qk_norm", "norm_topk":
the k weights renormalised to sum to one, "top_k7": one expert fewer,
"fp8": every weight rounded to float8_e4m3fn first, the nearest
precision below the bfloat16 the configuration serves in); the driver
uses them to show that the written tolerance would catch that mistake.
"""

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_GROUP = 8
ABLATIONS = ("rope", "qk_norm", "norm_topk", "top_k7", "fp8")


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


def attention(q, k, v):
    """Causal multi-head attention of one sequence: (T, H, dh) each."""
    T, H, dh = q.shape
    s = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
    t = jnp.arange(T)
    s = jnp.where(t[:, None] >= t[None, :], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1),
                      v).reshape(T, H * dh)


def top_k_mask(p, k):
    """(T, E) bool: the k largest of each row; of equal values the
    lower index ranks first."""
    e = jnp.arange(p.shape[-1])
    ahead = ((p[:, None, :] > p[:, :, None])
             | ((p[:, None, :] == p[:, :, None])
                & (e[None, None, :] < e[None, :, None])))
    return jnp.sum(ahead, axis=-1) < k


@jax.jit
def _expert_group(m, weight, w_gate, w_up, w_down):
    """sum over the experts of one group of weight * W_down( silu(W_gate
    m) * W_up m ): every expert of the group on every row; ``weight``
    (T, G) is 0 where the expert is outside the row's top-k set."""
    with jax.default_matmul_precision("highest"):
        g = jnp.einsum("td,edf->tef", m, w_gate.astype(F32))
        u = jnp.einsum("td,edf->tef", m, w_up.astype(F32))
        out = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                         w_down.astype(F32))
        return jnp.einsum("te,ted->td", weight, out)


def experts(m, mask, p, w_gate, w_up, w_down):
    """sum over experts of mask * p * W_down( silu(W_gate m) * W_up m ),
    every expert on every row; one program a group of experts, one
    after the other (what bounds the memory)."""
    weight = jnp.where(mask, p, 0.0)
    y = jnp.zeros_like(m)
    for e0 in range(0, w_gate.shape[0], EXPERT_GROUP):
        sl = slice(e0, e0 + EXPERT_GROUP)
        y = y + _expert_group(m, weight[:, sl], w_gate[sl], w_up[sl],
                              w_down[sl])
    return y


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "top_k", "eps", "theta", "ablate"))
def _attention_and_router(w, x, *, num_heads, top_k, eps, theta, ablate):
    """A block up to its experts: rows x (T, d) float32 -> (h, the
    experts' input m, the router's probabilities p (T, E), the (T, E)
    top-k mask)."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        T, d = x.shape
        n = rms_norm(x, w["w_in"], eps)
        q, k, v = n @ w["wq"], n @ w["wk"], n @ w["wv"]
        if ablate != "qk_norm":
            q = rms_norm(q, w["w_qn"], eps)
            k = rms_norm(k, w["w_kn"], eps)
        q, k, v = (t.reshape(T, num_heads, d // num_heads)
                   for t in (q, k, v))
        if ablate != "rope":
            q, k = rope(q, theta), rope(k, theta)
        h = x + attention(q, k, v) @ w["wo"]
        m = rms_norm(h, w["w_post"], eps)
        p = jax.nn.softmax(m @ w["wr"], axis=-1)
        mask = top_k_mask(p, top_k - 1 if ablate == "top_k7" else top_k)
        if ablate == "norm_topk":
            p = p / jnp.sum(jnp.where(mask, p, 0.0), axis=-1, keepdims=True)
        return h, m, p, mask


def layer(lp, x, *, num_heads, top_k, eps, theta, ablate):
    """One block over rows x (T, d) float32 -> (x', the (T, E) top-k
    mask)."""
    h, m, p, mask = _attention_and_router(
        {k: v for k, v in lp.items()
         if k not in ("w_gate", "w_up", "w_down")},
        x, num_heads=num_heads, top_k=top_k, eps=eps, theta=theta,
        ablate=ablate)
    return h + experts(m, mask, p, lp["w_gate"], lp["w_up"],
                       lp["w_down"]), mask


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(w_f, lm_head, x, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, w_f.astype(F32), eps) @ lm_head.astype(F32)


def forward(params, tokens, *, num_heads, top_k, eps=1e-5, theta=10000.0,
            ablate=None, rows=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), masks (L, T, E): each
    layer's top-k set of each row)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    masks = []
    for lp in params["layers"]:
        x, mask = layer(round8(lp), x, num_heads=num_heads, top_k=top_k,
                        eps=eps, theta=theta, ablate=ablate)
        masks.append(mask)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),
            jnp.stack(masks))


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
