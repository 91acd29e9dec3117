"""Plain float32 reference of the Kanana-2-30B-A3B decoder
(kakaocorp/kanana-2-30b-a3b-instruct-2601, ``model_type`` deepseek_v3),
as one chip of an expert-parallel group holds it, or (``held`` = all)
the whole layer.

Straightforward ``jax.numpy``: no kernel, no cache, no absorption, no
batching, no sorting of rows by expert; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, the EXPANDED form, not from the block under test:

    d 2048, H 32, nope 128, rope 64, v 128, rank 512, eps 1e-6,
    theta 1e6;  F(x; Wg, Wu, Wd) = Wd (silu(Wg x) * Wu x);  x_0 = E[token]
    1. h = RMSNorm(x; g1);  q = Wq h -> H x [q^n (nope) ; q^r (rope)]
    2. [c ; k^r] = Wkva h (rank + rope);  c <- RMSNorm(c; gc)
    3. q^r and k^r rotated at the row's absolute position t, channel 2i
       paired with 2i + 1 (interleaved) at t * theta^(-2i / rope);
       k^r is ONE row, shared by all heads
    4. [k^n_h ; v_h] = Wkvb c -> H x (nope + v)
    5. scores (q^n_h.k^n_h + q^r_h.k^r) / sqrt(nope + rope), causal,
       softmax in f32;  o_h = sum p v_h;  x <- x + Wo concat_h(o_h)
    6. m = RMSNorm(x; g2).  Layer 0: x <- x + F(m; dense).  Later
       layers: s = sigmoid(Wr m) over ALL published experts; the top-k
       of s + b (a tie to the lower index); w_e = scale * s_e / sum
       over the k chosen of s;  x <- x + F(m; shared) + sum over e
       chosen AND held of w_e F(m; e)
    7. after the last layer RMSNorm(x; gf), logits = H x over the held
       rows of the untied head

Step 6 is the DeepSeek-V3 router K-EXAONE shares: its reference
(``exaone_moe_block.py``, a sibling of this file, no part of the
program) is called for it, a few held experts at a time.

It takes the system's parameter pytree (``paddle_tpu/models/
kanana_mla.py``: ``emb``, ``lm_head``, ``w_f``, ``layers`` of ``w_in
w_post w_cn wq w_kva wo``, ``Wkvb`` as its two per-head halves ``w_uk``
(H, nope, rank) and ``w_uv`` (H, rank, v), which step 4 puts back
together, and the feed-forward's names as K-EXAONE's) in whatever dtype
it is served in and widens a piece at a time to float32; the heads one
at a time (all heads' scores at once would be H T^2 numbers).

``ablate`` changes one piece: "no_kv_norm" (step 2's norm dropped),
"rope_rotate_half" (channel i paired with i + rope / 2),
"rope_on_nope" (q^n and k^n rotated too), "scale_rsqrt128" (nope^-1/2
for (nope + rope)^-1/2), "k_rope_per_head" (head h reads the shared row
rolled by 2h channels: a row a head), "softmax_router", "no_renorm",
"scale_1" (for the published scale), "top_k5" (one expert fewer),
"shared_off", "dense_layer0_off" (layer 0's feed-forward dropped);
"fp8" (every weight rounded to float8_e4m3fn first) and "latent_fp8"
(the rows a page would hold, ``[c ; k^r]`` after the norm and the
rotation, rounded to float8_e4m3fn): the nearest precision below the
bfloat16 the configuration states for its weights and for its latent
rows.
"""

import functools

import jax
import jax.numpy as jnp

from perf.reference import exaone_moe_block as moe_ref
from perf.reference.exaone_moe_block import (F32, _head, _round_fp8,  # noqa: F401
                                             rel_rms, rms_norm)

ABLATIONS = ("no_kv_norm", "rope_rotate_half", "rope_on_nope",
             "scale_rsqrt128", "k_rope_per_head", "softmax_router",
             "no_renorm", "scale_1", "top_k5", "shared_off",
             "dense_layer0_off")
PRECISIONS = ("fp8", "latent_fp8")
# this file's names for the router's ablations, in the router's own
ROUTER_ABLATION = {"softmax_router": "sigmoid", "no_renorm": "no_renorm",
                   "scale_1": "no_scale", "shared_off": "shared"}


def rope(x, theta, rotate_half=False):
    """x (T, n, dr) at positions 0..T-1: channel 2i pairs with 2i + 1
    (or, ``rotate_half``, channel i with i + dr / 2)."""
    T, _, dr = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if rotate_half:
        a, b = x[..., :dr // 2], x[..., dr // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=(
    "num_heads", "nope", "rope_dim", "eps", "theta", "ablate"))
def _project(w, x, *, num_heads, nope, rope_dim, eps, theta, ablate):
    """Steps 1-4 -> (q (T, H, nope + rope), k (T, H, nope + rope), v
    (T, H, v))."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        T, H = x.shape[0], num_heads
        rank = w["w_cn"].shape[0]
        half = ablate == "rope_rotate_half"
        h = rms_norm(x, w["w_in"], eps)
        q = (h @ w["wq"]).reshape(T, H, nope + rope_dim)
        qn, qr = q[..., :nope], rope(q[..., nope:], theta, half)
        kva = h @ w["w_kva"]
        c = kva[:, :rank]
        if ablate != "no_kv_norm":
            c = rms_norm(c, w["w_cn"], eps)
        kr = rope(kva[:, None, rank:], theta, half)            # (T, 1, r)
        if ablate == "latent_fp8":
            c, kr = _fp8(c), _fp8(kr)
        # W_kvb as published: (rank, H x (nope + v)), head h's columns
        # [k^n ; v]
        w_kvb = jnp.concatenate(
            [jnp.swapaxes(w["w_uk"], 1, 2), w["w_uv"]], axis=-1)  # (H, c, .)
        kv = jnp.einsum("tc,hcn->thn", c, w_kvb)
        kn, v = kv[..., :nope], kv[..., nope:]
        if ablate == "rope_on_nope":
            qn, kn = rope(qn, theta), rope(kn, theta)
        kr = jnp.broadcast_to(kr, (T, H, rope_dim))
        if ablate == "k_rope_per_head":
            kr = jnp.stack([jnp.roll(kr[:, i], 2 * i, axis=-1)
                            for i in range(H)], axis=1)
        return (jnp.concatenate([qn, qr], -1),
                jnp.concatenate([kn, kr], -1), v)


def _fp8(a):
    return jax.lax.optimization_barrier(
        a.astype(jnp.float8_e4m3fn)).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def _attention(q, k, v, *, scale):
    """Step 5's heads, one at a time: (T, H, .) -> (T, H * v)."""
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
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], -1)


def layer(lp, x, *, first, num_heads, nope, rope_dim, top_k, scale, held,
          eps, theta, ablate):
    names = ("w_in", "w_cn", "wq", "w_kva", "w_uk", "w_uv")
    q, k, v = _project({n: lp[n] for n in names}, x, num_heads=num_heads,
                       nope=nope, rope_dim=rope_dim, eps=eps, theta=theta,
                       ablate=ablate)
    width = nope if ablate == "scale_rsqrt128" else nope + rope_dim
    a = _attention(q, k, v, scale=float(width) ** -0.5)
    h, m = moe_ref._project_and_norm(lp["wo"], lp["w_post"], x, a, eps=eps)
    if first and ablate == "dense_layer0_off":
        return h, None
    y, mask = moe_ref.feed_forward(
        lp, m, top_k=top_k - (ablate == "top_k5"), scale=scale, held=held,
        ablate=ROUTER_ABLATION.get(ablate))
    return h + y, mask


def forward(params, tokens, *, num_heads, nope, rope_dim, top_k, scale,
            held, eps=1e-6, theta=1e6, ablate=None, rows=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), masks: a (T, E)
    chosen mask per routed layer, stacked)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    masks = []
    for i, lp in enumerate(params["layers"]):
        x, mask = layer(round8(lp), x, first=i == 0, num_heads=num_heads,
                        nope=nope, rope_dim=rope_dim, top_k=top_k,
                        scale=scale, held=tuple(held), eps=eps, theta=theta,
                        ablate=ablate)
        if mask is not None:
            masks.append(mask)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),
            jnp.stack(masks) if masks else None)
