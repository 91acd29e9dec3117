"""Plain float32 reference of the GLM-5 decoder (zai-org/GLM-5,
``model_type`` glm_moe_dsa: latent attention on the rows a learned
indexer selects, the DeepSeek-V3.2 sparse-attention family), as one
chip of an expert-parallel group holds it, or (``held`` = all) the
whole layer.

Straightforward ``jax.numpy``: no kernel, no cache, no absorption, no
batching, no threshold; every matmul under
``jax.default_matmul_precision("highest")``.  Written from the
equations, the published EXPANDED form, not from the block under test:

    d 6144, H 64, q_rank 2048, rank 512, nope 192, rope 64, v 256,
    J 32 index heads of D 128 (the first R 64 channels rotated),
    k 2048 rows selected, eps 1e-5, theta 1e6;  x_0 = E[token]
    1. h = RMSNorm(x; g1);  c^q = RMSNorm(Wqa h; gq);
       q = Wqb c^q -> H x [q^n (nope) ; q^r (rope)]
    2. [c ; k^r] = Wkva h (rank + rope);  c <- RMSNorm(c; gc)
    3. q^r and k^r rotated at the row's position t, channel 2i paired
       with 2i + 1 at t * theta^(-2i / rope); k^r ONE row for all heads
    4. [k^n_h ; v_h] = Wkvb c -> H x (nope + v)
    5. the indexer: q^I = W^I_q c^q -> J x D;  k^I = LayerNorm(W^I_k h;
       gk, bk) (D);  the first R channels of every q^I_j and of k^I
       rotated as step 3 rotates (pairs (2i, 2i + 1), theta^(-2i / R));
       w = W^I_w h / sqrt(J) / sqrt(D) (J);
       I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])        s <= t
    6. S_t = the first min(k, t + 1) of the rows s <= t sorted by I[t, s]
       descending, a tie to the lower s (a stable sort of the FULL row)
    7. scores (q^n_h.k^n_h + q^r_h.k^r) / sqrt(nope + rope) over s in
       S_t, softmax in f32;  o_h = sum p v_h;  x <- x + Wo concat_h(o_h)
    8. the feed-forward and the head: K-EXAONE's router, to the letter
       (``exaone_moe_block.feed_forward``, a sibling of this file, no
       part of the program): the leading layers dense, then the sigmoid
       router over ALL published experts, the top-k of s + b, w_e =
       scale * s_e / sum of the k chosen, the shared expert unweighted,
       the sum over e chosen AND held

Computed in blocks so that a prompt of 12,000 rows fits in the 2.9 GB
the model and its pools leave of the chip: the selection ``ROW_BLOCK``
query rows at a time (the indexer's (J, block, T) products and the two
sorts of a (block, T) matrix), the heads' projections, attention and
their part of ``Wo`` ``HEAD_GROUP`` heads and ``QUERY_ROWS`` query rows
at a time (a head's (block, T) scores one at a time), the feed-forward
``FF_ROWS`` rows at a time, the last block of each padded to the
blocks' one shape; the float8 ablation rounds a weight where it is used,
not a layer whole; an ablation that changes a value and no shape is a
traced boolean of the jitted piece (one compile for all of them: the
chip compiles some fifty pieces of this file in a cold set-up).

It takes the system's parameter pytree (``paddle_tpu/models/
glm_dsa.py``: ``w_in w_qa w_qn w_qb w_kva w_cn wo``, ``Wkvb`` as its two
per-head halves ``w_uk`` (H, nope, rank) and ``w_uv`` (H, rank, v),
which step 4 puts back together, the indexer's ``wi_q wi_k wi_kn wi_kb
wi_w``, and the feed-forward's names as K-EXAONE's) in whatever dtype
it is served in and widens a piece at a time to float32.

Departures from the family's public code, the program's too: the
Hadamard rotation applied to q^I and k^I before they are quantised is
left out (orthogonal: every dot product is the same in exact
arithmetic, and nothing here is quantised); index rows in bfloat16
where the family's deployment keeps float8 (``index_fp8`` below is that
precision); the next-token-prediction module is not instantiated.

``ablate`` changes one piece: "dense_attention" (step 6 skipped: every
row s <= t), "index_topk_half" (k / 2), "index_no_relu",
"index_uniform_weights" (w = 1 / sqrt(J D) for every head),
"index_no_rope", "index_no_k_norm", "no_q_norm" (c^q unnormalised),
"v_192" (the last quarter of every head's value channels dropped: 192 of
256),
"scale_rsqrt192" (nope^-1/2 for (nope + rope)^-1/2), "top_k7" (one
expert fewer), "shared_off", "dense_layer0_off"; "fp8" (every weight
rounded to float8_e4m3fn first), "latent_fp8" (the rows a page would
hold, ``[c ; k^r]`` after the norm and the rotation) and "index_fp8"
(``k^I`` as a page would hold it): the nearest precision below the
bfloat16 the configuration states for its weights, its latent rows and
its index rows.
"""

import functools

import jax
import jax.numpy as jnp

from perf.reference import exaone_moe_block as moe_ref
from perf.reference.exaone_moe_block import (F32, _head, _round_fp8,  # noqa: F401
                                             rel_rms, rms_norm)
from perf.reference.kanana_mla_block import _fp8

ABLATIONS = ("dense_attention", "index_topk_half", "index_no_relu",
             "index_uniform_weights", "index_no_rope", "index_no_k_norm",
             "no_q_norm", "v_192", "scale_rsqrt192", "top_k7", "shared_off",
             "dense_layer0_off")
PRECISIONS = ("fp8", "latent_fp8", "index_fp8")
ROUTER_ABLATION = {"shared_off": "shared"}
# the ablations that change ``S_t`` (what ``correct`` judges on the sets)
INDEX_ABLATIONS = ("dense_attention", "index_topk_half", "index_no_relu",
                   "index_uniform_weights", "index_no_rope",
                   "index_no_k_norm", "no_q_norm")
# those the jitted pieces take as traced booleans
TRACED_ABLATIONS = ("no_q_norm", "latent_fp8", "index_no_k_norm",
                    "index_no_rope", "index_fp8", "index_uniform_weights",
                    "v_192")
ROW_BLOCK = 256
HEAD_GROUP = 8
QUERY_ROWS = 4096
FF_ROWS = 2048


def rope_at(x, first, theta):
    """x (B, n, dr), the rows at positions ``first + 0..B-1``: channel
    2i pairs with 2i + 1 at position * theta^(-2i / dr)."""
    dr = x.shape[-1]
    pos = first + jnp.arange(x.shape[0], dtype=F32)
    inv_freq = 1.0 / theta ** (jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = pos[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def rope(x, theta):
    """``rope_at`` of a sequence's rows from its first."""
    return rope_at(x, 0.0, theta)


def _flags(ablate):
    """The ablations the jitted pieces take as traced booleans (one
    compiled program whatever is ablated)."""
    return {name: jnp.asarray(ablate == name) for name in TRACED_ABLATIONS}


@functools.partial(jax.jit, static_argnames=(
    "index_heads", "index_rope", "eps", "theta"))
def _shared(w, x, flags, *, index_heads, index_rope, eps, theta):
    """Steps 1-2 and 5's projections, what all heads share -> (c^q (T,
    q_rank), c (T, rank), k^r (T, rope) rotated, q^I (T, J, D), k^I (T,
    D), w (T, J))."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        T, J, R = x.shape[0], index_heads, index_rope
        rank = w["w_cn"].shape[0]
        h = rms_norm(x, w["w_in"], eps)
        cq = h @ w["w_qa"]
        cq = jnp.where(flags["no_q_norm"], cq, rms_norm(cq, w["w_qn"], eps))
        kva = h @ w["w_kva"]
        c = rms_norm(kva[:, :rank], w["w_cn"], eps)
        kr = rope(kva[:, None, rank:], theta)[:, 0]            # (T, r)
        c = jnp.where(flags["latent_fp8"], _fp8(c), c)
        kr = jnp.where(flags["latent_fp8"], _fp8(kr), kr)
        # the indexer
        qi = (cq @ w["wi_q"]).reshape(T, J, -1)
        ki = h @ w["wi_k"]
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = jnp.where(flags["index_no_k_norm"], ki,
                       (ki - mean) * jax.lax.rsqrt(var + eps) * w["wi_kn"]
                       + w["wi_kb"])
        qi = jnp.where(flags["index_no_rope"], qi, jnp.concatenate(
            [rope(qi[..., :R], theta), qi[..., R:]], -1))
        ki = jnp.where(flags["index_no_rope"], ki, jnp.concatenate(
            [rope(ki[:, None, :R], theta)[:, 0], ki[:, R:]], -1))
        ki = jnp.where(flags["index_fp8"], _fp8(ki), ki)
        scale = float(J) ** -0.5 * float(qi.shape[-1]) ** -0.5
        wi = jnp.where(flags["index_uniform_weights"], scale,
                       (h @ w["wi_w"]) * scale)
        return cq, c, kr, qi, ki, wi


@jax.jit
def _selected(qi, ki, wi, k, relu):
    """Steps 5-6: (T, T) bool, row t's ``S_t`` (its first ``k`` rows by
    score): the FULL index matrix a block of query rows at a time, each
    row sorted whole."""
    T = qi.shape[0]
    blocks = -(-T // ROW_BLOCK)
    pad = blocks * ROW_BLOCK - T
    qi = jnp.pad(qi, ((0, pad), (0, 0), (0, 0)))
    wi = jnp.pad(wi, ((0, pad), (0, 0)))
    s_pos = jnp.arange(T)

    def block(args):
        qb, wb, first = args
        with jax.default_matmul_precision("highest"):
            dots = jnp.einsum("tjd,sd->jts", qb, ki)
            dots = jnp.where(relu, jnp.maximum(dots, 0.0), dots)
            scores = jnp.einsum("jts,tj->ts", dots, wb)
        seen = s_pos[None, :] <= (first + jnp.arange(ROW_BLOCK))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        order = jnp.argsort(-scores, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        return (rank < k) & seen

    out = jax.lax.map(block, (
        qi.reshape(blocks, ROW_BLOCK, *qi.shape[1:]),
        wi.reshape(blocks, ROW_BLOCK, -1),
        jnp.arange(blocks) * ROW_BLOCK))
    return out.reshape(blocks * ROW_BLOCK, T)[:T]


@functools.partial(jax.jit, static_argnames=("nope", "theta"))
def _heads(w_qb, w_uk, w_uv, wo, cq, first, c, kr, mask, scale, v_192, *,
           nope, theta):
    """Steps 1 (q), 3, 4 and 7 for a GROUP of heads and a block of query
    rows (``cq`` (B, q_rank) at positions ``first + 0..B-1``, ``mask``
    (B, T)) over all T key rows, and their part of the output projection
    -> (B, d).  ``w_qb`` (q_rank, G, nope + rope), ``w_uk`` (G, nope,
    rank), ``w_uv`` (G, rank, v), ``wo`` (G, v, d)."""
    with jax.default_matmul_precision("highest"):
        w_qb, w_uk, w_uv, wo = (a.astype(F32) for a in (w_qb, w_uk, w_uv,
                                                        wo))
        q = jnp.einsum("tc,cgn->tgn", cq, w_qb)
        qn, qr = q[..., :nope], rope_at(q[..., nope:], first, theta)
        # W_kvb as published: head h's columns [k^n ; v]
        w_kvb = jnp.concatenate([jnp.swapaxes(w_uk, 1, 2), w_uv], axis=-1)
        kv = jnp.einsum("tc,gcn->tgn", c, w_kvb)
        kn, v = kv[..., :nope], kv[..., nope:]
        kept = jnp.arange(v.shape[-1]) < v.shape[-1] * 3 // 4
        v = jnp.where(v_192 & ~kept, 0.0, v)

        def one_head(args):
            qn_h, qr_h, kn_h, v_h = args
            s = (qn_h @ kn_h.T + qr_h @ kr.T) * scale
            p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            return p @ v_h

        o = jax.lax.map(one_head, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (qn, qr, kn, v)))
        return jnp.einsum("gtv,gvd->td", o, wo)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, w_post, *, eps):
    return rms_norm(h, w_post.astype(F32), eps)


def _in_blocks(rows, a):
    """``a`` padded on its first axis to whole blocks of ``rows`` rows,
    as a list of blocks (one compiled shape whatever the length)."""
    a = jnp.pad(a, ((0, -a.shape[0] % rows),) + ((0, 0),) * (a.ndim - 1))
    return [a[r:r + rows] for r in range(0, a.shape[0], rows)]


def layer(lp, x, *, first, num_heads, nope, rope_dim, index_heads,
          index_rope, index_topk, top_k, scale, held, eps, theta, ablate,
          round8=lambda tree: tree, given=None):
    """-> (the layer's output, the router's (T, E) chosen mask or None,
    the (T, T) selected mask).  ``round8``: applied to every weight
    where it is used (the float8 ablation), a piece at a time.
    ``given``: a (T, T) mask to attend under in the place of step 6's
    (the indexer still runs)."""
    names = ("w_in", "w_qa", "w_qn", "w_kva", "w_cn", "wi_q", "wi_k",
             "wi_kn", "wi_kb", "wi_w")
    flags = _flags(ablate)
    cq, c, kr, qi, ki, wi = _shared(
        round8({n: lp[n] for n in names}), x, flags, index_heads=index_heads,
        index_rope=index_rope, eps=eps, theta=theta)
    T, H = x.shape[0], num_heads
    if given is not None:
        chosen = given
    elif ablate == "dense_attention":
        chosen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    else:
        chosen = _selected(
            qi, ki, wi, jnp.int32(index_topk // 2 if ablate
                                  == "index_topk_half" else index_topk),
            jnp.asarray(ablate != "index_no_relu"))
    del qi, ki, wi
    width = nope if ablate == "scale_rsqrt192" else nope + rope_dim
    w_qb = lp["w_qb"].reshape(-1, H, nope + rope_dim)
    wo = lp["wo"].reshape(H, -1, x.shape[1])
    # the padding's rows see no key: their softmax is not a number, and
    # they are cut off before the sum
    blocks = list(zip(_in_blocks(QUERY_ROWS, cq),
                      _in_blocks(QUERY_ROWS, chosen)))
    h = x
    for g in range(0, H, HEAD_GROUP):
        sl = slice(g, g + HEAD_GROUP)
        group = round8((w_qb[:, sl], lp["w_uk"][sl], lp["w_uv"][sl], wo[sl]))
        h = h + jnp.concatenate([
            _heads(*group, cq_b, jnp.float32(i * QUERY_ROWS), c, kr, mask_b,
                   jnp.float32(float(width) ** -0.5), flags["v_192"],
                   nope=nope, theta=theta)
            for i, (cq_b, mask_b) in enumerate(blocks)])[:T]
    if first and ablate == "dense_layer0_off":
        return h, None, chosen
    m = _norm(h, lp["w_post"], eps=eps)
    ff = round8({n: lp[n] for n in lp if n in (
        "w_gate", "w_up", "w_down", "wr", "b", "ws_gate", "ws_up",
        "ws_down")})
    ys, masks = [], []
    for m_b in _in_blocks(FF_ROWS, m):      # the feed-forward is by row
        y, mask = moe_ref.feed_forward(
            ff, m_b, top_k=top_k - (ablate == "top_k7"), scale=scale,
            held=held, ablate=ROUTER_ABLATION.get(ablate))
        ys.append(y)
        masks.append(mask)
    return (h + jnp.concatenate(ys)[:T],
            None if masks[0] is None else jnp.concatenate(masks)[:T], chosen)


def forward(params, tokens, *, num_heads, nope, rope_dim, index_heads,
            index_rope, index_topk, top_k, scale, held, eps=1e-5, theta=1e6,
            ablate=None, rows=None, sets=False, given=None):
    """Logits of one sequence of token ids (T,): all T rows, or the
    rows ``rows`` names -> (logits (len(rows), V), the routed layers'
    (T, E) chosen masks stacked, and with ``sets`` every layer's (T, T)
    selected mask stacked, else None).  ``given`` (layers, T, T) bool:
    every layer attends under ITS mask in the place of the one it would
    select (the comparison that leaves the selection out: a set chosen
    at an edge bfloat16 cannot resolve is held to the reference's as a
    SET, and the logits are held given the set)."""
    if ablate == "fp8":
        round8, ablate = _round_fp8, None
    else:
        round8 = lambda tree: tree  # noqa: E731
    x = round8(params["emb"][tokens]).astype(F32)
    masks, chosen = [], []
    for i, lp in enumerate(params["layers"]):
        x, mask, sel = layer(
            lp, x, round8=round8, first=i == 0, num_heads=num_heads,
            nope=nope,
            rope_dim=rope_dim, index_heads=index_heads,
            index_rope=index_rope, index_topk=index_topk, top_k=top_k,
            scale=scale, held=tuple(held), eps=eps, theta=theta,
            ablate=ablate, given=None if given is None else given[i])
        if mask is not None:
            masks.append(mask)
        if sets:
            chosen.append(sel)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return (_head(params["w_f"], round8(params["lm_head"]), x, eps=eps),
            jnp.stack(masks) if masks else None,
            jnp.stack(chosen) if sets else None)
