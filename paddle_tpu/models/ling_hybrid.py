"""Ling-3.0-flash (inclusionAI/Ling-3.0-flash, ``model_type``
bailing_hybrid) behind ``/generate``, as ONE chip of a 4-way
expert-parallel group serves the layers its pipeline stage holds: Kimi
Delta Attention (KDA, arXiv:2510.26692) five layers of six, a latent
attention layer (MLA, arXiv:2405.04434) the sixth, and DeepSeek-V3's
routed feed-forward (arXiv:2412.19437) with its group step.

Pre-norm residuals, RMSNorm, no bias.  A **KDA** layer, per head ``h``
of ``d_k = d_v`` (``u``: the layer's normed input rows):

    z = [W_q u; W_k u; W_v u], a causal depthwise conv of width 4 over
        each channel (zeros before row 0), SiLU
    q_t <- q_t / |q_t| * d_k^-1/2,  k_t <- k_t / |k_t|
    beta_t = sigmoid(W_b u)                       (a head)
    g_t = lower_bound * sigmoid(exp(A_log) (W_f u + dt_bias))
        (a head A KEY CHANNEL; ``kda_safe_gate``: bounded below by
        ``kda_lower_bound`` = -5), alpha_t = exp(g_t)
    S'_t = S_{t-1} Diag(alpha_t)
    S_t = S'_t + beta_t (v_t - S'_t k_t) k_t^T,   o_t = S_t q_t
    y_t = W_o [RMSNorm_{d_v}(o_t) * sigmoid(w_g . u)]   (ONE gate a head)

with the state ``S`` (d_v, d_k) float32, zero before row 0.  The decay
is a vector over the key channels where ``models/olmo_hybrid.py``'s is
one number a head: the same two computations (a prompt chunked, a
decode step one token on each slot's entry), each by its own kernel of
``pallas/kda.py`` where ``paddle_tpu.pallas`` says so and by its XLA
form here elsewhere (``chunked_kda``, ``step_kda``).  The plain
recurrence, row by row, is the benchmark's reference
(``perf/reference/ling_hybrid_block.py``).

The **latent** layer is ``models/kanana_mla.py``'s to the letter (one
row ``[c ; k^r ; zeros]`` a token, expanded through the flash kernel at
a prefill, absorbed through ``pallas/latent_attention.py`` at a step),
with one thing added: the head-wise output gate before ``W_o``
(``gated_attention_proj_granularity_type`` head_wise is not a
``kda_`` key, so it is read as both kinds of layer's).

The **feed-forward**: the first ``first_k_dense_replace`` layers a
dense SwiGLU, the rest K-EXAONE's routed layer (``models/moe.py``: the
sigmoid router over the published experts, a held range, a shared
SwiGLU) under the group step (``n_group`` groups, the ``topk_group``
best kept, the ``top_k`` among their experts).

Two resources a sequence, from the one cache manager: its page run,
which the latent layer alone writes (ONE pool ``(1, N, pg, 640)``, the
second a placeholder), and ONE state entry, every KDA layer's ``S`` and
the last three rows its conv saw.  The reservation, the table row, the
gauges and the refusals (``UnsupportedOverState``) are
``decode/state_entry.py``'s.

Matmul operands in the weights' dtype (bfloat16 as served), float32
accumulation, residual stream, norms, gates, decays and states; latent
rows and conv tails in the weights' dtype.  Random weights only: loading
a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as pk
from paddle_tpu.decode.state_entry import (  # noqa: F401  (re-exported)
    StateEntryCache,
    StateEntryLM,
    UnsupportedOverState,
    _pad_last,
    causal_conv,
    conv_over_entries,
    conv_tail,
    tail_shape,
)
from paddle_tpu.models import moe
from paddle_tpu.models.exaone_moe import ExaoneMoeBlock, swiglu
from paddle_tpu.models.kanana_mla import (QK_ROW_STD, KananaMlaBlock,
                                          _M_PREFILL_PAIRS, _init_ends,
                                          _normal)
from paddle_tpu.models.olmo_hybrid import l2_normalize, stored_key_width
from paddle_tpu.models.olmoe import _mm, rms_norm
from paddle_tpu.pallas import kda

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
LINEAR, LATENT = "linear_attention", "latent_attention"
CHUNK, SUB = 64, kda.SUB


class UnsupportedSwigluLimit(NotImplementedError):
    """A layer whose SwiGLU is clamped (a non-zero entry of
    ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``):
    how the limit clamps is not in ``config.json``, so it is refused,
    not guessed."""


def layer_types_of(layers: int, group: int) -> tuple:
    """``layer_group_size``: layer ``i`` is latent attention where
    ``(i + 1) % group == 0``, KDA otherwise."""
    return tuple(LATENT if (i + 1) % group == 0 else LINEAR
                 for i in range(layers))


def step_kda(q, k, v, g, beta, state):
    """One row on a state (any leading shape): ``q``, ``k``, ``g`` (...,
    H, d_k), ``v`` (..., H, d_v), ``beta`` (..., H), ``state`` (..., H,
    d_v, d_k) -> (o (..., H, d_v), the new state).  Multiply-reduces,
    float32.  ``pallas/kda.py:kda_step`` is this, in this order, on
    blocks of the pool in VMEM."""
    state = state * jnp.exp(g)[..., None, :]                  # S Diag(alpha)
    Sk = jnp.sum(state * k[..., None, :], axis=-1)            # (.., H, d_v)
    Sq = jnp.sum(state * q[..., None, :], axis=-1)
    u = beta[..., None] * (v - Sk)
    new = state + u[..., None] * k[..., None, :]
    return Sq + u * jnp.sum(k * q, axis=-1, keepdims=True), new


def chunked_kda(q, k, v, g, beta, state, chunk=CHUNK, sub=SUB):
    """The rule over T rows, chunked.  ``q``, ``k`` (T, H, d_k) already
    normalised and scaled, ``v`` (T, H, d_v), ``g`` (T, H, d_k) the log
    of the decay a key channel (at least ``-2 kda.REACH / sub``), ``beta``
    (T, H), ``state`` (H, d_v, d_k) as it stood before row 0 -> (o (T,
    H, d_v), the state after row T - 1).  All float32.

    ``pallas/kda.py``'s head has the algebra: inside a chunk ``(I + A)
    U = beta V - (beta K e^G) S_0^T`` with ``A[t, s] = beta_t sum_c
    k_t[c] k_s[c] e^(G_t[c] - G_s[c])`` strictly below the diagonal and
    ``M`` the same of ``q`` on and below it, both built a block of
    ``sub`` query rows at a time against one reference row, so that no
    factor leaves float32.  Everything but the three terms in ``S_0``
    for all chunks at once; a scan over the chunks carries the state.
    A row with ``g = 0, beta = 0`` (a bucket's padding) leaves the
    state as it was."""
    T, H, dk = q.shape
    rows = -(-T // sub) * sub
    C = min(chunk, rows)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    n = (T + pad) // C

    def chunks(a):          # (T, H, ...) -> (n, H, C, ...)
        return jnp.moveaxis(a.reshape((n, C) + a.shape[1:]), 2, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=2)                                 # (n, H, C, dk)
    kb = beta[..., None] * k
    A, M = [], []
    for lo in range(0, C, sub):
        ref = G[:, :, lo + sub // 2 - 1][:, :, None]       # the block's middle
        from_ref = jnp.exp(G[:, :, lo:lo + sub] - ref)
        keys = k * jnp.exp(jnp.minimum(ref - G, kda.REACH))
        A.append(jnp.einsum("nhtk,nhsk->nhts", kb[:, :, lo:lo + sub]
                            * from_ref, keys, precision=_HIGHEST))
        M.append(jnp.einsum("nhtk,nhsk->nhts", q[:, :, lo:lo + sub]
                            * from_ref, keys, precision=_HIGHEST))
    t = jnp.arange(C)
    A = jnp.where(t[:, None] > t[None, :], jnp.concatenate(A, axis=2), 0.0)
    M = jnp.where(t[:, None] >= t[None, :], jnp.concatenate(M, axis=2), 0.0)
    eG = jnp.exp(G)
    sol = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=_F32),
        jnp.concatenate([beta[..., None] * v, kb * eG], axis=-1),
        lower=True, unit_diagonal=True)
    U0, W = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
    Qg = eG * q
    Kd = jnp.exp(G[:, :, -1:] - G) * k
    g_end = eG[:, :, -1][:, :, None, :]                       # (n, H, 1, dk)

    def one(S, c):
        U0c, Wc, Mc, Qgc, Kdc, gc = c
        U = U0c - jnp.einsum("hck,hvk->hcv", Wc, S, precision=_HIGHEST)
        O = (jnp.einsum("hck,hvk->hcv", Qgc, S, precision=_HIGHEST)
             + jnp.einsum("hcs,hsv->hcv", Mc, U, precision=_HIGHEST))
        S = gc * S + jnp.einsum("hcv,hck->hvk", U, Kdc, precision=_HIGHEST)
        return S, O

    state, O = jax.lax.scan(one, state.astype(_F32),
                            (U0, W, M, Qg, Kd, g_end))
    o = jnp.moveaxis(O, 1, 2).reshape(n * C, H, -1)
    return o[:T], state


@dataclasses.dataclass(frozen=True)
class LingLatentBlock(KananaMlaBlock):
    """Kanana's latent layer, and the head-wise output gate before
    ``W_o``: one sigmoid a head of the layer's normed input."""

    def attn_out(self, lp, x, a):
        heads = lp["w_g"].shape[1]
        gate = jax.nn.sigmoid(_mm(rms_norm(x, lp["w_in"], self.eps),
                                  lp["w_g"]))
        a = a.reshape(a.shape[:-1] + (heads, -1)) * gate[..., None]
        return x + _mm(a.reshape(x.shape[:-1] + (-1,)), lp["wo"])


@dataclasses.dataclass(frozen=True)
class LingHybridBlock(StateEntryCache):
    """See ``decode/model.py:Gpt2Block`` for the block's contract and
    ``decode/state_entry.py:StateEntryCache`` for the cache side: the
    latent layer's page side is ``latent``'s (``KananaMlaBlock``'s
    functions, called as they are, over the page run's columns of the
    table and the one latent pool); a KDA layer's is the delta rule
    over the sequence's state entry.  The cache is ``(latent pool,
    placeholder, state_pool, conv_pool)``.  ``at``: the layer this view
    of the block is (``layer``)."""

    recurrent_kind = LINEAR
    layer_types: tuple = (LINEAR,) * 5 + (LATENT,)
    latent: LingLatentBlock = LingLatentBlock()
    lin_heads: int = 32
    d_k: int = 128
    d_v: int = 128
    lower_bound: float = -5.0
    eps: float = 1e-6
    top_k: int = 8
    scale: float = 2.5
    held: tuple = (0, 128)
    groups: tuple = (8, 4)       # (n_group, topk_group)
    full_pages: int = 64         # table columns of the page run
    at: int = 0

    # -- the block: the embedding and the head are K-EXAONE's ---------------

    embed = ExaoneMoeBlock.embed
    head = ExaoneMoeBlock.head

    def mlp(self, lp, x, live):
        """The feed-forward after the second pre-norm: a leading
        layer's dense SwiGLU, or the shared expert plus the held routed
        experts under the group step.  Reports (held experts + 1 +
        n_group,) int32: the live rows' assignments per held expert,
        those that went elsewhere, then the live rows that kept each
        group (a dense layer: zeros)."""
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["w_gate"].dtype)
        m = m.reshape(-1, m.shape[-1])
        if "wr" not in lp:
            y = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
            report = jnp.zeros((self.held[1] + 1 + self.groups[0],),
                               jnp.int32)
        else:
            with jax.named_scope("moe_shared"):
                y = swiglu(m, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
            routed, load, elsewhere, kept = moe.routed_experts(
                m, lp["wr"], lp["w_gate"], lp["w_up"], lp["w_down"],
                top_k=self.top_k,
                live=None if live is None else live.reshape(-1),
                scores=moe.sigmoid_scores(lp["b"], self.scale),
                held=self.held, groups=self.groups)
            y = y + routed
            report = jnp.concatenate(
                [load, elsewhere.astype(jnp.int32)[None], kept])
        return x + y.reshape(x.shape), report

    # -- a KDA layer's pieces -----------------------------------------------

    def _projections(self, lp, x):
        """-> (the rows the conv sees, in the weights' dtype; the output
        gate (..., H); the log decay ``g`` (..., H, d_k); ``beta`` (...,
        H))."""
        H = self.lin_heads
        u = rms_norm(x, lp["w_in"], self.eps)
        z = _mm(u, lp["w_qkv"]).astype(lp["w_qkv"].dtype)
        gate = jax.nn.sigmoid(_mm(u, lp["w_g"]))
        with jax.named_scope("lin_attn_gate"):
            f = _mm(u, lp["w_f"]) + lp["dt_bias"]
            f = f.reshape(f.shape[:-1] + (H, self.d_k))
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(lp["A_log"])[:, None] * f)
            beta = jax.nn.sigmoid(_mm(u, lp["w_b"]))
        return z, gate, g, beta

    def _split(self, zc):
        """The conv's output rows -> q, k (..., H, d_k) normalised and
        scaled, v (..., H, d_v)."""
        H, dk, dv = self.lin_heads, self.d_k, self.d_v
        lead = zc.shape[:-1]
        q = l2_normalize(zc[..., :H * dk].reshape(lead + (H, dk)))
        k = l2_normalize(zc[..., H * dk:2 * H * dk].reshape(lead + (H, dk)))
        v = zc[..., 2 * H * dk:].reshape(lead + (H, dv))
        return q * dk ** -0.5, k, v

    def _lin_out(self, lp, x, o, gate):
        """RMSNorm over each head's d_v channels times the head's gate,
        the output projection and the block's residual."""
        y = rms_norm(o, lp["w_on"], self.eps) * gate[..., None]
        return x + _mm(y.reshape(o.shape[:-2] + (-1,)), lp["w_o"])

    # -- the page side: the latent layer's ------------------------------------

    def store_pages(self, pages, kept, flat):
        return self.latent.store_prompts(pages, kept, flat)

    def page_mixer(self, lp, x, pos, pages, li, addr, heads):
        """The pool holds the latent layers alone, and the table's first
        columns are the page run."""
        addr = addr._replace(tables=addr.tables[:, :self.full_pages])
        return self.latent.mixer(lp, x, pos, pages, self.index_in_kind,
                                 addr, heads)

    # -- the mixers ---------------------------------------------------------

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        if not self.recurrent:
            return self.latent.prompt_mixer(lp, x, pos, heads, live)
        T = x.shape[0]
        z, gate, g, beta = self._projections(lp, x)
        n = T if live is None else jnp.sum(live.astype(jnp.int32))
        if live is not None:
            # a recurrence sees padding that causal attention hides:
            # rows from n on neither decay the state nor write to it
            g = jnp.where(live[:, None, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
        with jax.named_scope("lin_attn"):
            with jax.named_scope("lin_attn_conv"):
                zc = jax.nn.silu(causal_conv(z, lp["w_conv"]))
                tail = conv_tail(z, lp["w_conv"].shape[0], n)
            q, k, v = self._split(zc)
            with jax.named_scope("lin_attn_scan"):
                state = jnp.zeros((self.lin_heads, self.d_v, self.d_k), _F32)
                if pk.use_kda_chunked(state.dtype, T, *state.shape,
                                      self.lower_bound):
                    o, state = kda.kda_chunked(
                        q, k, v, g, beta, state,
                        interpret=pk.interpret_mode())
                else:
                    o, state = chunked_kda(q, k, v, g, beta, state)
            out = self._lin_out(lp, x, o, gate)
        return out, (state, tail)

    def recurrent_step(self, lp, x, cache, addr):
        pool, placeholder, state_pool, conv_pool = cache
        z, gate, g, beta = self._projections(lp, x)
        at = self.entries_of(state_pool, addr)
        states = state_pool.reshape((-1,) + state_pool.shape[2:])
        tails = conv_pool.reshape((-1,) + conv_pool.shape[2:])
        wide = states.shape[-1]                      # the keys as stored
        with jax.named_scope("lin_attn"):
            with jax.named_scope("lin_attn_conv"):
                zc, tails = conv_over_entries(tails, at, z, lp["w_conv"])
            q, k, v = self._split(zc)
            q, k, g = (_pad_last(a, wide) for a in (q, k, g))
            with jax.named_scope("lin_attn_state"):
                if pk.use_kda_step(states.dtype, *states.shape[1:]):
                    o, states = kda.kda_step(
                        states, at, q, k, v, g, beta,
                        interpret=pk.interpret_mode())
                else:
                    # the kernel's reference: the slots' entries
                    # gathered, advanced and scattered
                    o, new = step_kda(q, k, v, g, beta, states[at])
                    states = states.at[at].set(new)
            out = self._lin_out(lp, x, o, gate)
        return out, (pool, placeholder, states.reshape(state_pool.shape),
                     tails.reshape(conv_pool.shape))


# -- parameters --------------------------------------------------------------

# a KDA layer's decays: over unit-RMS inputs ``W_f u`` has std ~1, and
# ``dt_bias`` is drawn a channel uniform in DT_BIAS, so that ``exp(A_log)
# (W_f u + dt_bias)`` lies over about -10 .. -1 and a head's 128 decays
# ``alpha = exp(-5 sigmoid(.))`` over about 0.3 .. 0.9998, different
# between the channels of one head and between tokens.  At N(0, 0.02)
# around fla's default ``dt_bias`` every alpha is ~1, and neither the
# scalar-decay ablation nor the lower bound shows in a logit.
DT_BIAS = (-8.5, -2.5)
A_LOG = (np.log(0.7), np.log(1.4))
CONV_STD = 0.5       # the taps: at 0.02 the SiLU behind them is linear
# the latent layer's W_uv and W_o: ONE layer of six is latent, and at
# N(0, 0.02) what it adds is 0.08 of a logit, so that its scale at
# 128^-1/2 for 192^-1/2 moves a logit by a tenth of what bf16 rounding
# does (PERF.md section 6, PR 55); at 0.06 each its output is of the
# residual stream's order
LATENT_OUT_STD = 0.06


@functools.partial(jax.jit, static_argnames=(
    "kind", "routed", "d", "heads", "nope", "rope_dim", "v_dim", "rank",
    "lin_heads", "d_k", "d_v", "conv", "dense_width", "expert_width",
    "shared_width", "router_width", "held", "dtype"))
def _init_layer(key, *, kind, routed, d, heads, nope, rope_dim, v_dim, rank,
                lin_heads, d_k, d_v, conv, dense_width, expert_width,
                shared_width, router_width, held, dtype):
    """One layer's weights, made on the device: one program a kind of
    layer."""
    lk = jax.random.split(key, 20)
    ones = jnp.ones((d,), dtype)
    lp = {"w_in": ones, "w_post": ones}
    if kind == LATENT:
        wide = QK_ROW_STD * d ** -0.5
        lp.update(
            w_cn=jnp.ones((rank,), dtype),
            wq=_normal(lk[0], (d, heads * (nope + rope_dim)), wide, dtype),
            w_kva=jnp.concatenate(
                [_normal(lk[1], (d, rank), 0.02, dtype),
                 _normal(lk[2], (d, rope_dim), wide, dtype)], axis=1),
            w_uk=_normal(lk[3], (heads, nope, rank),
                         QK_ROW_STD * rank ** -0.5, dtype),
            w_uv=_normal(lk[4], (heads, rank, v_dim), LATENT_OUT_STD, dtype),
            w_g=_normal(lk[5], (d, heads), 0.02, dtype),
            wo=_normal(lk[6], (heads * v_dim, d), LATENT_OUT_STD, dtype))
    else:
        H, C = lin_heads, lin_heads * (2 * d_k + d_v)
        lp.update(
            w_qkv=_normal(lk[0], (d, C), 0.02, dtype),
            w_conv=_normal(lk[1], (conv, C), CONV_STD, dtype),
            w_f=_normal(lk[2], (d, H * d_k), 0.02, dtype),
            w_b=_normal(lk[3], (d, H), 0.02, dtype),
            w_g=_normal(lk[4], (d, H), 0.02, dtype),
            w_o=_normal(lk[5], (H * d_v, d), 0.02, dtype),
            w_on=jnp.ones((d_v,), dtype),
            dt_bias=jax.random.uniform(lk[6], (H * d_k,), _F32, *DT_BIAS),
            A_log=jax.random.uniform(lk[7], (H,), _F32, *A_LOG))
    if routed:
        f, s = expert_width, shared_width
        lp.update(
            wr=_normal(lk[8], (d, router_width), 0.02, dtype),
            b=_normal(lk[9], (router_width,), 0.02, _F32),
            ws_gate=_normal(lk[10], (d, s), 0.02, dtype),
            ws_up=_normal(lk[11], (d, s), 0.02, dtype),
            ws_down=_normal(lk[12], (s, d), 0.02, dtype),
            w_gate=_normal(lk[13], (held, d, f), 0.02, dtype),
            w_up=_normal(lk[14], (held, d, f), 0.02, dtype),
            w_down=_normal(lk[15], (held, f, d), 0.02, dtype))
    else:
        lp.update(w_gate=_normal(lk[8], (d, dense_width), 0.02, dtype),
                  w_up=_normal(lk[9], (d, dense_width), 0.02, dtype),
                  w_down=_normal(lk[10], (dense_width, d), 0.02, dtype))
    return lp


def init_params(key, *, vocab, layer_types, first_dense, dtype, **sizes):
    """Every weight N(0, 0.02) in ``dtype`` and every norm scale 1,
    but: the three that make q, k^n and k^r of the latent layer
    (``kanana_mla.QK_ROW_STD``) and its ``W_uv`` and ``W_o``
    (``LATENT_OUT_STD``); a KDA layer's conv taps (``CONV_STD``),
    ``dt_bias`` and ``A_log`` (``DT_BIAS``, ``A_LOG``, float32); the
    router's selection bias N(0, 0.02) in float32."""
    ks = jax.random.split(key, 1 + len(layer_types))
    params = _init_ends(ks[0], vocab=vocab, d=sizes["d"], dtype=dtype)
    params["layers"] = [
        _init_layer(ks[1 + i], kind=kind, routed=i >= first_dense,
                    dtype=dtype, **sizes)
        for i, kind in enumerate(layer_types)]
    return params


class LingHybridLM(StateEntryLM):
    """One chip's share of the layers one pipeline stage holds of
    Ling-3.0-flash over the paged skeleton: what ``make_decode_model()``
    returns (``perf/configs/ling-3.0-flash.gen_config.py``).  The
    reservation, the table row and the refusals are
    ``decode/state_entry.py``'s."""

    page_kind = "latent"

    def __init__(self, vocab: int = 39296, d_model: int = 2560,
                 num_heads: int = 32, num_layers: int = 6,
                 layer_group_size: int = 6, first_k_dense_replace: int = 2,
                 qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                 v_head_dim: int = 128, kv_lora_rank: int = 512,
                 linear_num_heads: int = 32, linear_head_dim: int = 128,
                 short_conv_kernel_size: int = 4,
                 kda_lower_bound: float = -5.0, dense_width: int = 6144,
                 expert_width: int = 768, shared_width: int = 768,
                 num_experts_published: int = 512, held_experts=(0, 128),
                 experts_per_tok: int = 8, n_group: int = 8,
                 topk_group: int = 4, routed_scaling_factor: float = 2.5,
                 expert_swiglu_limits: Sequence[float] = (),
                 shared_swiglu_limits: Sequence[float] = (),
                 rms_norm_eps: float = 1e-6, rope_theta: float = 6e6,
                 max_len: int = 8192, num_pages: int = 64,
                 page_size: int = 128, pages_per_seq: int = 64,
                 state_entries: int = 9, dtype="bfloat16", bos_id: int = 1,
                 eos_id: int = -1, seed: int = 0):
        layer_types = layer_types_of(int(num_layers), int(layer_group_size))
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        for name, limits in (("expert_swiglu_limit_list",
                              expert_swiglu_limits),
                             ("share_expert_swiglu_limit_list",
                              shared_swiglu_limits)):
            clamped = [i for i, x in enumerate(limits[:self.layers]) if x]
            if clamped:
                raise UnsupportedSwigluLimit(
                    f"{name} is non-zero at layers {clamped}: a clamped "
                    "SwiGLU is not laid out")
        if num_experts_published % n_group:
            raise ValueError("the router's experts are n_group groups of "
                             "one size")
        self.dh = int(qk_nope_head_dim) + int(qk_rope_head_dim)
        self._count_layers(layer_types, LINEAR)
        self.block = LingHybridBlock(
            layer_types=layer_types,
            latent=LingLatentBlock(
                nope=int(qk_nope_head_dim), rope_dim=int(qk_rope_head_dim),
                v_dim=int(v_head_dim), rank=int(kv_lora_rank),
                eps=float(rms_norm_eps), theta=float(rope_theta)),
            lin_heads=int(linear_num_heads), d_k=int(linear_head_dim),
            d_v=int(linear_head_dim), lower_bound=float(kda_lower_bound),
            eps=float(rms_norm_eps), top_k=int(experts_per_tok),
            scale=float(routed_scaling_factor),
            held=tuple(int(x) for x in held_experts),
            groups=(int(n_group), int(topk_group)),
            full_pages=self.full_pages)
        dtype = jnp.dtype(dtype)
        b, lat = self.block, self.block.latent
        self.conv_taps = int(short_conv_kernel_size)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, layer_types=layer_types,
            first_dense=int(first_k_dense_replace), dtype=dtype, d=self.d,
            heads=self.heads, nope=lat.nope, rope_dim=lat.rope_dim,
            v_dim=lat.v_dim, rank=lat.rank, lin_heads=b.lin_heads,
            d_k=b.d_k, d_v=b.d_v, conv=self.conv_taps,
            dense_width=int(dense_width), expert_width=int(expert_width),
            shared_width=int(shared_width),
            router_width=int(num_experts_published), held=b.held[1])
        self._routed = list(range(int(first_k_dense_replace), self.layers))
        self._router_width = int(num_experts_published)
        self._make_pools(
            num_pages, dtype, int(state_entries), (lat.width,),
            (b.lin_heads, b.d_v, stored_key_width(b.d_k)),
            tail_shape(self.conv_taps, b.lin_heads * (2 * b.d_k + b.d_v)),
            value_pool=False)

    def _observe(self, phase, report, rows):
        report = np.asarray(report)[self._routed]  # (routed, C + 1 + groups)
        C = self.block.held[1]
        moe.count_load(phase, report[:, :C], rows, self.block.top_k,
                       self._router_width, int(report[:, C].sum()))
        moe.count_groups(phase, report[:, C + 1:])

    def prefill(self, prompt, pages, cached_len: int = 0):
        out = super().prefill(prompt, pages, cached_len)
        n = len(prompt)
        _M_PREFILL_PAIRS.inc(n * (n + 1) // 2)
        return out
