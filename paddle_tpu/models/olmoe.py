"""OLMoE (allenai/OLMoE-1B-7B, arXiv:2409.02060) behind ``/generate``.

The block as published — RMSNorm, a second RMSNorm over the whole q and
k projections, rotary positions on q and k (rotate-half pairing), a
routed feed-forward of ``num_experts`` SwiGLU experts with the router's
top-k softmax weights taken as they are (``norm_topk_prob`` false), no
bias anywhere, an untied head — as a block definition for the paged
skeleton of ``paddle_tpu/decode/model.py``, plus its parameters (one
jitted initialiser) and the model object ``DecodeSession`` drives.

Matmul operands are in the weights' dtype (bfloat16 as served) with
float32 accumulation; the residual stream, the norms, the softmaxes and
the rotation are float32; the K (already rotated) and V rows a page
holds are in the pools' dtype.  Random weights only: loading a
checkpoint is not supported yet.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.decode.model import PagedDecoderLM, PageRunCache
from paddle_tpu.models import moe

_F32 = jnp.float32


def rms_norm(x, scale, eps):
    x = x.astype(_F32)
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps)) * scale.astype(_F32)


def rope_angles(pos, head_dim, theta):
    """(cos, sin), each (..., 1, head_dim / 2), of the rows' absolute
    positions ``pos`` (...)."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[..., None, None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """Rotate ``x`` (..., heads, dh): channel i pairs with i + dh/2
    (rotate-half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


@dataclasses.dataclass(frozen=True)
class OlmoeBlock(PageRunCache):
    """See ``decode/model.py:Gpt2Block`` for the contract."""

    eps: float = 1e-5
    theta: float = 10000.0
    top_k: int = 8

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def qkv(self, lp, x, pos, heads):
        n = rms_norm(x, lp["w_in"], self.eps)
        split = x.shape[:-1] + (heads, x.shape[-1] // heads)
        q = rms_norm(_mm(n, lp["wq"]), lp["w_qn"], self.eps).reshape(split)
        k = rms_norm(_mm(n, lp["wk"]), lp["w_kn"], self.eps).reshape(split)
        v = _mm(n, lp["wv"]).reshape(split)
        cos, sin = rope_angles(pos, split[-1], self.theta)
        dtype = lp["wq"].dtype
        return (rope(q, cos, sin).astype(dtype),
                rope(k, cos, sin).astype(dtype), v.astype(dtype))

    def attn_out(self, lp, x, a):
        return x + _mm(a, lp["wo"])

    def router_rows(self, lp, x):
        """What the router and the experts are fed: (R, d)."""
        m = rms_norm(x, lp["w_post"], self.eps).astype(lp["wr"].dtype)
        return m.reshape(-1, m.shape[-1])

    def mlp(self, lp, x, live):
        """The routed layer; reports the (E,) assignments per expert
        over the live rows."""
        y, load, _ = moe.routed_experts(
            self.router_rows(lp, x), lp["wr"], lp["w_gate"], lp["w_up"],
            lp["w_down"], top_k=self.top_k,
            live=None if live is None else live.reshape(-1))
        return x + y.reshape(x.shape), load

    def head(self, params, x):
        return _mm(rms_norm(x, params["w_f"], self.eps), params["lm_head"])


@functools.partial(jax.jit, static_argnames=(
    "vocab", "d", "layers", "experts", "expert_width", "dtype"))
def init_params(key, *, vocab, d, layers, experts, expert_width, dtype):
    """Every weight N(0, 0.02) in ``dtype``, every norm scale 1, made
    on the device by this one program."""
    def normal(k, *shape):
        return (jax.random.normal(k, shape, _F32) * 0.02).astype(dtype)

    ones = jnp.ones((d,), dtype)
    ks = jax.random.split(key, 2 + layers)
    f = expert_width
    params = {"emb": normal(ks[0], vocab, d), "w_f": ones,
              "lm_head": normal(ks[1], d, vocab), "layers": []}
    for i in range(layers):
        lk = jax.random.split(ks[2 + i], 8)
        params["layers"].append({
            "w_in": ones, "w_qn": ones, "w_kn": ones, "w_post": ones,
            "wq": normal(lk[0], d, d), "wk": normal(lk[1], d, d),
            "wv": normal(lk[2], d, d), "wo": normal(lk[3], d, d),
            "wr": normal(lk[4], d, experts),
            "w_gate": normal(lk[5], experts, d, f),
            "w_up": normal(lk[6], experts, d, f),
            "w_down": normal(lk[7], experts, f, d)})
    return params


class OlmoeLM(PagedDecoderLM):
    """OLMoE over the paged skeleton: what ``make_decode_model()``
    returns (``perf/configs/olmoe-1b-7b.gen_config.py``)."""

    def __init__(self, vocab: int = 50304, d_model: int = 2048,
                 num_heads: int = 16, num_layers: int = 16,
                 num_experts: int = 64, experts_per_tok: int = 8,
                 expert_width: int = 1024,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 10000.0,
                 max_len: int = 4096, num_pages: int = 32,
                 page_size: int = 32, pages_per_seq: int = 8,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = 0,
                 seed: int = 0):
        super().__init__(vocab, d_model, num_heads, num_layers, max_len,
                         page_size, pages_per_seq, bos_id, eos_id)
        self.block = OlmoeBlock(eps=float(rms_norm_eps),
                                theta=float(rope_theta),
                                top_k=int(experts_per_tok))
        dtype = jnp.dtype(dtype)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, d=self.d,
            layers=self.layers, experts=int(num_experts),
            expert_width=int(expert_width), dtype=dtype)
        self._make_pools(num_pages, dtype)

    def _observe(self, phase, report, rows):
        load = np.asarray(report)                       # (layers, experts)
        moe.count_load(phase, load, rows, self.block.top_k, load.shape[1])
