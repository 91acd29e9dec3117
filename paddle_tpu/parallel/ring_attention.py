"""Ring attention: sequence/context parallelism over a mesh axis.

The reference (PaddlePaddle v0.11.0) predates sequence parallelism —
its long-sequence story is LoD ragged batching (framework/lod_tensor.h).
A TPU-native framework must scale *sequence length* across chips, so
this module implements ring attention (Liu et al. 2023 style): Q stays
resident, K/V blocks rotate around the mesh axis via ``lax.ppermute``
over ICI, and softmax is accumulated online (flash-attention style
running max/sum), so no chip ever materializes the full S x S score
matrix or the full K/V.

Differentiable: the loop is a ``lax.scan`` and ``ppermute`` has a
well-defined transpose, so ``jax.grad`` through a ``shard_map``-wrapped
call yields the ring-parallel backward pass automatically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _online_block(q, k, v, bias, m, l, acc, scale):
    """One flash-style block update.  q:(B,H,Sq,D) k,v:(B,H,Sk,D);
    m,l:(B,H,Sq) running max / normalizer; acc:(B,H,Sq,D)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard: fully-masked rows have m_new == -inf; keep exp args finite
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _use_flash_chunks(B, H, S, D) -> bool:
    from paddle_tpu import pallas as pk
    from paddle_tpu.pallas import flash_attention as fa

    return pk.dispatch("ring_flash_attention",
                       pk.policy(fa.fits(B, H, S, D), S >= pk.FLASH_MIN_SEQ))


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Attention over sequence shards.  Call inside ``shard_map`` (or
    ``shard_map``-style manual SPMD) with the sequence dim of q/k/v
    sharded over ``axis_name``.

    q, k, v: (B, H, S_local, D); returns (B, H, S_local, D).
    ``causal`` masks by *global* position, computed from the shard index.

    Per-shard chunk math: when the local shapes fit, each (q_local,
    kv_chunk) block runs the Pallas flash kernel (no S_local x S_chunk
    score tensor in HBM) and chunks merge in log-sum-exp space; causal
    masking resolves at the ring level — chunks strictly ahead of this
    shard skip their FLOPs entirely, the diagonal chunk runs the
    kernel's causal mask, earlier chunks run unmasked.  Shapes the
    kernel rejects fall back to the jnp online-softmax block.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    if scale is None:
        scale = D ** -0.5

    if _use_flash_chunks(B, H, S, D):
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     n, idx)

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, D), jnp.float32)

    q_pos = idx * S + jnp.arange(S)

    def step(carry, t):
        k_cur, v_cur, m, l, acc = carry
        # chunk currently held arrived from shard (idx - t) mod n
        src = (idx - t) % n
        bias = None
        if causal:
            k_pos = src * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]
            bias = jnp.where(mask, 0.0, -jnp.inf)[None, None]
        m, l, acc = _online_block(qf, k_cur.astype(jnp.float32),
                                  v_cur, bias, m, l, acc, scale)
        # rotate K/V to the next shard around the ring (ICI neighbours)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m, l, acc), None

    (k, v, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, a0),
                                    jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


def _ring_attention_flash(q, k, v, axis_name, causal, scale, n, idx):
    """Ring attention with the Pallas flash kernel as the per-chunk
    block: chunk results (normalized out, lse) merge in log-sum-exp
    space, which is exact and keeps the backward pass flowing through
    the kernel's custom vjp plus elementwise merge algebra."""
    from paddle_tpu import pallas as pk
    from paddle_tpu.pallas.flash_attention import flash_attention_with_lse

    B, H, S, D = q.shape
    q3 = q.reshape(B * H, S, D)
    interp = pk.interpret_mode()

    o0 = jnp.zeros((B * H, S, D), jnp.float32)
    lse0 = jnp.full((B * H, S), -jnp.inf, jnp.float32)

    def step(carry, t):
        k_cur, v_cur, o, lse = carry
        src = (idx - t) % n
        k3 = k_cur.reshape(B * H, S, D)
        v3 = v_cur.reshape(B * H, S, D)

        def full(_):
            return flash_attention_with_lse(q3, k3, v3, False, scale,
                                            interp)

        def diag(_):
            return flash_attention_with_lse(q3, k3, v3, True, scale,
                                            interp)

        def skip(_):
            return (jnp.zeros_like(q3), jnp.full((B * H, S), -jnp.inf,
                                                 jnp.float32))

        if causal:
            # 0: src < idx (full), 1: src == idx (diagonal), 2: skip
            branch = jnp.where(src < idx, 0, jnp.where(src == idx, 1, 2))
            out_c, lse_c = lax.switch(branch, [full, diag, skip], None)
        else:
            out_c, lse_c = full(None)

        lse_new = jnp.logaddexp(lse, lse_c)
        safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
        w_old = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - safe))
        w_new = jnp.where(jnp.isneginf(lse_c), 0.0, jnp.exp(lse_c - safe))
        o = o * w_old[..., None] + out_c.astype(jnp.float32) \
            * w_new[..., None]

        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, o, lse_new), None

    (_, _, o, lse), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(n))
    return o.reshape(B, H, S, D).astype(q.dtype)


def local_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Single-device reference path, same signature semantics
    ((B, H, S, D) in, (B, H, S, D) out)."""
    B, H, S, D = q.shape
    if scale is None:
        scale = D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def ring_attention_sharded(mesh, sp_axis: str, q, k, v,
                           causal: bool = False,
                           batch_axis: Optional[str] = None,
                           head_axis: Optional[str] = None):
    """``shard_map``-wrapped ring attention usable from inside ``jit``.

    q, k, v are logically-global (B, H, S, D) arrays; the sequence dim
    is sharded over ``sp_axis``, batch over ``batch_axis`` (dp), heads
    over ``head_axis`` (tp) when given.  GSPMD composes this region
    with the surrounding program's shardings.
    """
    spec = P(batch_axis, head_axis, sp_axis, None)
    fn = functools.partial(ring_attention, axis_name=sp_axis, causal=causal)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return mapped(q, k, v)
