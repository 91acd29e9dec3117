"""A routed (sparse mixture-of-experts) feed-forward layer.

``routed_experts`` is the whole layer as one pure function of the rows
and the stacked expert weights: a float32 router softmax, top-k, a
stable sort of the ``rows x k`` assignments by expert, ONE grouped GEMM
per projection over the sorted rows (``jax.lax.ragged_dot``: on a TPU
the compiler lowers it to a grouped-matmul kernel that reads only the
experts that were hit; it is never 64 masked dense matmuls), un-sort,
weighted sum.  Fixed shapes: every row routes, whatever it holds; the
``live`` mask only decides which rows the returned load counts.

The four ``jax.named_scope``s (``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``) put every instruction of the layer
under a name in the compiled program's ``op_name``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.observability import metrics as _metrics

_F32 = jnp.float32

# fed from the (layers, experts) load a prefill or a step hands back;
# ``phase`` is "prefill" or "decode"
_M_ASSIGNMENTS = _metrics.counter(
    "moe_assignments_total",
    "(row, expert) assignments of the routed layers over live rows "
    "(real prompt rows, active slots), summed over layers")
_M_EXPERTS_HIT = _metrics.counter(
    "moe_experts_hit_total",
    "experts with at least one live row, summed over layers and calls: "
    "the expert weights a call had to read")
_M_LOAD_MAX = _metrics.counter(
    "moe_expert_load_max_total",
    "live rows of the busiest expert, summed over layers and calls; "
    "over moe_assignments_total / experts it is how uneven routing was")


def count_load(phase: str, load: np.ndarray) -> None:
    """Feed the registry from one call's (layers, experts) load."""
    _M_ASSIGNMENTS.inc(int(load.sum()), phase=phase)
    _M_EXPERTS_HIT.inc(int((load > 0).sum()), phase=phase)
    _M_LOAD_MAX.inc(int(load.max(axis=-1).sum()), phase=phase)


def route(m, wr, top_k: int):
    """Router of rows ``m`` (R, d) over ``wr`` (d, E): the float32
    softmax over all experts, its ``top_k`` largest per row (a tie goes
    to the lower expert index), the weights as the softmax gave them
    (not renormalised) -> (weights (R, k) f32, experts (R, k) int32)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(m, wr, preferred_element_type=_F32)
        p = jax.nn.softmax(logits, axis=-1)
    with jax.named_scope("moe_dispatch"):
        return jax.lax.top_k(p, top_k)


def routed_experts(m, wr, w_gate, w_up, w_down, *, top_k: int, live=None):
    """``sum_e p_e * W_down,e( silu(W_gate,e m) * W_up,e m )`` over each
    row's ``top_k`` experts.

    m (R, d); wr (d, E); w_gate, w_up (E, d, f); w_down (E, f, d);
    ``live`` (R,) bool or None (all rows) -> (y (R, d) float32, load
    (E,) int32: assignments per expert over the live rows)."""
    R, d = m.shape
    E = wr.shape[1]
    w, idx = route(m, wr, top_k)
    with jax.named_scope("moe_dispatch"):
        expert_of = idx.reshape(-1)                          # (R*k,)
        order = jnp.argsort(expert_of, stable=True)
        xs = m[order // top_k]                               # sorted rows
        sizes = jnp.zeros((E,), jnp.int32).at[expert_of].add(1)
        load = sizes if live is None else jnp.zeros((E,), jnp.int32).at[
            expert_of].add(jnp.repeat(live.astype(jnp.int32), top_k))
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, w_gate, sizes,
                               preferred_element_type=_F32)
        u = jax.lax.ragged_dot(xs, w_up, sizes,
                               preferred_element_type=_F32)
        h = (jax.nn.silu(g) * u).astype(m.dtype)
        ys = jax.lax.ragged_dot(h, w_down, sizes,
                                preferred_element_type=_F32)
    with jax.named_scope("moe_combine"):
        back = jnp.zeros((R * top_k,), jnp.int32).at[order].set(
            jnp.arange(R * top_k, dtype=jnp.int32))
        y = jnp.einsum("rk,rkd->rd", w, ys[back].reshape(R, top_k, d))
    return y, load
