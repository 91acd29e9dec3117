"""A routed (sparse mixture-of-experts) feed-forward layer.

``routed_experts`` is the whole layer as one pure function of the rows
and the stacked expert weights: float32 router scores by the block's
scoring rule (``softmax_scores``: OLMoE's; ``sigmoid_scores``: the
DeepSeek-V3 router K-EXAONE uses), top-k, then the sum over each row's
chosen experts, computed in one of two ways that ``expert_path`` picks
from the call's static shape at trace time (no option selects one):

- ``grouped``, a call of many rows (a prefill bucket over
  ``DENSE_MAX_ROWS``) or of so few that most experts get none (a step
  of a few slots): a stable sort of the ``rows x k`` assignments by
  expert, ONE grouped GEMM per projection over the sorted rows
  (``jax.lax.ragged_dot``: on a TPU the compiler lowers it to a
  grouped-matmul kernel that reads only the experts that were hit),
  un-sort, weighted sum.
- ``dense``, a call in between (a decode step of tens of slots, a
  verify chunk, a short bucket): every row through every held expert
  as matmuls batched over the experts, the routing weight (0 where a
  row did not choose the expert) doing the selecting.  It IS C masked
  dense matmuls, and that is the faster way to read the same bytes:
  such a call's rows hit nearly every held expert, so either way reads
  all of their weights once, and a plain matmul streams them at 84-87%
  of the chip's bandwidth where the grouped GEMM's 256-row tiles,
  holding about four real rows each, reach 37-62% (PERF.md section 6,
  PR 34).  The extra arithmetic (C / k times the grouped pass's) hides
  under the stream.

Fixed shapes: every row routes, whatever it holds; the ``live`` mask
only decides which rows the returned load counts.

The router always scores its full width.  A chip of an expert-parallel
deployment is told which experts it holds (``held``: the first and the
count of a contiguous range of the router's columns) and computes only
its own experts' part of each row's sum: the assignments that went to
experts held elsewhere weigh nothing (grouped: they sort behind the
held ones and belong to no group of the grouped GEMM).  What the other
chips would add is not computed and nothing stands in for them.

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
_M_ELSEWHERE = _metrics.counter(
    "moe_assignments_elsewhere_total",
    "(row, expert) assignments of live rows whose chosen expert this "
    "chip does not hold (another chip of the expert-parallel group "
    "does), summed over layers; 0 where every expert is held")
_M_LOAD_MAX = _metrics.counter(
    "moe_expert_load_max_total",
    "live rows of the busiest expert, summed over layers and calls; "
    "over moe_assignments_total / experts it is how uneven routing was")
_M_PATH = _metrics.counter(
    "moe_expert_path_total",
    "routed layers run, one a layer a call, by the way the call's "
    "shape had the expert sum computed: path=\"dense\" (every row "
    "through every held expert) or \"grouped\" (the grouped GEMM)")


def count_load(phase: str, load: np.ndarray, path: str,
               elsewhere: int = 0) -> None:
    """Feed the registry from one call's (layers, held experts) load,
    the ``path`` its shape had the layers take (``expert_path``) and
    its count of assignments that went to experts held elsewhere."""
    _M_PATH.inc(load.shape[0], path=path, phase=phase)
    _M_ASSIGNMENTS.inc(int(load.sum()), phase=phase)
    _M_EXPERTS_HIT.inc(int((load > 0).sum()), phase=phase)
    _M_LOAD_MAX.inc(int(load.max(axis=-1).sum()), phase=phase)
    if elsewhere:
        _M_ELSEWHERE.inc(int(elsewhere), phase=phase)


def softmax_scores(logits):
    """OLMoE's rule.  Of the router's logits (R, E) -> (what ranks the
    experts, what weighs them, the weights of the chosen (R, k) values
    of the latter): the softmax over all experts for both, taken as it
    is (not renormalised)."""
    p = jax.nn.softmax(logits, axis=-1)
    return p, p, lambda chosen: chosen


def sigmoid_scores(bias, scale: float):
    """The DeepSeek-V3 rule, as a function of the layer's selection
    ``bias`` (E,): ``s = sigmoid(logits)``; experts are ranked by ``s +
    bias`` and weighed by the unbiased ``s``, a row's weights being
    ``scale * s / sum over its k chosen`` (all of them, wherever they
    are held)."""
    def rule(logits):
        s = jax.nn.sigmoid(logits)
        return s + bias.astype(_F32), s, lambda chosen: (
            scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True))
    return rule


def route(m, wr, top_k: int, scores=softmax_scores):
    """Router of rows ``m`` (R, d) over ``wr`` (d, E): the float32
    scores of all experts by the rule ``scores``, the ``top_k`` largest
    per row of what it ranks by (a tie goes to the lower expert index),
    and the weights it makes of what it weighs by -> (weights (R, k)
    f32, experts (R, k) int32)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(m, wr, preferred_element_type=_F32)
        rank_by, weigh_by, weights_of = scores(logits)
    with jax.named_scope("moe_dispatch"):
        top, idx = jax.lax.top_k(rank_by, top_k)
        if weigh_by is not rank_by:
            top = jnp.take_along_axis(weigh_by, idx, axis=-1)
        return weights_of(top), idx


# The dense pass is taken by a call of at most DENSE_MAX_ROWS rows that
# sends each expert DENSE_MIN_PER_EXPERT assignments or more, in the
# mean.  Measured on a TPU v5e (PERF.md section 6, PR 34).  Inside both
# cells' prefill programs the dense pass wins at both expert shapes up
# to 256 rows, at 512 it wins at one and loses at the other, from 1,024
# it loses at both.  At two assignments an expert (86% of the experts
# hit) it has just overtaken the grouped GEMM, which reads only the
# experts that were hit; at one (63% hit) it loses.
DENSE_MAX_ROWS = 256
DENSE_MIN_PER_EXPERT = 2


def expert_path(rows: int, top_k: int, experts: int) -> str:
    """Which way a call of ``rows`` rows, each choosing ``top_k`` of
    the router's ``experts``, computes the expert sum: ``"dense"`` or
    ``"grouped"``.  A function of the call's static shape alone.

    The dense pass reads every held expert's gate, up and down
    matrices once, as plain batched matmuls that stream them at 84-87%
    of the chip's bandwidth, and multiplies every row with every held
    expert: ``6 rows C d f`` FLOPs under ``3 C d f itemsize`` bytes, a
    ratio that does not depend on C, d or f.  The grouped GEMM reads
    only the experts that were hit and does ``top_k / C`` of that
    arithmetic, but its 256-row tiles hold a handful of real rows each
    until a call has thousands, and it takes 1.5 to 3 times the time
    its bytes need.  So the dense pass is the faster one between two
    edges: the rows must be many enough to hit nearly every expert
    anyway (a step of a few slots reads a few experts, and should), and
    few enough for its arithmetic to hide under the stream (up to some
    two hundred rows) or at least to stay under the grouped GEMM's
    excess."""
    hits_nearly_all = rows * top_k >= DENSE_MIN_PER_EXPERT * experts
    return ("dense" if hits_nearly_all and rows <= DENSE_MAX_ROWS
            else "grouped")


def _grouped_experts(m, w, expert_of, sizes, w_gate, w_up, w_down, top_k,
                     partial):
    """The sum over sorted assignments: a stable sort of the ``R x k``
    assignments by expert, one grouped GEMM a projection over the
    sorted rows, un-sort, weighted sum.  The grouped GEMMs run over all
    R * k sorted rows: a row's k choices can all be held here, so no
    smaller static bound is exact; the rows behind the last group are
    no group's and are zeroed before the sum."""
    R, d = m.shape
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(expert_of, stable=True)
        xs = m[order // top_k]                               # sorted rows
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, w_gate, sizes,
                               preferred_element_type=_F32)
        u = jax.lax.ragged_dot(xs, w_up, sizes,
                               preferred_element_type=_F32)
        h = (jax.nn.silu(g) * u).astype(m.dtype)
        ys = jax.lax.ragged_dot(h, w_down, sizes,
                                preferred_element_type=_F32)
    with jax.named_scope("moe_combine"):
        if partial:
            in_a_group = jnp.arange(R * top_k) < jnp.sum(sizes)
            ys = jnp.where(in_a_group[:, None], ys, 0.0)
        back = jnp.zeros((R * top_k,), jnp.int32).at[order].set(
            jnp.arange(R * top_k, dtype=jnp.int32))
        return jnp.einsum("rk,rkd->rd", w, ys[back].reshape(R, top_k, d))


def _dense_experts(m, w, expert_of, w_gate, w_up, w_down, top_k):
    """The same sum with every row through every held expert, as
    matmuls batched over the experts: the weights are read once where
    they lie, and the routing weight, 0 for an expert a row did not
    choose (or that is held elsewhere), does the selecting."""
    R, d = m.shape
    C = w_gate.shape[0]
    with jax.named_scope("moe_dispatch"):
        # a row chooses an expert at most once: each sum has one term
        chose = expert_of.reshape(R, top_k, 1) == jnp.arange(C)
        wc = jnp.sum(jnp.where(chose, w[:, :, None], 0.0), axis=1)  # (R, C)
    with jax.named_scope("moe_experts"):
        ms = jnp.broadcast_to(m, (C, R, d))
        g = jnp.einsum("crd,cdf->crf", ms, w_gate,
                       preferred_element_type=_F32)
        u = jnp.einsum("crd,cdf->crf", ms, w_up,
                       preferred_element_type=_F32)
        h = (jax.nn.silu(g) * u).astype(m.dtype)
        ys = jnp.einsum("crf,cfd->crd", h, w_down,
                        preferred_element_type=_F32)
    with jax.named_scope("moe_combine"):
        return jnp.einsum("rc,crd->rd", wc, ys)


def routed_experts(m, wr, w_gate, w_up, w_down, *, top_k: int, live=None,
                   scores=softmax_scores, held=None):
    """``sum_e w_e * W_down,e( silu(W_gate,e m) * W_up,e m )`` over
    those of each row's ``top_k`` experts that are held here.

    m (R, d); wr (d, E); w_gate, w_up (C, d, f); w_down (C, f, d), the
    C experts ``held = (first, C)`` names of the router's E (None: all
    of them, C == E); ``live`` (R,) bool or None (all rows) -> (y
    (R, d) float32, load (C,) int32: assignments per held expert over
    the live rows, elsewhere () int32: the live rows' assignments to
    experts not held).

    ``expert_path`` of the call's shape says which of the two ways
    computes the sum."""
    R, d = m.shape
    E = wr.shape[1]
    first, C = held or (0, E)
    partial = (first, C) != (0, E)
    w, idx = route(m, wr, top_k, scores)
    with jax.named_scope("moe_dispatch"):
        expert_of = idx.reshape(-1)                          # (R*k,)
        if partial:
            here = (idx >= first) & (idx < first + C)
            w = jnp.where(here, w, 0.0)
            # experts held elsewhere sort behind the last held one and
            # index past the (C,) counts, where a scatter drops them
            expert_of = jnp.where(here, idx - first, C).reshape(-1)
        sizes = jnp.zeros((C,), jnp.int32).at[expert_of].add(1)
        load = sizes if live is None else jnp.zeros((C,), jnp.int32).at[
            expert_of].add(jnp.repeat(live.astype(jnp.int32), top_k))
        elsewhere = (R if live is None else jnp.sum(live)) * top_k \
            - jnp.sum(load)
    if expert_path(R, top_k, E) == "dense":
        y = _dense_experts(m, w, expert_of, w_gate, w_up, w_down, top_k)
    else:
        y = _grouped_experts(m, w, expert_of, sizes, w_gate, w_up, w_down,
                             top_k, partial)
    return y, load, elsewhere
