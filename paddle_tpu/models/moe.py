"""A routed (sparse mixture-of-experts) feed-forward layer.

``routed_experts`` is the whole layer as one pure function of the rows
and the stacked expert weights: float32 router scores by the block's
scoring rule (``softmax_scores``: OLMoE's; ``sigmoid_scores``: the
DeepSeek-V3 router K-EXAONE uses), top-k, then the sum over each row's
chosen experts.  What ONE expert computes is the block's to say too
(``form``): ``SWIGLU``, three matrices, ``W_down(silu(W_gate m) * W_up
m)``, every model's but one; ``RELU2``, two, ``W_down relu(W_up m)^2``
(Nemotron-H's).  The sum is computed in one of two ways that
``expert_path`` picks from the call's static shape at trace time (no
option selects one):

- ``grouped``, a call of many rows (a prefill bucket over
  ``DENSE_MAX_ROWS``) or of so few that most experts get none (a step
  of a few slots): a stable sort of the ``rows x k`` assignments by
  expert, the grouped GEMMs over sorted rows (``pallas/grouped_gemm.py``
  on a TPU: the matrices in front of the activation in one call whose
  epilogue applies it, then down, each streaming the
  matrices of the experts that were hit once; ``jax.lax.ragged_dot``,
  its reference, a projection elsewhere and under ``pallas.enable(False)``),
  and the weighted sum of each row's results.  How many sorted rows a
  pass takes is ``grouped_block_rows`` of the static shape: all ``rows
  x k`` when every expert is held, a quarter of that on a chip that
  holds 16 of 128 (below).
- ``dense``, a call in between (a decode step of tens of slots, a
  verify chunk, a short bucket): every row through every held expert
  as matmuls batched over the experts, the routing weight (0 where a
  row did not choose the expert) doing the selecting.  It IS C masked
  dense matmuls, and that is the faster way to read the same bytes:
  such a call's rows hit nearly every held expert, so either way reads
  all of their weights once, and a plain matmul streams them at 84-87%
  of the chip's bandwidth where a grouped GEMM, whose row tiles hold
  about four real rows each at such a size, does not (PERF.md section
  6, PRs 34 and 47).  The extra arithmetic (C / k times the grouped
  pass's) hides under the stream.

Fixed shapes: every row routes, whatever it holds; the ``live`` mask
decides which rows the returned load counts, and on the grouped path of
a chip that holds part of the experts also which rows are computed (a
row that is not live, a bucket's padding, gets 0 there and no live row
reads it).

The router always scores its full width.  A chip of an expert-parallel
deployment is told which experts it holds (``held``: the first and the
count of a contiguous range of the router's columns) and computes only
its own experts' part of each row's sum: the assignments that went to
experts held elsewhere weigh nothing.  On the grouped path they sort
behind the held ones, and the gather, the grouped GEMMs and the sum run
over the held ones alone, in blocks of ``grouped_block_rows`` sorted
rows (about twice what even routing sends to this chip) and as many
blocks as the data needs: one under any routing near even, ``rows x k``
over the block's rows when every row chooses only experts held here, so
the sum is exact for any routing and its cost follows what is held
(PERF.md section 6, PR 38).  What the other chips would add is not
computed and nothing stands in for them.

The four ``jax.named_scope``s (``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``) put every instruction of the layer
under a name in the compiled program's ``op_name`` (inside a share's
loop over blocks as ``.../while/body/moe_experts/...``); a router with
a group step (``kept_groups``: DeepSeek-V3's ``n_group`` /
``topk_group``) runs it under ``moe_group`` inside ``moe_dispatch``.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu import pallas as _pallas
from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.pallas import expert_acts
from paddle_tpu.pallas import grouped_gemm as _gg

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


_M_GROUPED_ROWS = _metrics.counter(
    "moe_grouped_rows_total",
    "sorted assignments of the routed layers run on the grouped path, "
    "summed over layers: rows=\"assigned\" (live rows' assignments to "
    "experts held here: some group's) and rows=\"computed\" (what the "
    "grouped GEMMs ran over: blocks x the block's rows); assigned over "
    "computed is how full the blocks were")
_M_GROUPED_BLOCKS = _metrics.counter(
    "moe_grouped_blocks_total",
    "blocks of sorted assignments the grouped path ran, summed over "
    "layers; over the routed layers run grouped (moe_expert_path_total) "
    "it says how often one block was not enough")


_M_GROUPS = _metrics.counter(
    "moe_groups_chosen_total",
    "live rows that kept an expert group at the router's group step "
    "(n_group / topk_group), by group, summed over the routed layers; "
    "its largest group over the mean is how uneven the group step was")


def count_groups(phase: str, group_load: np.ndarray) -> None:
    """Feed ``moe_groups_chosen_total`` from one call's (layers,
    n_group) count of the live rows that kept each group."""
    for group, rows in enumerate(group_load.sum(axis=0)):
        _M_GROUPS.inc(int(rows), group=str(group), phase=phase)


def count_load(phase: str, load: np.ndarray, rows: int, top_k: int,
               experts: int, elsewhere: int = 0) -> None:
    """Feed the registry from one call's (layers, held experts) load,
    the call's static shape (``rows`` each choosing ``top_k`` of the
    router's ``experts``: what ``expert_path`` and ``grouped_block_rows``
    chose by) and its count of assignments that went to experts held
    elsewhere."""
    path = expert_path(rows, top_k, experts)
    assigned = int(load.sum())
    _M_PATH.inc(load.shape[0], path=path, phase=phase)
    _M_ASSIGNMENTS.inc(assigned, phase=phase)
    _M_EXPERTS_HIT.inc(int((load > 0).sum()), phase=phase)
    _M_LOAD_MAX.inc(int(load.max(axis=-1).sum()), phase=phase)
    if elsewhere:
        _M_ELSEWHERE.inc(int(elsewhere), phase=phase)
    if path == "grouped":
        # what the device's loop did, from the load it handed back
        # (every expert held: one block of rows x top_k a layer)
        block_rows = grouped_block_rows(rows, top_k, load.shape[1], experts)
        blocks = int(grouped_blocks(load.sum(axis=-1), block_rows).sum())
        _M_GROUPED_BLOCKS.inc(blocks, phase=phase)
        _M_GROUPED_ROWS.inc(assigned, rows="assigned", phase=phase)
        _M_GROUPED_ROWS.inc(blocks * block_rows, rows="computed",
                            phase=phase)


class ExpertForm(NamedTuple):
    """What one expert computes in front of its down projection, the
    block's to say as the scoring rule is: ``h = act(m W_1, ..)`` of
    the float32 products with the matrices the caller hands in, and
    ``fused``, the grouped GEMM call that computes ``h`` over sorted
    rows with ``act`` as its epilogue."""

    act: Callable
    fused: Callable


SWIGLU = ExpertForm(expert_acts.swiglu, _gg.gate_up)
RELU2 = ExpertForm(expert_acts.relu2, _gg.up)


def softmax_scores(logits):
    """OLMoE's rule.  Of the router's logits (R, E) -> (what ranks the
    experts, what weighs them, the weights of the chosen (R, k) values
    of the latter): the softmax over all experts for both, taken as it
    is (not renormalised)."""
    p = jax.nn.softmax(logits, axis=-1)
    return p, p, lambda chosen: chosen


def sigmoid_scores(bias, scale: float, eps: float = 0.0):
    """The DeepSeek-V3 rule, as a function of the layer's selection
    ``bias`` (E,): ``s = sigmoid(logits)``; experts are ranked by ``s +
    bias`` and weighed by the unbiased ``s``, a row's weights being
    ``scale * s / (sum over its k chosen + eps)`` (all of them, wherever
    they are held; ``eps`` 0 unless the model's rule has one: LFM2's
    1e-6)."""
    def rule(logits):
        s = jax.nn.sigmoid(logits)

        def weights_of(chosen):
            total = jnp.sum(chosen, axis=-1, keepdims=True)
            return scale * chosen / (total + eps if eps else total)

        return s + bias.astype(_F32), s, weights_of
    return rule


def kept_groups(rank_by, n_group: int, topk_group: int):
    """The group step of DeepSeek-V3's router (``n_group`` /
    ``topk_group``).  The experts lie in ``n_group`` groups of equal
    size, side by side; a group's score is the sum of its two largest
    entries of ``rank_by`` (R, E), and each row keeps its ``topk_group``
    best groups (a tie goes to the lower group) -> (R, n_group) bool."""
    R, E = rank_by.shape
    best_two, _ = jax.lax.top_k(rank_by.reshape(R, n_group, E // n_group), 2)
    _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    return jnp.any(groups[..., None] == jnp.arange(n_group), axis=1)


def _route(m, wr, top_k, scores, groups):
    """``route``, and the (R, n_group) bool of the groups each row kept
    (None without a group step)."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(m, wr, preferred_element_type=_F32)
        rank_by, weigh_by, weights_of = scores(logits)
    with jax.named_scope("moe_dispatch"):
        kept, ranked = None, rank_by
        if groups is not None and groups[0] > 1:
            n_group, topk_group = groups
            with jax.named_scope("moe_group"):
                kept = kept_groups(rank_by, n_group, topk_group)
                ranked = jnp.where(
                    jnp.repeat(kept, rank_by.shape[1] // n_group, axis=1),
                    rank_by, -jnp.inf)
        top, idx = jax.lax.top_k(ranked, top_k)
        if weigh_by is not ranked:
            top = jnp.take_along_axis(weigh_by, idx, axis=-1)
        return weights_of(top), idx, kept


def route(m, wr, top_k: int, scores=softmax_scores, groups=None):
    """Router of rows ``m`` (R, d) over ``wr`` (d, E): the float32
    scores of all experts by the rule ``scores``, the ``top_k`` largest
    per row of what it ranks by (a tie goes to the lower expert index),
    and the weights it makes of what it weighs by -> (weights (R, k)
    f32, experts (R, k) int32).  ``groups``: ``(n_group, topk_group)``,
    the ``top_k`` taken among the experts of the groups ``kept_groups``
    keeps (under ``moe_group``); None or one group: every expert
    stands, and the program is what it was."""
    return _route(m, wr, top_k, scores, groups)[:2]


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
    arithmetic, but its row tiles hold a handful of real rows each
    until a call has thousands, its sort, gather and scatter-add cost
    what the dense pass has no need of, and it takes 1.3 to 1.5 times
    the time its bytes need (``ragged_dot`` took 2 to 4 times, PERF.md
    section 6, PR 47).  So the dense pass is the faster one between two
    edges: the rows must be many enough to hit nearly every expert
    anyway (a step of a few slots reads a few experts, and should), and
    few enough for its arithmetic to hide under the stream (up to some
    two hundred rows) or at least to stay under the grouped GEMM's
    excess."""
    hits_nearly_all = rows * top_k >= DENSE_MIN_PER_EXPERT * experts
    return ("dense" if hits_nearly_all and rows <= DENSE_MAX_ROWS
            else "grouped")


# A block of sorted assignments is a whole number of the grouped GEMM
# kernel's row tiles, in pairs: the 256 rows the blocks were cut to when
# ``ragged_dot``'s tile set them (PR 38), kept so that no block's size
# moved when the kernel's 128 rows took over (PERF.md section 6, PR 47).
GROUPED_ROW_TILE = 2 * _gg.ROW_TILE


def grouped_block_rows(rows: int, top_k: int, held: int, experts: int) -> int:
    """How many sorted assignments one pass of the grouped GEMMs takes
    on a chip that holds ``held`` of the router's ``experts``: about
    twice what even routing sends here, ``2 rows top_k held / experts``,
    rounded up to the kernel's row tile and never over ``rows x
    top_k``, which is what it is when every expert is held.  Where a
    chip holds part of the experts and ``rows x top_k`` is a row tile or
    more but no whole number of them (32 slots x 6: 192), "never over"
    is that number rounded UP to whole row tiles, so that the block is
    one the kernel takes (the rows behind the assignments are no
    group's): at 192 rows ``jax.lax.ragged_dot`` read 5.8 times its
    bytes' time (PERF.md section 6, PR 64).  A function
    of the call's static shape alone, like ``expert_path``."""
    full = rows * top_k
    want = -(-2 * full * held // experts)
    if held != experts and full >= _gg.ROW_TILE:
        full = -(-full // _gg.ROW_TILE) * _gg.ROW_TILE
    return min(full, -(-want // GROUPED_ROW_TILE) * GROUPED_ROW_TILE)


def grouped_blocks(held_assignments, block_rows: int):
    """Blocks a grouped call runs for ``held_assignments`` sorted rows
    (a number or an array of them): the loop's trip count, computed the
    same way on the host by ``count_load``."""
    return -(-held_assignments // block_rows)


def _grouped_gemms(xs, sizes, w_in, w_down, form):
    """Each expert (``form`` of the matrices ``w_in``, then ``w_down``)
    over its group of the sorted rows ``xs``
    (``sizes`` rows a group, in order; rows behind the last group are
    no group's and what comes out for them means nothing) -> float32.
    Float32 products of the operands as stored, ``h`` rounded to the
    rows' dtype in between, by the kernel or by its reference."""
    _, d, f = w_in[0].shape
    with jax.named_scope("moe_experts"):
        if _pallas.use_grouped_gemm(xs.dtype, w_in[0].dtype, xs.shape[0],
                                    d, f):
            # one walk over the sorted rows for both calls
            kw = dict(walk=_gg.visits(sizes, xs.shape[0], _gg.ROW_TILE),
                      interpret=_pallas.interpret_mode())
            h = form.fused(xs, *w_in, sizes, **kw)
            return _gg.grouped_gemm(h, w_down, sizes, **kw)
        h = form.act(*(_gg.grouped_gemm_reference(xs, w, sizes)
                       for w in w_in)).astype(xs.dtype)
        return _gg.grouped_gemm_reference(h, w_down, sizes)


def _grouped_experts(m, w, expert_of, sizes, w_in, w_down, top_k, form):
    """Every expert held: a stable sort of the ``R x k`` assignments by
    expert, one grouped GEMM a projection over all of the sorted rows
    (each is some group's), un-sort, weighted sum."""
    R, d = m.shape
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(expert_of, stable=True)
        xs = m[order // top_k]                               # sorted rows
    ys = _grouped_gemms(xs, sizes, w_in, w_down, form)
    with jax.named_scope("moe_combine"):
        back = jnp.zeros((R * top_k,), jnp.int32).at[order].set(
            jnp.arange(R * top_k, dtype=jnp.int32))
        return jnp.einsum("rk,rkd->rd", w, ys[back].reshape(R, top_k, d))


def _grouped_held_experts(m, w, expert_of, sizes, w_in, w_down, top_k,
                          block_rows, form):
    """Part of the experts held: the same sum over the assignments that
    are some held expert's group, and over nothing else.  Those sort
    first (``expert_of`` is C for the others); they are taken in blocks
    of ``block_rows`` sorted rows, as many blocks as the data needs
    (``grouped_blocks``: one, under routing anywhere near even), so the
    result is exact for any routing, a call whose rows all choose held
    experts included, at a cost that follows what is held.  A block
    gathers its ``(B, d)`` rows, runs the grouped GEMMs with the
    group sizes clipped to the block, and adds its weighted ``(B, d)``
    result into the ``(R, d)`` output by row: nothing of ``R x k`` rows
    by ``d`` columns exists."""
    R, d = m.shape
    B, n = block_rows, R * top_k
    with jax.named_scope("moe_dispatch"):
        order = jnp.argsort(expert_of, stable=True)
        # whole blocks: a dynamic slice past the end would be moved back
        order = jnp.pad(order, (0, -n % B))
        ends = jnp.cumsum(sizes)
        starts, held = ends - sizes, ends[-1]
        w_flat = w.reshape(-1)

    def block(b, y):
        lo = b * B
        with jax.named_scope("moe_dispatch"):
            mine = jax.lax.dynamic_slice(order, (lo,), (B,))
            row = mine // top_k
            in_a_group = lo + jnp.arange(B, dtype=jnp.int32) < held
            of_block = (jnp.clip(ends, lo, lo + B)
                        - jnp.clip(starts, lo, lo + B))
            xs = m[row]
        ys = _grouped_gemms(xs, of_block, w_in, w_down, form)
        with jax.named_scope("moe_combine"):
            # the rows behind the last group are no group's: whatever
            # the kernel left there is not read
            ys = jnp.where(in_a_group[:, None], w_flat[mine][:, None] * ys,
                           0.0)
            return y.at[row].add(ys)

    return jax.lax.fori_loop(0, grouped_blocks(held, B), block,
                             jnp.zeros((R, d), _F32))


def _dense_experts(m, w, expert_of, w_in, w_down, top_k, form):
    """The same sum with every row through every held expert, as
    matmuls batched over the experts: the weights are read once where
    they lie, and the routing weight, 0 for an expert a row did not
    choose (or that is held elsewhere), does the selecting."""
    R, d = m.shape
    C = w_down.shape[0]
    with jax.named_scope("moe_dispatch"):
        # a row chooses an expert at most once: each sum has one term
        chose = expert_of.reshape(R, top_k, 1) == jnp.arange(C)
        wc = jnp.sum(jnp.where(chose, w[:, :, None], 0.0), axis=1)  # (R, C)
    with jax.named_scope("moe_experts"):
        ms = jnp.broadcast_to(m, (C, R, d))
        h = form.act(*(jnp.einsum("crd,cdf->crf", ms, w_i,
                                  preferred_element_type=_F32)
                       for w_i in w_in)).astype(m.dtype)
        ys = jnp.einsum("crf,cfd->crd", h, w_down,
                        preferred_element_type=_F32)
    with jax.named_scope("moe_combine"):
        return jnp.einsum("rc,crd->rd", wc, ys)


def routed_experts(m, wr, *weights, top_k: int, live=None,
                   scores=softmax_scores, held=None, groups=None,
                   form=SWIGLU):
    """``sum_e w_e * expert_e(m)`` over those of each row's ``top_k``
    experts that are held here; an expert by ``form``: ``SWIGLU``,
    ``W_down,e( silu(W_gate,e m) * W_up,e m )`` of ``weights = (w_gate,
    w_up, w_down)``, or ``RELU2``, ``W_down,e relu(W_up,e m)^2`` of
    ``weights = (w_up, w_down)``.

    m (R, d); wr (d, E); the matrices in front of the activation (C, d,
    f); w_down (C, f, d), the
    C experts ``held = (first, C)`` names of the router's E (None: all
    of them, C == E); ``live`` (R,) bool or None (all rows) -> (y
    (R, d) float32, load (C,) int32: assignments per held expert over
    the live rows, elsewhere () int32: the live rows' assignments to
    experts not held).  With ``groups`` (``route``'s) a fourth: (n_group,)
    int32, the live rows that kept each group.

    ``expert_path`` of the call's shape says which of the two ways
    computes the sum, and ``grouped_block_rows`` over how many sorted
    assignments at a time the grouped one runs."""
    R, d = m.shape
    E = wr.shape[1]
    *w_in, w_down = weights
    first, C = held or (0, E)
    partial = (first, C) != (0, E)
    path = expert_path(R, top_k, E)
    # part of the experts held, grouped: a row that is not live (a
    # bucket's padding right of the prompt; no live row reads it) is no
    # group's, as if it had chosen experts held elsewhere
    live_groups = partial and path == "grouped" and live is not None
    w, idx, kept = _route(m, wr, top_k, scores, groups)
    with jax.named_scope("moe_dispatch"):
        expert_of = idx.reshape(-1)                          # (R*k,)
        if partial:
            here = (idx >= first) & (idx < first + C)
            if live_groups:
                here &= live[:, None]
            w = jnp.where(here, w, 0.0)
            # experts held elsewhere sort behind the last held one and
            # index past the (C,) counts, where a scatter drops them
            expert_of = jnp.where(here, idx - first, C).reshape(-1)
        sizes = jnp.zeros((C,), jnp.int32).at[expert_of].add(1)
        load = sizes if live is None or live_groups else jnp.zeros(
            (C,), jnp.int32).at[expert_of].add(
                jnp.repeat(live.astype(jnp.int32), top_k))
        elsewhere = (R if live is None else jnp.sum(live)) * top_k \
            - jnp.sum(load)
    if path == "dense":
        y = _dense_experts(m, w, expert_of, w_in, w_down, top_k, form)
    elif partial:
        y = _grouped_held_experts(
            m, w, expert_of, sizes, w_in, w_down, top_k,
            grouped_block_rows(R, top_k, C, E), form)
    else:
        y = _grouped_experts(m, w, expert_of, sizes, w_in, w_down, top_k,
                             form)
    if kept is None:
        return y, load, elsewhere
    with jax.named_scope("moe_dispatch"):
        if live is not None:
            kept &= live[:, None]
        return y, load, elsewhere, jnp.sum(kept.astype(jnp.int32), axis=0)
