"""Nemotron-H (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
nemotron_h) behind ``/generate``: one chip's share of an expert-parallel
deployment, every published layer.

**A layer is ONE part**, ``x <- x + part(RMSNorm(x; w_in))`` (one norm a
layer, eps 1e-5), the part by the layer's letter in the published
``hybrid_override_pattern``; after the last layer a final RMSNorm and
the untied head:

- ``M``, **Mamba-2**: Granite's layer (``models/granite_hybrid.py``
  writes the equations) at Granite's head shape, 64 heads of 64 channels
  on a state of 128, but in ``n_groups`` 8 GROUPS: heads ``8g .. 8g + 7``
  read ``B_g`` and ``C_g``, the conv runs over 4,096 + 2 x 8 x 128 =
  6,144 channels, and the gated norm runs over each group's 512 channels
  on its own.  The functions are Granite's, given groups (``chunked_ssd``
  over a prompt, ONE ``pallas/ssd_step.py`` call a layer over a step's
  entries, ``pallas/conv_step.py`` over the tails); this block IS
  ``GraniteHybridBlock`` with ``mamba_n_groups`` 8 and every multiplier 1.
- ``*``, **attention**: 32 query heads on 2 K/V heads of 128 (16 query
  heads a K/V head), no bias, no q/k norm, NO rotation, causal softmax
  of ``q.k x 128^-1/2``: the flash kernel over a prompt, the grouped
  walk over the page run at a step (``decode/attention.py``), a page's
  row two heads of 128 lanes.
- ``E``, **experts**: ``s = sigmoid(W_r m)`` float32 over the published
  128; the 6 largest of ``s + b`` chosen; weights ``2.5 s_e / (sum of
  the 6 chosen s + 1e-20)``; an expert is TWO matrices, ``W_down
  relu(W_up m)^2`` (``moe.RELU2``), 1,856 wide; beside them ONE shared
  expert of the same form 3,712 wide on every row (``moe_shared``).
  This chip holds experts ``held`` of each layer and computes their part
  of each row's sum (``models/moe.py``); nothing stands in for the rest.

The skeleton's loops ask every layer for a mixer and then for a
feed-forward; this block answers for the part a layer does not have
with the rows as they came (``decode/model.py`` says how the scopes and
the reports follow).  An ``E`` layer keeps nothing of a sequence.

**An expert's matrices are stored at 1,920 columns** (``stored_width``):
``W_up`` with 64 zero columns behind the published 1,856, ``W_down``
with 64 zero rows; ``relu(0)^2 = 0`` times a zero row adds exact zeros.
Stored as published, ``pallas/grouped_gemm.py:fits`` refuses the width
(14.5 tiles of lanes) and every bucket's grouped GEMMs fall to
``jax.lax.ragged_dot``.

The cache is ``decode/state_entry.py``'s: the 23 Mamba-2 layers' states
and conv tails ONE entry a sequence, the 6 attention layers' K/V rows a
page run.  A prompt runs in ONE bucket, 8,192 rows at most (``max_len``;
a sequence holds 8,576, the prompt and its answer): chunks over a
Mamba-2 state, a prefix hit, a fork and the speculative verify are
refused by name (``UnsupportedOverState``).

Random weights only: loading a checkpoint is not supported.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.decode.state_entry import (
    StateEntryLM,
    UnsupportedOverState,
    tail_shape,
)
from paddle_tpu.models import moe
from paddle_tpu.models.granite_hybrid import (
    ATTENTION,
    LANES,
    MAMBA,
    GraniteHybridBlock,
    _normal,
    attention_params,
    heads_a_row,
    mamba_params,
)
from paddle_tpu.models.olmoe import _mm, rms_norm

_F32 = jnp.float32
EXPERTS = "experts"
KINDS = {"M": MAMBA, "*": ATTENTION, "E": EXPERTS}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# The standard deviation of a q or k row's numbers (``granite_hybrid.
# QK_ROW_STD`` says what it is for).  A score here is ``q.k x 128^-1/2``
# over 128 numbers, so its standard deviation is this squared: 2.4, the
# spread Granite's 4.5 gives its ``q.k / 64`` over 64.
QK_ROW_STD = 1.55
# The selection bias' standard deviation (``exaone_moe.init_params``).
BIAS_STD = 0.02
# The 1e-20 the published router adds to the sum of the chosen scores.
ROUTER_EPS = 1e-20


def layer_kinds(pattern: str) -> tuple:
    """The published ``hybrid_override_pattern`` as ``layer_types``."""
    return tuple(KINDS[c] for c in pattern)


def stored_width(width: int) -> int:
    """The columns an expert's ``W_up`` (rows of its ``W_down``) is
    stored at: whole 128-lane tiles, zeros behind the published ones."""
    return -(-width // LANES) * LANES


def relu2_expert(m, w_up, w_down):
    """``W_down relu(W_up m)^2``: float32 products, ``h`` in the
    weights' dtype in between (as ``exaone_moe.swiglu``)."""
    return _mm(moe.RELU2.act(_mm(m, w_up)).astype(w_down.dtype), w_down)


@dataclasses.dataclass(frozen=True)
class NemotronHBlock(GraniteHybridBlock):
    """``GraniteHybridBlock`` (the Mamba-2 and attention layers, the
    packed pages, the cache side) with layers that are one part alone,
    the routed relu^2 experts and an untied head.  ``experts``: the
    router's published width; ``held``: (first, count) of the experts
    held here."""

    layer_types: tuple = layer_kinds(PATTERN)
    kv_heads: int = 2
    head_dim: int = 128
    pack: int = 1
    mamba_n_groups: int = 8
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 128 ** -0.5
    logits_scaling: float = 1.0
    full_pages: int = 67
    top_k: int = 6
    scale: float = 2.5
    experts: int = 128
    held: tuple = (0, 16)

    @property
    def experts_alone(self) -> bool:
        return self.layer_types[self.at] == EXPERTS

    def embed(self, params, tokens, pos):
        return params["emb"][tokens].astype(_F32)

    def head(self, params, x):
        return _mm(rms_norm(x, params["w_f"], self.eps), params["lm_head"])

    # -- a layer's one part: the other answers with the rows as they came ---

    def prompt_mixer(self, lp, x, pos, heads, live, kept=(), last=None):
        if self.experts_alone:
            return x, None
        return super().prompt_mixer(lp, x, pos, heads, live, kept, last)

    def mixer(self, lp, x, pos, cache, li, addr, heads, lone=False):
        if self.experts_alone:
            return x, cache
        return super().mixer(lp, x, pos, cache, li, addr, heads, lone)

    def scores(self, lp):
        return moe.sigmoid_scores(lp["b"], self.scale, ROUTER_EPS)

    def router_rows(self, lp, x):
        """What an ``E`` layer's experts and router are fed: (R, d) in
        the weights' dtype."""
        m = rms_norm(x, lp["w_in"], self.eps).astype(lp["w_up"].dtype)
        return m.reshape(-1, m.shape[-1])

    def mlp(self, lp, x, live):
        """An ``E`` layer: the shared expert plus the held routed ones.
        Reports (held experts + 1,) int32: the live rows' assignments
        per held expert, then those that went elsewhere; None from a
        layer that is a mixer alone."""
        if not self.experts_alone:
            return x, None
        m = self.router_rows(lp, x)
        with jax.named_scope("moe_shared"):
            y = relu2_expert(m, lp["ws_up"], lp["ws_down"])
        routed, load, elsewhere = moe.routed_experts(
            m, lp["wr"], lp["w_up"], lp["w_down"], top_k=self.top_k,
            live=None if live is None else live.reshape(-1),
            scores=self.scores(lp), held=self.held, form=moe.RELU2)
        report = jnp.concatenate([load, elsewhere.astype(jnp.int32)[None]])
        return x + (y + routed).reshape(x.shape), report


@functools.partial(jax.jit, static_argnames=(
    "kind", "d", "heads", "kv_heads", "head_dim", "mamba_n_heads",
    "mamba_d_head", "mamba_d_state", "mamba_n_groups", "conv", "width",
    "shared_width", "router_width", "held", "dtype"))
def _init_layer(key, *, kind, d, heads, kv_heads, head_dim, mamba_n_heads,
                mamba_d_head, mamba_d_state, mamba_n_groups, conv, width,
                shared_width, router_width, held, dtype):
    """One layer's parameters: one program a kind of layer."""
    lk = jax.random.split(key, 6)
    lp = {"w_in": jnp.ones((d,), dtype)}
    if kind == ATTENTION:
        lp.update(attention_params(
            lk[:4], d=d, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            qk_row_std=QK_ROW_STD, dtype=dtype))
    elif kind == MAMBA:
        lp.update(mamba_params(
            lk[:5], d=d, heads=mamba_n_heads, head_dim=mamba_d_head,
            d_state=mamba_d_state, groups=mamba_n_groups, conv=conv,
            dtype=dtype))
    else:
        def normal(k, *shape):
            return _normal(k, shape=shape, std=0.02, dtype=dtype)

        pad = stored_width(width) - width
        lp.update(
            wr=normal(lk[0], d, router_width),
            b=jax.random.normal(lk[1], (router_width,), _F32) * BIAS_STD,
            w_up=jnp.pad(normal(lk[2], held, d, width),
                         ((0, 0), (0, 0), (0, pad))),
            w_down=jnp.pad(normal(lk[3], held, width, d),
                           ((0, 0), (0, pad), (0, 0))),
            ws_up=normal(lk[4], d, shared_width),
            ws_down=normal(lk[5], shared_width, d))
    return lp


def init_params(key, *, vocab, layer_types, **sizes):
    """Every matrix N(0, 0.02) in ``dtype`` but an attention layer's q
    and k projections (``QK_ROW_STD``), every norm scale 1; the Mamba-2
    layers' recurrence parameters, conv taps and bias as Granite's are
    drawn (``granite_hybrid.init_params`` says why: dropping the decay,
    the conv or a group's B then moves the logits); the router's
    selection bias N(0, ``BIAS_STD``) float32.  **``W_up`` at 0.02 is
    1.04 x 2,688^-1/2**: an expert's pre-activation over a normed row is
    N(0, 1.04^2), so half of its 1,856 numbers are rectified away and
    the square of the rest has mean 0.54 and stays under ~30 (5.5
    standard deviations): relu^2 neither dies nor leaves bfloat16's
    range, nor float8's 448 in the reference rounded below.  Made on the
    device, a layer at a time by one program a kind of layer."""
    d, dtype = sizes["d"], sizes["dtype"]
    ks = jax.random.split(key, 2 + len(layer_types))
    return {"emb": _normal(ks[0], shape=(vocab, d), std=0.02, dtype=dtype),
            "w_f": jnp.ones((d,), dtype),
            "lm_head": _normal(ks[1], shape=(d, vocab), std=0.02,
                               dtype=dtype),
            "layers": [_init_layer(k, kind=kind, **sizes)
                       for k, kind in zip(ks[2:], layer_types)]}


class NemotronHLM(StateEntryLM):
    """Nemotron-H's share of one chip over the paged skeleton: what
    ``make_decode_model()`` returns
    (``perf/configs/nemotron-3-nano-30b-a3b.gen_config.py``).  The
    reservation (the run's pages, then ONE state entry), the table row
    and the refusals are ``decode/state_entry.py``'s.

    ``max_len``: the longest PROMPT, the top bucket's rows (8,192); a
    sequence holds ``pages_per_seq x page_size`` rows (8,576), so a
    prompt of the top bucket still decodes."""

    def __init__(self, vocab: int = 16384, d_model: int = 2688,
                 num_heads: int = 32, num_kv_heads: int = 2,
                 head_dim: int = 128, pattern: str = PATTERN,
                 mamba_num_heads: int = 64, mamba_head_dim: int = 64,
                 ssm_state_size: int = 128, n_groups: int = 8,
                 conv_kernel: int = 4, expert_width: int = 1856,
                 shared_width: int = 3712, num_experts_published: int = 128,
                 held_experts=(0, 16), experts_per_tok: int = 6,
                 routed_scaling_factor: float = 2.5,
                 layer_norm_epsilon: float = 1e-5, max_len: int = 8192,
                 num_pages: int = 64, page_size: int = 128,
                 pages_per_seq: int = 67, state_entries: int = 33,
                 dtype="bfloat16", bos_id: int = 1, eos_id: int = -1,
                 seed: int = 0):
        layer_types = layer_kinds(pattern)
        super().__init__(vocab, d_model, num_heads, len(layer_types),
                         max_len, page_size, pages_per_seq, bos_id, eos_id)
        if num_heads % num_kv_heads:
            raise ValueError("the K/V heads have to divide the query heads")
        if int(mamba_num_heads) % int(n_groups):
            raise ValueError("the groups have to divide the mamba heads")
        held = tuple(int(x) for x in held_experts)
        if not 0 <= held[0] <= held[0] + held[1] <= num_experts_published:
            raise ValueError("held_experts: a range of the router's width")
        self.dh, self.kv_heads = int(head_dim), int(num_kv_heads)
        self._count_layers([t for t in layer_types if t != EXPERTS], MAMBA)
        H, P, N = int(mamba_num_heads), int(mamba_head_dim), int(
            ssm_state_size)
        G = int(n_groups)
        pack = heads_a_row(self.kv_heads, self.dh)
        state_pack = heads_a_row(H, P)
        self.block = NemotronHBlock(
            layer_types=layer_types, kv_heads=self.kv_heads,
            head_dim=self.dh, pack=pack, state_pack=state_pack,
            mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N,
            mamba_n_groups=G, eps=float(layer_norm_epsilon),
            attention_multiplier=self.dh ** -0.5,
            full_pages=self.full_pages, top_k=int(experts_per_tok),
            scale=float(routed_scaling_factor),
            experts=int(num_experts_published), held=held)
        dtype = jnp.dtype(dtype)
        self.conv_taps = int(conv_kernel)
        self.params = init_params(
            jax.random.key(seed), vocab=self.vocab, layer_types=layer_types,
            d=self.d, heads=self.heads, kv_heads=self.kv_heads,
            head_dim=self.dh, mamba_n_heads=H, mamba_d_head=P,
            mamba_d_state=N, mamba_n_groups=G, conv=self.conv_taps,
            width=int(expert_width), shared_width=int(shared_width),
            router_width=int(num_experts_published), held=held[1],
            dtype=dtype)
        # a page's row as the gauges count it: the published K/V heads
        self.stored_heads = self.kv_heads
        self._make_pools(
            num_pages, dtype, int(state_entries),
            (self.kv_heads // pack, pack * self.dh),
            (H // state_pack, N, state_pack * P),
            tail_shape(self.conv_taps, H * P + 2 * G * N))

    def prefill_bucket(self, n: int) -> int:
        if self.prefill_cap < n <= self.seq_rows:
            raise UnsupportedOverState(
                f"a prompt of {n} rows is over the {self.prefill_cap}-row "
                "top bucket: it would go on in chunks over the Mamba-2 "
                "layers' state entry, and this block's chunked recurrence "
                "does not yet start from the state an entry holds "
                "(StateEntryCache.recurrent_chunk)")
        return super().prefill_bucket(n)

    def _observe(self, phase, report, rows):
        report = np.asarray(report)          # (routed layers, held + 1)
        moe.count_load(phase, report[:, :-1], rows, self.block.top_k,
                       self.block.experts, int(report[:, -1].sum()))
