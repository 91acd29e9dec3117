"""Per-kernel-family config spaces for the autotuner.

Each family describes, for one kernel entry point:

- ``shape_names``     — what the dims of a tune shape mean (CLI help
  and table headers);
- ``default_shapes``  — the shapes ``paddle tune`` measures when the
  caller gives none (the sizes the repo's benchmarks exercise);
- ``smoke_shapes``    — tiny shapes for ``--smoke`` (CPU interpret
  mode, tier-1 time budget);
- ``configs(shape)``  — every *valid* candidate config at that shape,
  filtered through the kernel's own ``fits()``/``block_ok()``
  predicate so the search space never proposes a config the dispatch
  layer would reject;
- ``build(shape, dtype, cfg, interpret)`` — a zero-arg callable
  running ``CHAIN`` chained applications of the kernel with the config
  pinned as explicit static args (``cfg=None`` = the hard-coded
  default path, the baseline every speedup is measured against).

Configs are pinned explicitly rather than through a temporary DB so
each candidate gets its own jit trace — DB resolution happens at trace
time and would otherwise be frozen into a cached jaxpr.

This module imports the kernel modules, so the tuning package's
``__init__`` must not import it (kernels lazily import the package for
``lookup()`` — importing spaces there would be a cycle).
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

CHAIN = 4  # sequential in-jit applications per timed call

_POW2_BLOCKS = (64, 128, 256, 512, 1024)


def _divisors(n: int, lo: int = 8, step: int = 8) -> List[int]:
    return [d for d in range(lo, n + 1, 1) if n % d == 0 and d % step == 0]


class Family:
    """One tunable kernel family: its search space and its harness."""

    def __init__(self, name: str, shape_names: Sequence[str],
                 default_shapes: Sequence[Tuple[int, ...]],
                 smoke_shapes: Sequence[Tuple[int, ...]],
                 configs: Callable[[Tuple[int, ...]], List[Dict[str, Any]]],
                 build: Callable[..., Callable[[], Any]],
                 default_dtype: str = "float32"):
        self.name = name
        self.shape_names = tuple(shape_names)
        self.default_shapes = [tuple(s) for s in default_shapes]
        self.smoke_shapes = [tuple(s) for s in smoke_shapes]
        self.configs = configs
        self.build = build
        self.default_dtype = default_dtype


def _key(i: int):
    return jax.random.key(i)


def _chain_accumulate(apply, out_shape, args):
    """CHAIN applications folded into one jitted callable; every
    application feeds an f32 accumulator so none can be elided."""
    def run(*a):
        acc = jnp.zeros(out_shape, jnp.float32)
        for _ in range(CHAIN):
            out = apply(*a)
            first = jax.tree_util.tree_leaves(out)[0]
            acc = acc + first.astype(jnp.float32)
        return acc

    jitted = jax.jit(run)
    return lambda: jitted(*args)


# ---------------------------------------------------------------------------
# softmax: (rows, cols) -> block_rows
# ---------------------------------------------------------------------------


def _softmax_configs(shape):
    from paddle_tpu.pallas import softmax as sm

    rows, cols = shape
    return [{"block_rows": br} for br in _POW2_BLOCKS
            if sm.fits(rows, cols, br)]


def _softmax_build(shape, dtype, cfg, interpret):
    from paddle_tpu.pallas import softmax as sm

    rows, cols = shape
    cfg = cfg or {}
    x = jax.random.normal(_key(0), (rows, cols), dtype)
    return _chain_accumulate(
        lambda a: sm._softmax_impl(a, cfg.get("block_rows"), interpret),
        (rows, cols), (x,))


# ---------------------------------------------------------------------------
# flash attention forward: (BH, S, Sk, D) -> (blk_q, blk_k)
# ---------------------------------------------------------------------------


def _flash_configs(shape):
    from paddle_tpu.pallas import flash_attention as fa

    _, s, sk, d = shape
    return [{"blk_q": bq, "blk_k": bk}
            for bq, bk in itertools.product((128, 256, 512, 1024), repeat=2)
            if fa._blocks_ok(s, sk, d, bq, bk)]


def _flash_build(shape, dtype, cfg, interpret):
    from paddle_tpu.pallas import flash_attention as fa

    bh, s, sk, d = shape
    cfg = cfg or {}
    q = jax.random.normal(_key(0), (bh, s, d), dtype)
    k = jax.random.normal(_key(1), (bh, sk, d), dtype)
    v = jax.random.normal(_key(2), (bh, sk, d), dtype)
    scale = d ** -0.5
    return _chain_accumulate(
        lambda a, b, c: fa._flash_fwd_impl(
            a, b, c, False, scale, interpret,
            blk_q=cfg.get("blk_q"), blk_k=cfg.get("blk_k"))[0],
        (bh, s, d), (q, k, v))


# ---------------------------------------------------------------------------
# lstm sequence: (t, b, h) -> block_b (batch blocking)
# ---------------------------------------------------------------------------


def _lstm_configs(shape):
    from paddle_tpu.pallas import lstm as lk

    t, b, h = shape
    # block_b == b is the default whole-batch grid (the baseline)
    return [{"block_b": bb} for bb in _divisors(b)
            if bb != b and lk.block_ok(b, h, bb)]


def _lstm_build(shape, dtype, cfg, interpret):
    from paddle_tpu.pallas import lstm as lk

    t, b, h = shape
    cfg = cfg or {}
    xproj = jax.random.normal(_key(0), (t, b, 4 * h), dtype) * 0.1
    w = jax.random.normal(_key(1), (h, 4 * h), dtype) * 0.1
    bias = jnp.zeros((4 * h,), dtype)
    h0 = jnp.zeros((b, h), dtype)
    c0 = jnp.zeros((b, h), dtype)
    return _chain_accumulate(
        lambda *a: lk._lstm_seq_impl(*a, interpret=interpret,
                                     block_b=cfg.get("block_b"))[0],
        (t, b, h), (xproj, w, bias, h0, c0))


# ---------------------------------------------------------------------------
# ragged paged attention: (S, P, page, H, D) -> (slots_per_block, semantics)
# ---------------------------------------------------------------------------


def _rpa_configs(shape):
    from paddle_tpu.decode import attention as da

    s, p, page, h, d = shape
    out = []
    for sb in (1, 2, 4, 8, 16):
        if not da.block_ok(s, h, d, sb):
            continue
        for sem in ("parallel", "arbitrary"):
            if sb == 1 and sem == "parallel":
                continue  # that IS the default baseline
            out.append({"slots_per_block": sb, "slot_semantics": sem})
    return out


def _rpa_build(shape, dtype, cfg, interpret):
    from paddle_tpu.decode import attention as da

    s, p, page, h, d = shape
    cfg = cfg or {}
    npages = s * p + 1
    q = jax.random.normal(_key(0), (s, h, d), dtype)
    kp = jax.random.normal(_key(1), (npages, page, h, d), dtype)
    vp = jax.random.normal(_key(2), (npages, page, h, d), dtype)
    ptab = jnp.arange(s * p, dtype=jnp.int32).reshape(s, p)
    lens = jnp.full((s,), p * page, jnp.int32)
    return _chain_accumulate(
        lambda *a: da.ragged_paged_attention(
            *a, interpret=interpret,
            slots_per_block=cfg.get("slots_per_block"),
            slot_semantics=cfg.get("slot_semantics")),
        (s, h, d), (q, kp, vp, ptab, lens))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


SPACES: Dict[str, Family] = {
    "softmax": Family(
        "softmax", ("rows", "cols"),
        default_shapes=[(8192, 512), (4096, 1024)],
        smoke_shapes=[(512, 128)],
        configs=_softmax_configs, build=_softmax_build),
    "flash_attention": Family(
        "flash_attention", ("bh", "s", "sk", "d"),
        default_shapes=[(8, 2048, 2048, 128)],
        smoke_shapes=[(2, 256, 256, 8)],
        configs=_flash_configs, build=_flash_build),
    "lstm": Family(
        "lstm", ("t", "b", "h"),
        default_shapes=[(64, 64, 512)],
        smoke_shapes=[(4, 16, 128)],
        configs=_lstm_configs, build=_lstm_build),
    "ragged_paged_attention": Family(
        "ragged_paged_attention", ("s", "p", "page", "h", "d"),
        default_shapes=[(64, 8, 16, 8, 128)],
        smoke_shapes=[(8, 2, 8, 2, 8)],
        configs=_rpa_configs, build=_rpa_build),
}
