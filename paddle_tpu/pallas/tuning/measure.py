"""On-device measurement for the autotuner.

Each timed call runs ``space.CHAIN`` chained kernel applications
inside one jit so the per-dispatch floor amortizes, timings force a
host read, and the reported number is the *best of N* repetitions (min
is the standard autotuner statistic — noise only ever adds time).

Configs that fail to compile or lower are recorded as infeasible
(``Infeasible`` carries the reason), never propagated as a crash: a
search space filtered by ``fits()`` can still hit Mosaic layout limits
the predicates don't model.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from paddle_tpu.pallas.tuning import space as _space


class Infeasible(Exception):
    """The candidate config failed to compile/lower/run."""


def _sync(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def time_call(fn, reps: int = 3, warmup: int = 1) -> float:
    """Best-of-``reps`` milliseconds per single kernel application.

    ``fn`` is a zero-arg callable running ``space.CHAIN`` chained
    applications (a ``Family.build`` product).
    """
    out = None
    for _ in range(max(warmup, 1)):
        out = fn()
    _sync(out)
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        out = fn()
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3 / _space.CHAIN


def measure_config(family: "_space.Family", shape: Tuple[int, ...],
                   dtype: str, cfg: Optional[Dict[str, Any]],
                   interpret: bool = False, reps: int = 3) -> float:
    """Milliseconds for one (shape, config) point; ``cfg=None`` times
    the hard-coded default path.  Raises ``Infeasible`` on any
    compile/lower/run failure."""
    try:
        fn = family.build(shape, dtype, cfg, interpret)
        return time_call(fn, reps=reps)
    except KeyboardInterrupt:
        raise
    except Exception as e:  # XlaRuntimeError, Mosaic errors, asserts...
        raise Infeasible(f"{family.name}{shape} {cfg}: "
                         f"{type(e).__name__}: {e}") from e
