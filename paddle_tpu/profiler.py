"""Profiling (reference: python/paddle/v2/fluid/profiler.py wraps
nvprof; the TPU equivalent is jax.profiler/xprof traces)."""

from __future__ import annotations

import contextlib
import inspect

import jax


def _start_trace_options():
    """Option names ``jax.profiler.start_trace`` accepts beyond the log
    dir (introspected, so this tracks the installed jax version)."""
    try:
        params = inspect.signature(jax.profiler.start_trace).parameters
        return frozenset(list(params)[1:])
    except (TypeError, ValueError):  # builtins/extension fallback
        return frozenset({"create_perfetto_link", "create_perfetto_trace"})


@contextlib.contextmanager
def profiler(output_dir: str = "/tmp/paddle_tpu_profile", **kwargs):
    """Trace context: view with xprof/tensorboard.

    Keyword options are forwarded to ``jax.profiler.start_trace``
    (e.g. ``create_perfetto_link=True``); unknown keys raise instead of
    being silently dropped.
    """
    supported = _start_trace_options()
    unknown = sorted(set(kwargs) - supported)
    if unknown:
        raise TypeError(
            f"profiler(): unsupported option(s) {unknown}; "
            f"jax.profiler.start_trace accepts {sorted(supported)}")
    jax.profiler.start_trace(output_dir, **kwargs)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# reference-compatible alias (fluid.profiler.cuda_profiler)
cuda_profiler = profiler
