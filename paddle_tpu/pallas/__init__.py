"""Hand-written Pallas TPU kernels for hot ops (north star: the
reference's hand-written CUDA kernels — paddle/operators/math/*.cu,
paddle/cuda/src/hl_cuda_lstm.cu etc. — reimplemented for the MXU/VPU).

Policy (PADDLE_TPU_USE_PALLAS, default ``auto``):

- ``auto``: on a TPU backend, the kernels an earlier setup measured
  ahead of their XLA lowering dispatch (benchmark/pallas_bench.py is
  the harness): the fused whole-sequence LSTM at H<=384, the row
  softmax at cols<=256, flash attention at S>=1024.  The thresholds
  come from that earlier setup and are NOT re-measured on the locally
  attached v5e.  The blocked matmul and scalar-prefetch gather lost to
  XLA there and are never auto-dispatched — they remain as tested
  reference kernels and custom-epilogue scaffolds.
- ``1``/``on``: force every kernel on (benchmarking, tests).
- ``0``/``off``: pure XLA lowerings.

Off a TPU the kernels run under ``interpret=True`` for numerics tests.
On a TPU backend a kernel that dispatches runs compiled or raises —
interpret mode is ignored there, and the jnp/XLA reference is chosen
only by ``fits()`` and the mode.  Every dispatch decision is counted at
trace time in ``pallas_dispatch_total{kernel, path}`` (path =
compiled | interpret | reference).
"""

from __future__ import annotations

import os

from paddle_tpu.observability import metrics as _metrics

_M_DISPATCH = _metrics.counter(
    "pallas_dispatch_total",
    "Pallas kernel dispatch decisions, counted at trace time, by kernel "
    "and path (compiled | interpret | reference = the jnp/XLA lowering)")

_MODE_ENV = os.environ.get("PADDLE_TPU_USE_PALLAS", "auto").lower()
_STATE = {
    "mode": {"1": "on", "on": "on", "0": "off", "off": "off"}.get(
        _MODE_ENV, "auto"),
    "interpret": os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "0") == "1",
}


def enable(flag=True, interpret: bool | None = None):
    """enable(True)='on', enable(False)='off', enable('auto')='auto'.
    Strings follow the env convention: '1'/'on', '0'/'off', 'auto'."""
    if isinstance(flag, str):
        norm = {"1": "on", "on": "on", "true": "on",
                "0": "off", "off": "off", "false": "off",
                "auto": "auto"}.get(flag.lower())
        if norm is None:
            raise ValueError(f"pallas.enable: unknown mode {flag!r}")
        _STATE["mode"] = norm
    else:
        _STATE["mode"] = "on" if flag else "off"
    if interpret is not None:
        _STATE["interpret"] = bool(interpret)


def mode() -> str:
    return _STATE["mode"]


def tpu_backend() -> bool:
    """Whether traced work lands on a TPU: the platform of jax's default
    device when one is pinned (``jax.default_device`` — the Executor
    sets it for CPUPlace), else the default backend."""
    import jax

    dev = jax.config.jax_default_device
    platform = (getattr(dev, "platform", dev) if dev is not None
                else jax.default_backend())
    return platform == "tpu"


def interpret_mode() -> bool:
    """Interpret mode is for hosts without a TPU: on a TPU backend a
    dispatched kernel is always compiled, whatever the flag says."""
    return _STATE["interpret"] and not tpu_backend()


def auto_ok() -> bool:
    # auto mode dispatches real kernels only on a TPU backend; interpret
    # mode works off-TPU (CPU numerics tests set it explicitly)
    return _STATE["interpret"] or tpu_backend()


def dispatch(kernel: str, use: bool) -> bool:
    """Count one dispatch decision (trace time) and return it."""
    path = ("reference" if not use
            else "interpret" if interpret_mode() else "compiled")
    _M_DISPATCH.inc(kernel=kernel, path=path)
    return use


def policy(fits: bool, auto: bool) -> bool:
    """fits() gates everything; 'on' forces, 'auto' asks the threshold."""
    if _STATE["mode"] == "off" or not fits:
        return False
    return _STATE["mode"] == "on" or (auto_ok() and auto)


def use_lstm(b: int, h: int) -> bool:
    from paddle_tpu.pallas import lstm as _l

    # earlier setup: XLA won at H>=512
    return dispatch("lstm", policy(_l.fits(b, h), h <= 384))


def use_softmax(rows: int, cols: int) -> bool:
    from paddle_tpu.pallas import softmax as _s

    # earlier setup: XLA won at cols=512
    return dispatch("softmax", policy(_s.fits(rows, cols), cols <= 256))


def use_flash_attention(bh: int, s_q: int, s_k: int, d: int) -> bool:
    """Blocked online-softmax attention.  On an earlier setup it beat
    the jnp softmax(QK^T)V lowering at S>=1024, where the S x S score
    tensor stops fitting cache-friendly fusions; below that XLA's fused
    unblocked attention won on kernel count.  Not re-measured."""
    from paddle_tpu.pallas import flash_attention as _f

    return dispatch("flash_attention", policy(
        _f.fits(1, bh, s_q, d) and s_q == s_k, s_q >= 1024))


def use_batch_norm(rows: int, cols: int) -> bool:
    """Fused BN stats+normalize / BN-grad kernels.  On an earlier setup
    XLA's BN lowering ran at a higher fraction of HBM bandwidth at
    ResNet shapes (and fuses the statistics into the producing conv's
    epilogue inside real models), so the kernels are never
    auto-dispatched — they remain as tested reference kernels."""
    from paddle_tpu.pallas import batch_norm as _b

    return dispatch("batch_norm", policy(_b.fits(rows, cols), False))


def use_conv2d(n: int, h: int, w: int, c: int, o: int, kh: int, kw: int,
               stride: int, padding: int) -> bool:
    """Implicit-GEMM conv kernels (pallas/conv.py).  On an earlier setup
    the XLA conv emitter won at every ResNet-50 hot shape, so the
    kernels are never auto-dispatched; they remain as verified
    scaffolds for fused custom-epilogue experiments."""
    from paddle_tpu.pallas import conv as _c

    return dispatch("conv2d", policy(
        _c.fits(n, h, w, c, o, kh, kw, stride, padding), False))


def use_matmul() -> bool:
    return dispatch("matmul", policy(True, False))  # lost to XLA: never auto


def use_gather() -> bool:
    return dispatch("gather", policy(True, False))  # lost to XLA: never auto


from paddle_tpu.pallas.matmul import matmul as pallas_matmul  # noqa: E402
from paddle_tpu.pallas.softmax import softmax as pallas_softmax  # noqa: E402
from paddle_tpu.pallas.embedding import gather_rows as pallas_gather_rows  # noqa: E402
from paddle_tpu.pallas.lstm import lstm_seq as pallas_lstm_seq  # noqa: E402
from paddle_tpu.pallas.flash_attention import (  # noqa: E402
    flash_attention as pallas_flash_attention)
from paddle_tpu.pallas.batch_norm import (  # noqa: E402
    batch_norm_train as pallas_batch_norm_train)
from paddle_tpu.pallas.conv import conv2d_nhwc as pallas_conv2d_nhwc  # noqa: E402
