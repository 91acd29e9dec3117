"""AOT serving artifacts: kill the cold start.

``paddle compile`` exports a model's serving programs — the bucket
ladder every replica would JIT at boot, optionally the decode step —
as serialized XLA executables in a versioned artifact directory
(``artifact.py``).  ``paddle serve --artifacts=DIR`` boots replicas
from that store: the Executor consults it at every compile-cache miss
and, on a manifest match, deserializes instead of tracing+compiling.

Beside the jax persistent compile cache (which skips XLA compilation
but still traces and lowers), this path serializes whole executables
through ``jax.experimental.serialize_executable`` with the donation mask
pinned in the manifest and re-proved at load: a boot neither traces nor
compiles, and donation stays active on artifact-booted replicas.  Any mismatch — version skew, device kind,
fingerprint drift, corrupt payload, donation drift —
is a loud JIT fallback counted in ``aot_load_total{result}``: slower,
never wrong.

Two attachment surfaces:

- per-Executor: ``executor.aot_store = store`` (the serving replica
  pool wires each replica this way — no process-global state);
- process-global ``attach(store)`` — for paths that build executors
  deep inside a model (the paged decode engine) where threading a
  store handle through would touch every layer.

``capture(writer)`` is the export side: inside the context every
compile miss is lowered AOT, serialized, and recorded.
"""

from __future__ import annotations

import contextlib
from typing import Optional

from paddle_tpu.aot.artifact import (
    ArtifactStore,
    ArtifactWriter,
    MANIFEST_NAME,
    SCHEMA,
)
from paddle_tpu.aot.export import export_generator, export_model

__all__ = [
    "ArtifactStore", "ArtifactWriter", "MANIFEST_NAME", "SCHEMA",
    "active_exporter", "active_store", "attach", "capture", "detach",
    "export_generator", "export_model",
]

_ACTIVE_STORE: Optional[ArtifactStore] = None
_ACTIVE_EXPORTER: Optional[ArtifactWriter] = None


def attach(store: ArtifactStore) -> ArtifactStore:
    """Make ``store`` the process-global artifact store every Executor
    consults on a compile miss (executors with an explicit
    ``aot_store`` attribute keep their own)."""
    global _ACTIVE_STORE
    _ACTIVE_STORE = store
    return store


def detach() -> None:
    global _ACTIVE_STORE
    _ACTIVE_STORE = None


def active_store() -> Optional[ArtifactStore]:
    return _ACTIVE_STORE


def active_exporter() -> Optional[ArtifactWriter]:
    return _ACTIVE_EXPORTER


@contextlib.contextmanager
def capture(writer: ArtifactWriter):
    """Every Executor compile miss inside the context is exported into
    ``writer`` (and the captured AOT executable is what actually runs,
    so the export is validated by execution, not just serialization)."""
    global _ACTIVE_EXPORTER
    prev = _ACTIVE_EXPORTER
    _ACTIVE_EXPORTER = writer
    try:
        yield writer
    finally:
        _ACTIVE_EXPORTER = prev
