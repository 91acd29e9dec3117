"""Persistent tuning database: measured tile configs, checked in.

The TVM lesson ("TVM: An Automated End-to-End Optimizing Compiler for
Deep Learning", PAPERS.md): search over a schedule space with on-device
measurement, then *persist* the winners so dispatch never searches
again.  The store here is one JSON document:

- schema-versioned (``paddle_tpu.tuning_db.v1``) — a loader rejects
  documents from a different schema instead of misreading them;
- keyed by ``kernel|shape-bucket|dtype|device-kind`` where the shape
  bucket rounds every dimension up the serving engine's power-of-two
  ladder (bucket.py), so one measured config covers a bucket;
- written atomically (tmp file + ``os.replace``) and *merged* rather
  than clobbered on re-tune — tuning one kernel never drops another
  kernel's entries.

Dispatch reads through the process-global accessor (``get_db`` /
``lookup`` in ``tuning/__init__``); kernels fall back to their
hard-coded defaults on a miss, so behavior without a database is
bit-identical to an untuned tree.
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

from paddle_tpu.pallas.tuning.bucket import bucket_shape

SCHEMA = "paddle_tpu.tuning_db.v1"

# the checked-in database, shipped next to this module
DEFAULT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tuning_db.json")


def make_key(kernel: str, shape: Sequence[int], dtype: str,
             device_kind: str) -> str:
    """DB key for a *query* shape: the shape is bucketed here, so every
    shape in a bucket resolves to the same entry."""
    dims = "x".join(str(d) for d in bucket_shape(shape))
    return f"{kernel}|{dims}|{dtype}|{device_kind}"


class TuningDB:
    """In-memory view of the tuning document: {key: record}.

    A record is ``{"config": {...}, "time_ms": float,
    "default_time_ms": float, "speedup": float, "interpret": bool,
    "n_configs": int, "n_infeasible": int, "shape": [...]}`` — only
    ``config`` is consumed by dispatch; the rest is provenance the
    speedup tables are built from.
    """

    def __init__(self, entries: Optional[Dict[str, dict]] = None,
                 path: Optional[str] = None):
        self.entries: Dict[str, dict] = dict(entries or {})
        self.path = path

    # -- query ----------------------------------------------------------

    def lookup(self, kernel: str, shape: Sequence[int], dtype: str,
               device_kind: str) -> Optional[Dict[str, Any]]:
        rec = self.entries.get(make_key(kernel, shape, dtype, device_kind))
        if rec is None:
            return None
        cfg = rec.get("config")
        return dict(cfg) if isinstance(cfg, dict) else None

    def __len__(self) -> int:
        return len(self.entries)

    def kernels(self) -> Iterable[str]:
        return sorted({k.split("|", 1)[0] for k in self.entries})

    # -- mutation -------------------------------------------------------

    def put(self, kernel: str, shape: Sequence[int], dtype: str,
            device_kind: str, record: dict) -> str:
        key = make_key(kernel, shape, dtype, device_kind)
        self.entries[key] = dict(record)
        return key

    def merge(self, other: "TuningDB") -> "TuningDB":
        """Fold ``other``'s entries over this DB's (other wins on key
        collision — re-tuned entries replace stale ones)."""
        self.entries.update(other.entries)
        return self

    # -- persistence ----------------------------------------------------

    @classmethod
    def load(cls, path: str) -> "TuningDB":
        """Parse a tuning document.  Raises ``ValueError`` on a schema
        mismatch (a future-schema file must not be half-read) and
        propagates IO/JSON errors — callers that want tolerance use
        ``load_or_empty``."""
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != SCHEMA:
            raise ValueError(
                f"tuning db {path}: schema {doc.get('schema')!r} != "
                f"{SCHEMA!r}; re-run `paddle tune` to regenerate")
        return cls(doc.get("entries", {}), path=path)

    @classmethod
    def load_or_empty(cls, path: str) -> "TuningDB":
        """Dispatch-side loader: a missing/corrupt/foreign-schema file
        degrades to an empty DB (= hard-coded defaults), never a crash."""
        try:
            return cls.load(path)
        except FileNotFoundError:
            return cls(path=path)
        except (ValueError, OSError, json.JSONDecodeError):
            return cls(path=path)

    def save(self, path: Optional[str] = None,
             merge_existing: bool = True) -> str:
        """Atomic write: serialize to a tmp file in the target dir, then
        ``os.replace`` — a reader never sees a torn document.  When the
        target already holds a valid DB, its entries are merged under
        ours first (re-tune updates, never clobbers)."""
        path = path or self.path or DEFAULT_PATH
        entries = self.entries
        if merge_existing and os.path.exists(path):
            try:
                base = TuningDB.load(path)
                entries = dict(base.entries)
                entries.update(self.entries)
            except (ValueError, OSError, json.JSONDecodeError):
                pass  # unreadable target: overwrite with ours
        doc = {"schema": SCHEMA, "entries": dict(sorted(entries.items()))}
        d = os.path.dirname(os.path.abspath(path)) or "."
        fd, tmp = tempfile.mkstemp(prefix=".tuning_db_", suffix=".tmp",
                                   dir=d)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=False)
                f.write("\n")
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        self.path = path
        return path


def normalize_device_kind(kind: str) -> str:
    """'TPU v5 lite' -> 'tpu_v5_lite' (stable DB-key token)."""
    return "_".join(kind.strip().lower().split())


def current_device_kind() -> str:
    import jax

    return normalize_device_kind(jax.devices()[0].device_kind)
