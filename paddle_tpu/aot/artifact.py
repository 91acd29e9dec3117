"""Versioned AOT serving artifacts: manifest + executable store.

An artifact directory is one ``paddle compile`` run::

    <dir>/MANIFEST.json            # schema, environment pins, entries
    <dir>/executables/<id>.bin     # pickled (payload, in_tree, out_tree)
                                   #   from jax.experimental
                                   #   .serialize_executable

Each entry is one compiled executor step, keyed exactly like the
Executor's in-process compile cache: (optimized-program fingerprint,
feed signature, fetch set).  The manifest pins everything that could
make a stored executable wrong or slow to reuse:

- jax / jaxlib versions, backend platform and device kind (an XLA
  binary is not portable across any of these);
- compile-context flags (amp, pallas mode, interpret, trace_ops) —
  the same bits that key the executor cache;
- per entry: the donation mask the analyzer proved at export time.
  The load side re-runs the analysis and REFUSES the entry on drift,
  because the serialized executable's input-output aliasing is baked
  in — running it with a different donation contract would either leak
  the aliasing win or read freed buffers.

Every lookup lands in ``aot_load_total{result=...}``: ``loaded`` or a
``rejected_*`` reason.  A rejection is always a loud JIT fallback —
slower, never wrong.
"""

from __future__ import annotations

import contextlib
import hashlib
import io
import json
import os
import pickle
import sys
from typing import Any, Dict, Optional, Tuple

from paddle_tpu.observability import metrics as _metrics

SCHEMA = "paddle_tpu.aot.v1"
MANIFEST_NAME = "MANIFEST.json"
EXEC_DIR = "executables"

_M_AOT_LOAD = _metrics.counter(
    "aot_load_total",
    "artifact-store lookups by outcome: loaded, or rejected_* (version "
    "skew / device / flags / fingerprint / bucket / corrupt "
    "/ donation drift) — every rejection is a loud JIT fallback")
_M_AOT_EXPORT = _metrics.counter(
    "aot_export_total",
    "executables serialized into an artifact directory by paddle compile")


def sig_json(feed_sig) -> str:
    """Canonical JSON for an Executor ``_feed_signature`` tuple (tuples
    become lists; the string is the manifest's entry key component)."""
    return json.dumps(feed_sig, separators=(",", ":"), sort_keys=False)


def environment_fingerprint(backend: Optional[str] = None) -> Dict[str, str]:
    import jax

    try:
        import jaxlib.version as _jlv

        jaxlib_version = _jlv.__version__
    except Exception:  # pragma: no cover - jaxlib always ships version
        jaxlib_version = "unknown"
    devs = jax.devices(backend) if backend else jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
    }


def flags_fingerprint() -> Dict[str, Any]:
    """The compile-context bits the executor cache keys on (beyond the
    program/feed/fetch triple): flipping any retraces, so an artifact
    exported under different flags must not load."""
    from paddle_tpu import amp
    from paddle_tpu import pallas as pk
    from paddle_tpu.flags import FLAGS

    return {
        "amp": bool(amp.is_enabled()),
        "pallas_mode": str(pk.mode()),
        "pallas_interpret": bool(pk.interpret_mode()),
        "trace_ops": bool(FLAGS.get("trace_ops")),
    }


def _entry_id(program_fp: str, sig: str, fetch_names) -> str:
    h = hashlib.sha256()
    h.update(program_fp.encode())
    h.update(b"\x00")
    h.update(sig.encode())
    h.update(b"\x00")
    h.update(json.dumps(list(fetch_names)).encode())
    return h.hexdigest()[:24]


@contextlib.contextmanager
def compiled_afresh():
    """Compile inside the block without jax's persistent compilation
    cache.  An executable that XLA:CPU loaded from that cache serializes
    into a payload whose functions are gone: the artifact loads, and
    fails at its first run (``NOT_FOUND: Function ... not found``).  So
    what an export serializes is compiled here and now.  jax decides
    once whether it uses the cache, hence the two resets."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


class ArtifactWriter:
    """Accumulates serialized executables + manifest entries; one
    ``paddle compile`` run writes one of these and calls ``finish()``."""

    def __init__(self, out_dir: str, backend: Optional[str] = None):
        self.out_dir = out_dir
        self.backend = backend
        self.entries: Dict[str, dict] = {}
        os.makedirs(os.path.join(out_dir, EXEC_DIR), exist_ok=True)

    def add(self, *, program_fp: str, feed_sig, fetch_names,
            executable, state_names, donated_names, held_names,
            out_state_names, written_names, uses_rng: bool) -> dict:
        """Serialize one ``jax.stages.Compiled`` under its cache key.
        Idempotent per key (warmup may hit the same bucket twice)."""
        from jax.experimental import serialize_executable as _ser

        sig = sig_json(feed_sig)
        eid = _entry_id(program_fp, sig, fetch_names)
        if eid in self.entries:
            return self.entries[eid]
        payload, in_tree, out_tree = _ser.serialize(executable)
        buf = io.BytesIO()
        pickle.dump({"payload": payload, "in_tree": in_tree,
                     "out_tree": out_tree}, buf,
                    protocol=pickle.HIGHEST_PROTOCOL)
        blob = buf.getvalue()
        rel = os.path.join(EXEC_DIR, f"{eid}.bin")
        with open(os.path.join(self.out_dir, rel), "wb") as f:
            f.write(blob)
        entry = {
            "id": eid,
            "program_fp": program_fp,
            "feed_sig": sig,
            "fetch_names": list(fetch_names),
            "state_names": list(state_names),
            "donated_names": list(donated_names),
            "held_names": list(held_names),
            "out_state_names": list(out_state_names),
            "written_names": list(written_names),
            "uses_rng": bool(uses_rng),
            # the devices the executable was compiled for: the load side
            # must name them, or jax loads it over every visible device
            "device_ids": [d.id for d in
                           executable.runtime_executable().local_devices()],
            "file": rel,
            "sha256": hashlib.sha256(blob).hexdigest(),
            "nbytes": len(blob),
        }
        self.entries[eid] = entry
        _M_AOT_EXPORT.inc()
        return entry

    def finish(self, extra: Optional[dict] = None) -> str:
        """Write MANIFEST.json; returns its path."""
        doc = {
            "schema": SCHEMA,
            "env": environment_fingerprint(self.backend),
            "flags": flags_fingerprint(),
            "entries": sorted(self.entries.values(),
                              key=lambda e: e["id"]),
        }
        if extra:
            doc.update(extra)
        path = os.path.join(self.out_dir, MANIFEST_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return path


class ArtifactStore:
    """Read side of an artifact directory.

    Store-level pins (schema, versions, device, flags) are
    validated once at open; a mismatch poisons the store — every lookup
    then counts its ``rejected_<reason>`` and falls back to JIT.
    Entry-level problems (unknown fingerprint, missing bucket, corrupt
    payload, donation drift) reject per lookup.  ``results`` mirrors
    the global ``aot_load_total`` series for this store instance, so
    tests and the CLI can assert without diffing process metrics."""

    def __init__(self, root: str):
        self.root = root
        self.poisoned: Optional[str] = None
        self.entries: Dict[Tuple[str, str, Tuple[str, ...]], dict] = {}
        self.fingerprints: set = set()
        self.results: Dict[str, int] = {}
        self.manifest: Optional[dict] = None
        self._warned: set = set()
        try:
            with open(os.path.join(root, MANIFEST_NAME)) as f:
                self.manifest = json.load(f)
        except Exception as exc:
            self.poisoned = "corrupt"
            self._warn(f"unreadable manifest ({exc}); serving will JIT")
            return
        self.poisoned = self._validate(self.manifest)
        if self.poisoned is not None:
            return
        for e in self.manifest.get("entries", ()):
            key = (e["program_fp"], e["feed_sig"],
                   tuple(e["fetch_names"]))
            self.entries[key] = e
            self.fingerprints.add(e["program_fp"])

    # -- validation ---------------------------------------------------------

    def _validate(self, doc: dict) -> Optional[str]:
        if doc.get("schema") != SCHEMA:
            self._warn(f"schema {doc.get('schema')!r} != {SCHEMA!r}")
            return "schema"
        env, here = doc.get("env", {}), environment_fingerprint()
        for k in ("jax", "jaxlib"):
            if env.get(k) != here[k]:
                self._warn(f"{k} {env.get(k)!r} != running {here[k]!r}")
                return "version"
        for k in ("platform", "device_kind"):
            if env.get(k) != here[k]:
                self._warn(f"{k} {env.get(k)!r} != running {here[k]!r}")
                return "device"
        if doc.get("flags") != flags_fingerprint():
            self._warn(f"compile-context flags {doc.get('flags')!r} != "
                       f"running {flags_fingerprint()!r}")
            return "flags"
        return None

    def _warn(self, msg: str) -> None:
        if msg in self._warned:
            return
        self._warned.add(msg)
        print(f"[paddle_tpu.aot] artifact store {self.root}: {msg} "
              "-- falling back to JIT compilation", file=sys.stderr)

    def _count(self, result: str) -> None:
        self.results[result] = self.results.get(result, 0) + 1
        _M_AOT_LOAD.inc(result=result)

    # -- lookup -------------------------------------------------------------

    def lookup(self, program_fp: str, sig: str, fetch_names,
               validate=None):
        """Return ``(meta, loaded_executable)`` for a manifest match, or
        ``None`` (after counting the rejection reason).  ``validate``
        is an optional ``meta -> reason-or-None`` hook run before the
        payload is touched — the executor uses it to re-prove the
        donation mask."""
        if self.poisoned is not None:
            self._count(f"rejected_{self.poisoned}")
            return None
        meta = self.entries.get((program_fp, sig, tuple(fetch_names)))
        if meta is None:
            if program_fp in self.fingerprints:
                # the program is known but this (bucket, fetch) combo
                # was never exported — likely a wider serve ladder
                self._warn(f"no entry for bucket sig {sig} "
                           f"(program {program_fp[:12]})")
                self._count("rejected_bucket")
            else:
                self._warn(f"program fingerprint {program_fp[:12]} not "
                           "in manifest (model or optimizer drifted "
                           "since export)")
                self._count("rejected_fingerprint")
            return None
        if validate is not None:
            reason = validate(meta)
            if reason is not None:
                self._warn(f"entry {meta['id']}: {reason}")
                self._count(f"rejected_{reason.split(':')[0]}")
                return None
        loaded = self._deserialize(meta)
        if loaded is None:
            return None
        self._count("loaded")
        return meta, loaded

    def _deserialize(self, meta: dict):
        import jax
        from jax.experimental import serialize_executable as _ser

        path = os.path.join(self.root, meta["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
            if hashlib.sha256(blob).hexdigest() != meta["sha256"]:
                raise ValueError("payload sha256 mismatch (truncated or "
                                 "corrupt executable file)")
            doc = pickle.loads(blob)
            by_id = {d.id: d for d in jax.devices()}
            return _ser.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=[by_id[i] for i in meta["device_ids"]])
        except Exception as exc:
            self._warn(f"entry {meta['id']}: {type(exc).__name__}: {exc}")
            self._count("rejected_corrupt")
            return None

    # -- introspection ------------------------------------------------------

    def info(self) -> dict:
        return {
            "root": self.root,
            "poisoned": self.poisoned,
            "entries": len(self.entries),
            "results": dict(self.results),
        }
