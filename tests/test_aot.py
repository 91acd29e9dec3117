"""AOT serving artifacts (ISSUE 20): export/load round trip, donation
restored under the loaded executable, and the rejection taxonomy.

The contract under test: an artifact-booted executor is **bit-identical**
to JIT and keeps buffer donation active; ANY manifest mismatch (version
skew, model drift, corrupt payload) is a loud JIT
fallback — the right `rejected_*` reason lands in
``aot_load_total{result}`` / ``store.results`` and the answer is still
bit-identical, never wrong.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import aot, framework
from paddle_tpu.aot.artifact import ArtifactStore, ArtifactWriter
from paddle_tpu.executor import Executor, Scope
from paddle_tpu.observability import metrics as _metrics


def _program(scale=2.0):
    """Stateful step: Y = W*scale (fetched), W = W*1.5 (donated
    update) — small enough to compile fast, stateful enough to
    exercise the donation mask."""
    prog = framework.Program()
    block = prog.global_block()
    block.create_var(name="W", shape=(8, 8), dtype="float32",
                     persistable=True)
    block.create_var(name="Y", shape=(8, 8), dtype="float32")
    block.append_op(type="scale", inputs={"X": ["W"]},
                    outputs={"Out": ["Y"]}, attrs={"scale": scale})
    block.append_op(type="scale", inputs={"X": ["W"]},
                    outputs={"Out": ["W"]}, attrs={"scale": 1.5})
    return prog


W0 = np.arange(64, dtype=np.float32).reshape(8, 8)


def _run_steps(prog, *, store=None, steps=1):
    """Fresh executor + scope; returns (executor, [Y per step])."""
    exe = Executor()
    if store is not None:
        exe.aot_store = store
    scope = Scope()
    scope.set("W", jnp.asarray(W0))
    outs = []
    for _ in range(steps):
        (y,) = exe.run(prog, feed={}, fetch_list=["Y"], scope=scope)
        outs.append(np.asarray(y))
    return exe, outs


@pytest.fixture()
def artifact_dir(tmp_path):
    """Export the scale program once; yields (art_dir, jit reference
    outputs for two steps)."""
    art = str(tmp_path / "artifacts")
    writer = ArtifactWriter(art)
    exe = Executor()
    scope = Scope()
    scope.set("W", jnp.asarray(W0))
    prog = _program()
    with aot.capture(writer):
        (y1,) = exe.run(prog, feed={}, fetch_list=["Y"], scope=scope)
        (y2,) = exe.run(prog, feed={}, fetch_list=["Y"], scope=scope)
    writer.finish()
    return art, [np.asarray(y1), np.asarray(y2)]


def _source_count(name, source):
    """Sum one cache counter across program labels for a source."""
    fam = _metrics.snapshot().get(name, {"values": []})
    return sum(v["value"] for v in fam["values"]
               if v["labels"].get("source") == source)


# -- happy path -------------------------------------------------------------


def test_roundtrip_bit_identical(artifact_dir):
    art, ref = artifact_dir
    store = ArtifactStore(art)
    exe, outs = _run_steps(_program(), store=store, steps=2)
    assert store.results == {"loaded": 1}
    assert exe.compile_counts == {"jit": 0, "aot": 1}
    assert np.array_equal(outs[0], ref[0])
    assert np.array_equal(outs[1], ref[1])


def test_export_with_the_compile_cache_warm_loads_and_runs(tmp_path):
    """An executable XLA:CPU got from jax's persistent compile cache
    serializes into a payload whose functions are gone: it loads, and
    fails at its first run (``NOT_FOUND: Function ... not found``).  So
    an export compiles its step itself; the second export here finds the
    first one's entry in the cache (every compile is kept: the floor is
    brought to 0 s) and its artifact must still run."""
    import jax

    floor = "jax_persistent_cache_min_compile_time_secs"
    was = getattr(jax.config, floor)
    jax.config.update(floor, 0.0)
    try:
        ref = None
        for turn in ("cold", "warm"):
            art = str(tmp_path / turn)
            writer = ArtifactWriter(art)
            with aot.capture(writer):
                _, ref = _run_steps(_program(scale=7.0), steps=2)
            writer.finish()
            store = ArtifactStore(art)
            exe, outs = _run_steps(_program(scale=7.0), store=store, steps=2)
            assert store.results == {"loaded": 1}, turn
            assert exe.compile_counts == {"jit": 0, "aot": 1}
            assert np.array_equal(outs[0], ref[0]), turn
            assert np.array_equal(outs[1], ref[1]), turn
    finally:
        jax.config.update(floor, was)


def test_donation_restored_under_aot(artifact_dir):
    """Donation through the loaded executable (with the persistent XLA
    compile cache on, as the test conftest sets it): export and load
    agree on the mask and the aliasing survives serialization."""
    art, _ = artifact_dir
    store = ArtifactStore(art)
    exe = Executor()
    exe.aot_store = store
    scope = Scope()
    scope.set("W", jnp.asarray(W0))
    prog = _program()
    exe.run(prog, feed={}, fetch_list=["Y"], scope=scope)
    w_step1 = scope.get("W")
    exe.run(prog, feed={}, fetch_list=["Y"], scope=scope)
    entry = next(iter(store.entries.values()))
    # step 2 donated its input (step 1's own output) — the aliasing
    # win survived serialization, it isn't silently dropped on load
    assert entry["donated_names"] == ["W"]
    assert w_step1.is_deleted()
    # the caller's host array is never clobbered by donation (the
    # first step copies any buffer the executable doesn't own)
    assert np.array_equal(W0, np.arange(64, dtype=np.float32).reshape(8, 8))
    assert store.results == {"loaded": 1}


def test_donation_restored_fresh_process():
    """End-to-end donation proof in a fresh process without the
    persistent compile cache: export, reload in a fresh executor, and
    assert step 2's donated input — step 1's own output — comes back
    deleted."""
    import subprocess
    import sys
    import textwrap

    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_COMPILATION_CACHE",
                                "JAX_PERSISTENT_CACHE"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    code = textwrap.dedent("""
        import os, tempfile
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu import aot, framework
        from paddle_tpu.aot.artifact import ArtifactStore, ArtifactWriter
        from paddle_tpu.executor import Executor, Scope

        prog = framework.Program()
        b = prog.global_block()
        b.create_var(name="W", shape=(8, 8), dtype="float32",
                     persistable=True)
        b.create_var(name="Y", shape=(8, 8), dtype="float32")
        b.append_op(type="scale", inputs={"X": ["W"]},
                    outputs={"Out": ["Y"]}, attrs={"scale": 2.0})
        b.append_op(type="scale", inputs={"X": ["W"]},
                    outputs={"Out": ["W"]}, attrs={"scale": 1.5})
        W0 = np.arange(64, dtype=np.float32).reshape(8, 8)
        with tempfile.TemporaryDirectory() as t:
            art = os.path.join(t, "a")
            w = ArtifactWriter(art)
            exe = Executor()
            sc = Scope()
            sc.set("W", jnp.asarray(W0))
            with aot.capture(w):
                (y_ref,) = exe.run(prog, feed={}, fetch_list=["Y"],
                                   scope=sc)
            w.finish()
            exe2 = Executor()
            exe2.aot_store = ArtifactStore(art)
            sc2 = Scope()
            sc2.set("W", jnp.asarray(W0))
            (y,) = exe2.run(prog, feed={}, fetch_list=["Y"], scope=sc2)
            w1 = sc2.get("W")
            exe2.run(prog, feed={}, fetch_list=["Y"], scope=sc2)
            assert exe2.aot_store.results == {"loaded": 1}, \\
                exe2.aot_store.results
            assert np.array_equal(np.asarray(y_ref), np.asarray(y))
            assert w1.is_deleted(), "loaded executable dropped donation"
            assert np.array_equal(
                W0, np.arange(64, dtype=np.float32).reshape(8, 8))
        print("DONATION-OK")
    """)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "DONATION-OK" in proc.stdout


def test_cache_counters_labeled_by_source(artifact_dir):
    art, _ = artifact_dir
    miss0 = _source_count("executor_compile_cache_miss_total", "aot")
    hit0 = _source_count("executor_compile_cache_hit_total", "aot")
    store = ArtifactStore(art)
    _run_steps(_program(), store=store, steps=3)
    miss1 = _source_count("executor_compile_cache_miss_total", "aot")
    hit1 = _source_count("executor_compile_cache_hit_total", "aot")
    assert miss1 - miss0 == 1  # one store load = one miss{source="aot"}
    assert hit1 - hit0 == 2  # steps 2..3 reuse it as cache hits


# -- rejection taxonomy: every mismatch is a loud, correct JIT fallback ----


def _assert_jit_fallback(store, reason, ref):
    exe, outs = _run_steps(_program(), store=store, steps=2)
    assert exe.compile_counts["aot"] == 0
    assert exe.compile_counts["jit"] == 1
    assert store.results.get(reason, 0) >= 1
    assert store.results.get("loaded", 0) == 0
    assert np.array_equal(outs[0], ref[0])
    assert np.array_equal(outs[1], ref[1])


def _edit_manifest(art, mutate):
    path = os.path.join(art, "MANIFEST.json")
    with open(path) as f:
        doc = json.load(f)
    mutate(doc)
    with open(path, "w") as f:
        json.dump(doc, f)


def test_version_skew_rejected(artifact_dir):
    art, ref = artifact_dir

    def bump(doc):
        doc["env"]["jaxlib"] = "0.0.1"

    _edit_manifest(art, bump)
    _assert_jit_fallback(ArtifactStore(art), "rejected_version", ref)


def test_fingerprint_drift_rejected(artifact_dir):
    art, ref = artifact_dir
    # serve a *different* model (scale 3.0) against the scale-2.0
    # artifacts: the optimized-program fingerprint cannot match
    store = ArtifactStore(art)
    exe, _ = _run_steps(_program(scale=3.0), store=store, steps=1)
    assert exe.compile_counts == {"jit": 1, "aot": 0}
    assert store.results == {"rejected_fingerprint": 1}
    # and the original program still loads from the same (unmodified)
    # store instance — rejection is per lookup, not poison
    exe2, outs = _run_steps(_program(), store=store, steps=2)
    assert exe2.compile_counts == {"jit": 0, "aot": 1}
    assert store.results.get("loaded") == 1
    assert np.array_equal(outs[0], ref[0])


def test_stale_tuning_db_field_ignored(artifact_dir):
    """A manifest exported while the tuning DB's digest was pinned
    still loads: the field is no pin any more."""
    art, ref = artifact_dir

    def stale(doc):
        doc["tuning_db"] = "deadbeef" * 8

    _edit_manifest(art, stale)
    store = ArtifactStore(art)
    assert store.poisoned is None
    exe, outs = _run_steps(_program(), store=store, steps=2)
    assert exe.compile_counts == {"jit": 0, "aot": 1}
    assert store.results == {"loaded": 1}
    assert np.array_equal(outs[0], ref[0])


def test_truncated_payload_rejected(artifact_dir):
    art, ref = artifact_dir
    exec_dir = os.path.join(art, "executables")
    for name in os.listdir(exec_dir):
        path = os.path.join(exec_dir, name)
        with open(path, "rb") as f:
            blob = f.read()
        with open(path, "wb") as f:
            f.write(blob[: len(blob) // 2])
    _assert_jit_fallback(ArtifactStore(art), "rejected_corrupt", ref)


def test_bitflipped_payload_rejected(artifact_dir):
    art, ref = artifact_dir
    exec_dir = os.path.join(art, "executables")
    for name in os.listdir(exec_dir):
        path = os.path.join(exec_dir, name)
        with open(path, "rb") as f:
            blob = bytearray(f.read())
        blob[len(blob) // 2] ^= 0xFF  # sha256 in the manifest catches it
        with open(path, "wb") as f:
            f.write(bytes(blob))
    _assert_jit_fallback(ArtifactStore(art), "rejected_corrupt", ref)


def test_corrupt_manifest_poisons_store(artifact_dir):
    art, ref = artifact_dir
    with open(os.path.join(art, "MANIFEST.json"), "w") as f:
        f.write("{ not json")
    store = ArtifactStore(art)
    assert store.poisoned == "corrupt"
    _assert_jit_fallback(store, "rejected_corrupt", ref)


def test_schema_skew_rejected(artifact_dir):
    art, ref = artifact_dir

    def skew(doc):
        doc["schema"] = "paddle_tpu.aot.v999"

    _edit_manifest(art, skew)
    _assert_jit_fallback(ArtifactStore(art), "rejected_schema", ref)


def test_rejections_land_in_global_metric(artifact_dir):
    art, _ = artifact_dir

    def bump(doc):
        doc["env"]["jaxlib"] = "0.0.1"

    _edit_manifest(art, bump)
    ctr = _metrics.counter("aot_load_total", "")
    before = ctr.value(result="rejected_version")
    _run_steps(_program(), store=ArtifactStore(art), steps=1)
    assert ctr.value(result="rejected_version") == before + 1
