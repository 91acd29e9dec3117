"""Pallas autotuner tests: shape bucketing, tuning-DB persistence,
dispatch hit-vs-miss parity on every kernel family (interpret mode),
infeasible-config handling, and the `paddle tune --smoke` e2e path."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.pallas import tuning
from paddle_tpu.pallas.tuning import bucket as tb
from paddle_tpu.pallas.tuning.db import SCHEMA, TuningDB, make_key


@pytest.fixture(autouse=True)
def _isolated_db():
    """Every test starts undispatched and leaves no global DB behind."""
    tuning.disable()
    yield
    tuning.set_db(None)          # re-resolve from env/default next use
    jax.clear_caches()           # DB resolution is frozen into traces


def _install(kernel, shape, dtype, cfg):
    db = TuningDB()
    db.put(kernel, shape, dtype, tuning.current_device_kind(),
           {"config": cfg})
    tuning.set_db(db)
    jax.clear_caches()


# ---------------------------------------------------------------------------
# bucketing (shared with the serving batcher)
# ---------------------------------------------------------------------------


def test_bucket_dim_edges():
    assert tb.bucket_dim(0) == 1
    assert tb.bucket_dim(1) == 1
    assert tb.bucket_dim(2) == 2
    assert tb.bucket_dim(3) == 4
    assert tb.bucket_dim(4) == 4
    assert tb.bucket_dim(5) == 8
    assert tb.bucket_dim(8) == 8
    assert tb.bucket_dim(9) == 16
    assert tb.bucket_dim(1 << 20) == 1 << 20
    assert tb.bucket_dim((1 << 20) + 1) == 1 << 21


def test_bucket_shape_and_ladder():
    assert tb.bucket_shape((3, 100, 128)) == (4, 128, 128)
    assert tb.bucket_ladder(1) == (1,)
    assert tb.bucket_ladder(5) == (1, 2, 4, 8)
    assert tb.bucket_ladder(8) == (1, 2, 4, 8)


def test_serving_bucketer_delegates():
    from paddle_tpu.serving import batching

    for n in (1, 2, 3, 7, 8, 9, 100):
        assert batching.next_bucket(n) == tb.bucket_dim(n)
    assert batching.bucket_ladder(6) == tb.bucket_ladder(6)


def test_make_key_buckets_shapes():
    a = make_key("matmul", (100, 100, 100), "float32", "cpu")
    b = make_key("matmul", (128, 128, 128), "float32", "cpu")
    assert a == b == "matmul|128x128x128|float32|cpu"
    assert make_key("matmul", (129, 128, 128), "float32", "cpu") != a


# ---------------------------------------------------------------------------
# DB persistence
# ---------------------------------------------------------------------------


def test_db_round_trip(tmp_path):
    p = str(tmp_path / "db.json")
    db = TuningDB()
    db.put("matmul", (256, 512, 256), "float32", "cpu",
           {"config": {"bm": 128}, "time_ms": 1.0})
    db.save(p)
    got = TuningDB.load(p)
    assert got.lookup("matmul", (256, 512, 256), "float32",
                      "cpu") == {"bm": 128}
    # in-bucket query shape resolves to the same entry
    assert got.lookup("matmul", (200, 500, 200), "float32",
                      "cpu") == {"bm": 128}
    assert got.lookup("matmul", (256, 512, 256), "bfloat16",
                      "cpu") is None
    assert got.lookup("matmul", (256, 512, 256), "float32",
                      "tpu_v4") is None


def test_db_save_merges_not_clobbers(tmp_path):
    p = str(tmp_path / "db.json")
    a = TuningDB()
    a.put("softmax", (512, 128), "float32", "cpu",
          {"config": {"block_rows": 128}})
    a.save(p)
    b = TuningDB()
    b.put("matmul", (256, 512, 256), "float32", "cpu",
          {"config": {"bm": 128}})
    b.save(p)
    got = TuningDB.load(p)
    assert len(got) == 2, "re-tune dropped another kernel's entries"
    # re-tuning the same key replaces the record
    c = TuningDB()
    c.put("softmax", (512, 128), "float32", "cpu",
          {"config": {"block_rows": 256}})
    c.save(p)
    got = TuningDB.load(p)
    assert got.lookup("softmax", (512, 128), "float32",
                      "cpu") == {"block_rows": 256}
    assert len(got) == 2


def test_db_atomic_write_no_stray_tmp(tmp_path):
    p = str(tmp_path / "db.json")
    db = TuningDB()
    db.put("softmax", (512, 128), "float32", "cpu", {"config": {}})
    db.save(p)
    leftovers = [f for f in os.listdir(tmp_path) if f != "db.json"]
    assert leftovers == []


def test_db_schema_reject(tmp_path):
    p = str(tmp_path / "db.json")
    with open(p, "w") as f:
        json.dump({"schema": "paddle_tpu.tuning_db.v999",
                   "entries": {"k": {}}}, f)
    with pytest.raises(ValueError):
        TuningDB.load(p)
    assert len(TuningDB.load_or_empty(p)) == 0
    assert len(TuningDB.load_or_empty(str(tmp_path / "missing.json"))) == 0
    with open(p, "w") as f:
        f.write("{corrupt")
    assert len(TuningDB.load_or_empty(p)) == 0


def test_env_var_disables_lookup(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TUNING_DB", "off")
    tuning.set_db(None)
    assert len(tuning.get_db()) == 0
    assert tuning.lookup("softmax", (512, 128), "float32") is None


def test_env_var_points_at_path(tmp_path, monkeypatch):
    p = str(tmp_path / "db.json")
    db = TuningDB()
    db.put("softmax", (512, 128), "float32",
           tuning.current_device_kind(), {"config": {"block_rows": 64}})
    db.save(p)
    monkeypatch.setenv("PADDLE_TPU_TUNING_DB", p)
    tuning.set_db(None)
    assert tuning.lookup("softmax", (512, 128),
                         "float32") == {"block_rows": 64}


# ---------------------------------------------------------------------------
# empty-DB dispatch = hard-coded defaults (bit-parity with HEAD)
# ---------------------------------------------------------------------------


def test_empty_db_resolves_defaults():
    from paddle_tpu.pallas import flash_attention as fa
    from paddle_tpu.pallas import lstm as lk
    from paddle_tpu.pallas import softmax as sm

    assert sm._resolve_block_rows(1024, 128, "float32", None) == \
        sm.DEFAULT_CONFIG["block_rows"]
    assert fa._resolve_blocks(2, 1024, 1024, 128, "float32") == (
        fa._pick_block(1024), fa._pick_block(1024))
    assert lk._resolve_block_b(4, 16, 128, "float32") is None


def test_rpa_empty_db_resolves_default():
    from paddle_tpu.decode import attention as da

    assert da._resolve_config(8, 2, 8, 2, 8, "float32") == (
        da.DEFAULT_CONFIG["slots_per_block"],
        da.DEFAULT_CONFIG["slot_semantics"])


# ---------------------------------------------------------------------------
# dispatch hit-vs-miss parity: tuned config must only change speed
# ---------------------------------------------------------------------------


def test_softmax_hit_parity(rng):
    from paddle_tpu.pallas.softmax import softmax

    x = jnp.asarray(rng.randn(512, 128).astype("float32"))
    miss = np.asarray(softmax(x, interpret=True))
    _install("softmax", (512, 128), "float32", {"block_rows": 64})
    hit = np.asarray(softmax(x, interpret=True))
    np.testing.assert_allclose(hit, miss, atol=1e-6)


def test_flash_attention_hit_parity(rng):
    from paddle_tpu.pallas.flash_attention import flash_attention

    q, k, v = (jnp.asarray(rng.randn(2, 256, 8).astype("float32") * 0.3)
               for _ in range(3))
    miss = np.asarray(flash_attention(q, k, v, causal=True,
                                      interpret=True))
    _install("flash_attention", (2, 256, 256, 8), "float32",
             {"blk_q": 128, "blk_k": 128})
    hit = np.asarray(flash_attention(q, k, v, causal=True,
                                     interpret=True))
    np.testing.assert_allclose(hit, miss, atol=2e-5, rtol=1e-5)


def test_lstm_hit_parity(rng):
    from paddle_tpu.pallas.lstm import lstm_seq

    t, b, h = 3, 16, 128
    xp = jnp.asarray(rng.randn(t, b, 4 * h).astype("float32") * 0.1)
    w = jnp.asarray(rng.randn(h, 4 * h).astype("float32") * 0.1)
    bias = jnp.zeros((4 * h,), jnp.float32)
    h0 = jnp.zeros((b, h), jnp.float32)
    c0 = jnp.zeros((b, h), jnp.float32)
    miss = [np.asarray(o) for o in lstm_seq(xp, w, bias, h0, c0, True)]
    _install("lstm", (t, b, h), "float32", {"block_b": 8})
    hit = [np.asarray(o) for o in lstm_seq(xp, w, bias, h0, c0, True)]
    for h_, m_ in zip(hit, miss):
        np.testing.assert_allclose(h_, m_, atol=1e-6)


def test_rpa_hit_parity(rng):
    from paddle_tpu.decode.attention import (
        ragged_paged_attention, ragged_paged_attention_reference)

    s, p, page, h, d = 8, 2, 8, 2, 8
    q = jnp.asarray(rng.randn(s, h, d).astype("float32"))
    kp = jnp.asarray(rng.randn(s * p + 1, page, h, d).astype("float32"))
    vp = jnp.asarray(rng.randn(s * p + 1, page, h, d).astype("float32"))
    pt = jnp.asarray(rng.randint(0, s * p, (s, p)).astype("int32"))
    lens = jnp.asarray(rng.randint(1, p * page + 1, s).astype("int32"))
    ref = np.asarray(ragged_paged_attention_reference(q, kp, vp, pt, lens))
    miss = np.asarray(ragged_paged_attention(q, kp, vp, pt, lens,
                                             interpret=True))
    _install("ragged_paged_attention", (s, p, page, h, d), "float32",
             {"slots_per_block": 4, "slot_semantics": "arbitrary"})
    hit = np.asarray(ragged_paged_attention(q, kp, vp, pt, lens,
                                            interpret=True))
    np.testing.assert_allclose(miss, ref, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(hit, ref, atol=2e-5, rtol=1e-5)


def test_bucket_valid_config_falls_back_at_actual_shape(rng):
    """An entry whose config does not divide the actual shape must fall
    back to defaults (DB keys are buckets, not points)."""
    from paddle_tpu.pallas.softmax import softmax

    x = jnp.asarray(rng.randn(512, 128).astype("float32"))
    miss = np.asarray(softmax(x, interpret=True))
    _install("softmax", (512, 128), "float32", {"block_rows": 192})
    hit = np.asarray(softmax(x, interpret=True))   # must not assert
    np.testing.assert_allclose(hit, miss, atol=0)  # identical path


# ---------------------------------------------------------------------------
# measurement + tune CLI
# ---------------------------------------------------------------------------


def test_measure_infeasible_config_is_recorded_not_raised():
    from paddle_tpu.pallas.tuning import measure, space

    fam = space.SPACES["softmax"]
    with pytest.raises(measure.Infeasible):
        # 999 divides nothing: the kernel's fits() assert fires inside
        # the build and must surface as Infeasible, not AssertionError
        measure.measure_config(fam, (512, 128), "float32",
                               {"block_rows": 999}, interpret=True,
                               reps=1)


def test_config_spaces_are_valid():
    from paddle_tpu.pallas.tuning import space

    assert set(space.SPACES) == {"softmax", "flash_attention", "lstm",
                                 "ragged_paged_attention"}
    for name, fam in space.SPACES.items():
        for shape in fam.smoke_shapes:
            cands = fam.configs(shape)
            assert cands, f"{name}{shape}: empty config space"
            assert all(isinstance(c, dict) for c in cands)


def test_tune_smoke_e2e(tmp_path):
    """`paddle tune --kernel=softmax --budget=2 --smoke`: enumerate ->
    measure -> persist -> dispatch-hit, inside the tier-1 budget."""
    from paddle_tpu.pallas.tuning.tune import main as tune_main

    out = str(tmp_path / "db.json")
    rc = tune_main([f"--output={out}", "--kernel=softmax", "--smoke",
                    "--budget=2"])
    assert rc == 0
    db = TuningDB.load(out)
    assert len(db) == 1
    assert db.entries and SCHEMA == "paddle_tpu.tuning_db.v1"
    (rec,) = db.entries.values()
    assert rec["default_time_ms"] > 0 and rec["time_ms"] > 0
    assert rec["n_configs"] >= 1
    art = json.load(open(out.rsplit(".json", 1)[0] + ".telemetry.json"))
    assert art["schema"] == "paddle_tpu.tune.v1"
    assert art["results"][0]["kernel"] == "softmax"
    # the saved DB serves dispatch
    tuning.set_db(out)
    assert tuning.lookup("softmax", (512, 128), "float32") is not None


def test_checked_in_db_loads():
    """The shipped tuning_db.json parses under the current schema and
    every entry's config is consumable by dispatch."""
    from paddle_tpu.pallas.tuning.db import DEFAULT_PATH

    db = TuningDB.load(DEFAULT_PATH)
    assert len(db) >= 1
    for key, rec in db.entries.items():
        assert isinstance(rec.get("config"), dict), key
        assert rec.get("default_time_ms", 0) >= rec.get("time_ms", 0) > 0, key


def test_unknown_kernel_flag_errors():
    from paddle_tpu.pallas.tuning.tune import main as tune_main

    assert tune_main(["--kernel=nope", "--smoke"]) == 2
