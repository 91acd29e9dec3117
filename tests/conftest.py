"""Test config: force an 8-device virtual CPU mesh so sharding tests run
without TPU hardware (the driver separately dry-runs multichip)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags = (_flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_backend_optimization_level" not in _flags:
    # tests are compile-bound (every test builds fresh XLA programs);
    # opt level 0 halves compile time with identical numerics — measured
    # 71s -> 32s on the GoogLeNet train-step compile
    _flags = (_flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = _flags
# persistent compile cache: warm reruns skip XLA compilation entirely
# (keyed by HLO hash, so correctness is unaffected).  The directory is
# the program's own (paddle_tpu/compile_cache.py); it is exported so the
# subprocesses tests spawn share it.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

from paddle_tpu import compile_cache  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache.configure()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--shard", action="store",
        default=os.environ.get("PYTEST_SHARD"),
        help="'i/n' (1-based): run only the i-th of n deterministic "
             "slices of the suite.  Slicing is per test FILE (stable "
             "crc32 of the filename), so module-scoped fixtures stay "
             "together and every test runs in exactly one shard.  Lets "
             "the tier-1 suite split across driver windows instead of "
             "squeezing into one 600 s timeout (scripts/run_tier1.sh).")


def pytest_collection_modifyitems(config, items):
    spec = config.getoption("--shard")
    if not spec:
        return
    try:
        idx, total = (int(p) for p in spec.split("/", 1))
    except ValueError:
        raise pytest.UsageError(f"--shard must look like '2/3', got {spec!r}")
    if not (total >= 1 and 1 <= idx <= total):
        raise pytest.UsageError(f"--shard {spec!r}: need 1 <= i <= n")
    import zlib

    keep, drop = [], []
    for item in items:
        h = zlib.crc32(os.path.basename(str(item.fspath)).encode())
        (keep if h % total == idx - 1 else drop).append(item)
    items[:] = keep
    if drop:
        config.hook.pytest_deselected(items=drop)


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, a fresh global scope, and
    a zeroed telemetry registry (counters would otherwise accumulate
    across tests in one process)."""
    from paddle_tpu import framework
    from paddle_tpu import executor as executor_mod
    from paddle_tpu import observability

    framework.reset_default_programs()
    executor_mod._global_scope = executor_mod.Scope()
    executor_mod._scope_stack = [executor_mod._global_scope]
    observability.reset()
    yield


@pytest.fixture
def rng():
    return np.random.RandomState(42)
