"""Each cell under ``--rehearse`` on the CPU: the whole control flow of
``perf/run.py`` at the toy sizes of the cell's files, traced, in a new
process (the four-chip cell on four virtual devices).  Never a result."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_rehearses(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{cell['chips']}")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell["name"],
         "--seed", str(2 ** 31 + 17), "--seconds", "2", "--trace",
         str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in BENCH[group]
               if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(out["metrics"]) <= allowed and out["metrics"]
    if trace:
        assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
        assert len(out["breakdown"]["device_ops"]) <= 10
    else:
        assert set(out["metrics"]) == allowed


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
