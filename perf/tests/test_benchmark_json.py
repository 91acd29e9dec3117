"""``BENCHMARK.json`` keeps to the contract's characters and every
entry resolves to its files."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _perf(*parts):
    return os.path.join(ROOT, "perf", *parts)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["perf"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    n4 = sum(w["chips"] == 4 for w in bench["workloads"])
    assert n4 <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for e in bench["configs"] + bench["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_entry_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert c["file"] == f"perf/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and body["source"] == c["source"]
        for key in ("assumed", "departures", "stands_for"):
            assert key in body, (c["name"], key)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in bench["workloads"]:
        assert w["config"] in configs
        with open(_perf("workloads", w["name"] + ".json")) as f:
            body = json.load(f)
        assert (body["config"], body["traffic"], body["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert os.path.exists(_perf("traffic", w["traffic"] + ".json"))
        assert os.path.exists(_perf("drivers", body["driver"] + ".py"))
        mine = [m for m in bench["end_to_end"] if m["name"] != "setup_s"
                and w["name"] in m.get("workloads", cells)]
        assert mine, f"{w['name']} reports no end-to-end metric"
    for m in bench["per_layer"]:
        # ``<reader>.<tag>`` reads as ``<reader>`` where it has no
        # file of its own (``run.load_reader``)
        assert any(os.path.exists(_perf("layer_metrics", n + ".py"))
                   for n in (m["name"], m["name"].rsplit(".", 1)[0])), m
        assert m["moves"] in e2e
        where = set(m.get("workloads", cells))
        assert where <= cells
        assert where <= set(e2e[m["moves"]].get("workloads", cells)), m
    for w in cells:
        assert any(w in m.get("workloads", cells)
                   for m in bench["per_layer"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)


def test_one_reader_serves_several_names():
    from perf import run

    base, tagged = (run.load_reader(n) for n in (
        "decode_itl_p95_ms", "decode_itl_p95_ms.some-tag"))
    record = {"client": {"itl_ms": [1.0, 2.0, 3.0, 40.0]}}
    assert tagged(record) == base(record) > 3.0
    # a name with a file of its own keeps it
    assert run.load_reader("exec_host_ms.img").__module__.endswith("_img")
    with pytest.raises(FileNotFoundError):
        run.load_reader("no_such_metric.tag")
