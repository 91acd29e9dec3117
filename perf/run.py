"""The benchmark's one command: run ONE cell once in a new process.

    python3 perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data the harness finds by the names in
``BENCHMARK.json``: ``perf/workloads/<name>.json`` (configuration,
traffic, chips, driver, what ``correct`` compares), ``perf/configs/
<config>.json`` (the sizes as run, with source, reduced, assumed and
departures), ``perf/traffic/<traffic>.json`` (the mix's parameters),
the driver ``perf/drivers/<driver>.py`` and each per-layer metric's
reader ``perf/layer_metrics/<metric>.py``.  A later PR adds a cell, a
configuration, a traffic mix, a driver or a metric by adding files and
entries; it edits none that is there.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, traced, ``breakdown``.  Without a TPU holding the chips the cell
asks for it exits non-zero and prints no result — except under
``--rehearse``, which runs the cell's control flow at the toy sizes of
its files on whatever jax has, prints ``"platform": "cpu"`` and is
never a result.
"""

import time

T_START = time.perf_counter()   # process start, as near as Python allows

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def deep_merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def load_cell(name, rehearse):
    """(benchmark entry, workload, config, traffic) of one cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"perf: no workload {name!r} in BENCHMARK.json")
    wl = load_json("workloads", name + ".json")
    for key in ("config", "traffic", "chips"):
        if wl[key] != entries[0][key]:
            raise SystemExit(f"perf: {name}: {key} differs between "
                             "BENCHMARK.json and the workload's file")
    cfg = load_json("configs", wl["config"] + ".json")
    traffic = load_json("traffic", wl["traffic"] + ".json")
    if rehearse:
        cfg = deep_merge(cfg, cfg.get("rehearse", {}))
        traffic = deep_merge(traffic, traffic.get("rehearse", {}))
        wl = deep_merge(wl, wl.get("rehearse", {}))
    return bench, wl, cfg, traffic


def metrics_of(bench, group, workload):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(metric):
    """``perf/layer_metrics/<metric>.py``, loaded by path (a metric's
    name may hold dots)."""
    path = os.path.join(HERE, "layer_metrics", metric + ".py")
    if not os.path.exists(path) and "." in metric:
        # one reader serves several names: ``<reader>.<tag>`` reads as
        # ``<reader>`` where no file of its own exists (a quantity whose
        # cells report different end-to-end metrics is listed once for
        # each, and the arithmetic is one)
        path = os.path.join(HERE, "layer_metrics",
                            metric.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "perf_layer_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever jax has; never a result")
    args = ap.parse_args(argv)

    bench, wl, cfg, traffic = load_cell(args.workload, args.rehearse)

    from perf.harness import runtime, trace as tr

    cache_dir = runtime.configure_cache(args.rehearse)
    device, peaks = runtime.require_device(wl["chips"], args.rehearse)
    runtime.say(f"cell {wl['name']} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; device {device}; cache {cache_dir}")
    if args.rehearse:
        from paddle_tpu import pallas as pk

        pk.enable("auto", interpret=True)   # kernels interpreted off-TPU
    setup = {}
    ctx = {"workload": wl, "config": cfg, "traffic": traffic,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "rehearse": args.rehearse,
           "compile_events": runtime.CompileEvents(),
           "mark_setup_done": lambda: setup.setdefault(
               "s", time.perf_counter() - T_START)}
    driver = importlib.import_module(f"perf.drivers.{wl['driver']}")
    record = driver.run(ctx)
    record.update(config=cfg, traffic=traffic, workload=wl, peaks=peaks)

    values = dict(record["end_to_end"], setup_s=setup["s"])
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": {}}
    dev = dict(device, memory_peak_bytes=runtime.memory_peak_bytes(
        record["devices"], record.get("planned_bytes", 0)))
    if args.trace:
        from perf.harness import hlo

        cats = {}
        for text in record.get("compiled_text", {}).values():
            cats.update(hlo.categories(text))
        summary = tr.summary(record["trace"], categories=cats)
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = summary["breakdown"]
        runtime.say(f"busy per device: {summary['busy_s_per_device']}")
        if not args.rehearse and summary["busy_s"] <= 0:
            raise SystemExit("perf: no operation ran on the device in the "
                             "traced window")
        for m in metrics_of(bench, "per_layer", wl["name"]):
            v = load_reader(m["name"])(record)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        runtime.say(f"end-to-end in the traced window (not a result): "
                    f"{values}")
    else:
        for m in metrics_of(bench, "end_to_end", wl["name"]):
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    out["device"] = dev
    out["beside"] = {k: v for k, v in values.items()
                     if k not in out["metrics"]}
    runtime.say(f"facts: {record['facts']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
