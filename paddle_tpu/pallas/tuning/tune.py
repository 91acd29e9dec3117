"""`paddle tune`: reproduce the tuning database from one command.

For every selected kernel family and shape, enumerates the valid config
space (space.py), measures the hard-coded default plus up to
``--budget`` candidates (random-sampled beyond the budget, seeded), and
persists the measured winner into the tuning database (db.py, atomic
merge-write).  Prints a tuned-vs-default speedup table and records a
``paddle_tpu.tune.v1`` telemetry artifact through the observability
layer.

Flags (``--k=v`` style, the repo CLI convention):

  --kernel=softmax,lstm     families to tune (default: all)
  --shapes=8192x512;4096x1024
                            per-family shapes (default: the family's
                            ``default_shapes``; dims are 'x'-joined,
                            shapes ';'-separated)
  --budget=N                max measured candidates per (kernel, shape)
                            (default 32)
  --reps=N                  best-of-N timing repetitions (default 3)
  --dtype=float32           operand dtype
  --output=PATH             database path (default: the checked-in
                            ``tuning_db.json`` next to the package)
  --telemetry=PATH          artifact path (default: ``<output>`` with
                            ``.telemetry.json``)
  --seed=N                  candidate-sampling seed (default 0)
  --smoke                   tiny shapes + budget 2 + interpret-mode on
                            CPU: the enumerate -> measure -> persist ->
                            dispatch-hit path in tier-1 time

On CPU the kernels run in interpret mode and entries are keyed
``device_kind=cpu`` with ``"interpret": true`` provenance — real TPU
runs key separately and never collide with them.
"""

from __future__ import annotations

import json
import random
import sys
from typing import Optional

from paddle_tpu.pallas.tuning import db as _dbmod
from paddle_tpu.pallas.tuning.db import TuningDB, current_device_kind


def _parse_shapes(spec: str):
    shapes = []
    for part in spec.split(";"):
        part = part.strip()
        if part:
            shapes.append(tuple(int(d) for d in part.split("x")))
    return shapes


def _use_interpret() -> bool:
    import jax

    return jax.default_backend() != "tpu"


def tune_one(family, shape, dtype: str, budget: int, reps: int,
             interpret: bool, seed: int = 0, log=print) -> Optional[dict]:
    """Measure one (family, shape) point; returns the DB record (or
    ``None`` when the space is empty) plus prints progress."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.pallas.tuning import measure

    m_measured = metrics.counter(
        "tune_configs_measured_total",
        "autotuner candidate configs actually timed")
    m_infeasible = metrics.counter(
        "tune_configs_infeasible_total",
        "autotuner candidate configs that failed to compile/run")

    cands = family.configs(shape)
    n_space = len(cands)
    if budget and len(cands) > budget:
        cands = random.Random(seed).sample(cands, budget)
    try:
        default_ms = measure.measure_config(family, shape, dtype, None,
                                            interpret, reps)
    except measure.Infeasible as e:
        log(f"  {family.name}{shape}: default infeasible ({e}); skipped")
        return None

    best_cfg, best_ms, n_inf = None, float("inf"), 0
    for cfg in cands:
        try:
            ms = measure.measure_config(family, shape, dtype, cfg,
                                        interpret, reps)
            m_measured.inc(kernel=family.name)
        except measure.Infeasible:
            n_inf += 1
            m_infeasible.inc(kernel=family.name)
            continue
        if ms < best_ms:
            best_cfg, best_ms = cfg, ms
    if best_cfg is None or best_ms >= default_ms:
        # nothing measured beat the default: record the default itself
        # so dispatch stays on the proven-best path and re-tunes skip
        best_cfg, best_ms = None, default_ms
    return {
        "config": best_cfg or {},
        "time_ms": round(best_ms, 6),
        "default_time_ms": round(default_ms, 6),
        "speedup": round(default_ms / best_ms, 4) if best_ms else 1.0,
        "interpret": interpret,
        "n_configs": n_space,
        "n_infeasible": n_inf,
        "shape": list(shape),
    }


def _artifact(path: str, rows, out_path: str, device_kind: str):
    import jax

    from paddle_tpu import observability as obs

    art = {
        "schema": "paddle_tpu.tune.v1",
        "db_path": out_path,
        "device": {
            "backend": jax.default_backend(),
            "kind": jax.devices()[0].device_kind,
            "count": jax.device_count(),
            "db_device_kind": device_kind,
        },
        "results": rows,
        "metrics": obs.snapshot(),
    }
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    return path


def main(argv) -> int:
    from paddle_tpu.pallas import tuning
    from paddle_tpu.pallas.tuning import space

    kv, rest = _cli_kv(argv)
    if rest:
        print(f"tune: unexpected args {rest}", file=sys.stderr)
        return 2
    smoke = "smoke" in kv
    budget = int(kv.get("budget", 2 if smoke else 32))
    reps = int(kv.get("reps", 1 if smoke else 3))
    dtype = kv.get("dtype", "float32")
    seed = int(kv.get("seed", 0))
    out_path = kv.get("output", _dbmod.DEFAULT_PATH)
    names = [n for n in kv.get("kernel", "").split(",") if n]
    if not names:
        names = sorted(space.SPACES)
    unknown = [n for n in names if n not in space.SPACES]
    if unknown:
        print(f"tune: unknown kernel(s) {unknown}; "
              f"one of {sorted(space.SPACES)}", file=sys.stderr)
        return 2
    shapes_flag = _parse_shapes(kv["shapes"]) if "shapes" in kv else None
    interpret = _use_interpret()
    device_kind = current_device_kind()

    # measure against hard-coded defaults, not whatever DB is installed
    tuning.disable()
    new_db = TuningDB()
    rows = []
    mode = "interpret(cpu)" if interpret else "compiled"
    print(f"tune: kernels={names} budget={budget} reps={reps} "
          f"dtype={dtype} mode={mode} -> {out_path}")
    for name in names:
        family = space.SPACES[name]
        shapes = shapes_flag or (family.smoke_shapes if smoke
                                 else family.default_shapes)
        for shape in shapes:
            if len(shape) != len(family.shape_names):
                print(f"tune: {name} wants dims "
                      f"{'x'.join(family.shape_names)}, got {shape}",
                      file=sys.stderr)
                return 2
            rec = tune_one(family, shape, dtype, budget, reps,
                           interpret, seed)
            if rec is None:
                continue
            new_db.put(name, shape, dtype, device_kind, rec)
            rows.append({"kernel": name, "shape": list(shape),
                         "dtype": dtype, **{k: rec[k] for k in
                         ("config", "time_ms", "default_time_ms",
                          "speedup", "n_configs", "n_infeasible")}})
            print(json.dumps({"kernel": name,
                              "shape": "x".join(map(str, shape)),
                              "default_ms": rec["default_time_ms"],
                              "tuned_ms": rec["time_ms"],
                              "speedup": rec["speedup"],
                              "config": rec["config"]}))

    saved = new_db.save(out_path, merge_existing=True)
    print(f"tune: {len(new_db)} entr{'y' if len(new_db) == 1 else 'ies'} "
          f"-> {saved}")

    # prove the round trip: the saved DB must serve dispatch hits
    tuning.set_db(saved)
    hits = sum(1 for r in rows if tuning.lookup(
        r["kernel"], r["shape"], r["dtype"], device_kind) is not None)
    print(f"tune: dispatch round-trip {hits}/{len(rows)} hits")

    telemetry = kv.get("telemetry",
                       out_path.rsplit(".json", 1)[0] + ".telemetry.json")
    try:
        _artifact(telemetry, rows, saved, device_kind)
        print(f"tune: telemetry artifact -> {telemetry}")
    except Exception as e:  # artifact failure must not fail the tune
        print(f"tune: telemetry artifact failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    return 0


def _cli_kv(argv):
    """`--k=v` plus bare `--flag` (stored as empty string) parsing."""
    out, rest = {}, []
    for a in argv:
        if a.startswith("--"):
            k, _, v = a[2:].partition("=")
            out[k] = v
        else:
            rest.append(a)
    return out, rest


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main(sys.argv[1:]))
