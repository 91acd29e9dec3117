"""Rehearsal 3 of the on-chip-measurement guide, by hand: compile the
cells' programs at their REAL sizes for a *described* TPU v5e (no chip
needed) and print the compiler's memory plan.

    JAX_PLATFORMS=cpu python perf/scratch_compile.py lm [--layers L --batch B --recompute 0|1]
    JAX_PLATFORMS=cpu python perf/scratch_compile.py decode [--pages N]
    JAX_PLATFORMS=cpu python perf/scratch_compile.py dp4

Nothing runs, so this says nothing about results or times; a compile
that passes is not a chip run.  It is a script, not a test: it loads
libtpu's compiler at its top level, which a test file must never do.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from paddle_tpu import pallas as pk  # noqa: E402

# code that asks for the backend sees the CPU here; the compile is for
# the chip, so the kernels' dispatch has to take its TPU branch
pk.tpu_backend = lambda: True
jax.config.update("jax_enable_compilation_cache", False)


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    text = compiled.as_text()
    print(f"{tag}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.3f} GB -> planned {total} bytes "
          f"({total / 1e9:.2f} GB); tpu_custom_call x"
          f"{text.count('tpu_custom_call')}, all-reduce x"
          f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}")
    return total


def step_shapes(cfg, traffic, sharding_of):
    """(fn, state shapes, feed shapes) of the executor's step for the
    program ``cfg`` names, the state initialised on the CPU to learn
    its shapes."""
    import importlib

    import paddle_tpu as fluid
    from paddle_tpu import amp, executor as em

    amp.enable(cfg.get("amp") == "bf16")
    mod = importlib.import_module(f"perf.programs.{cfg['program']}")
    built = mod.build(cfg, traffic)
    exe, scope = fluid.Executor(fluid.CPUPlace()), em.Scope()
    exe.run(built["startup"], scope=scope)
    feed = {n: (np.zeros(f["shape"], np.float32) if f["draw"] == "normal"
                else np.zeros(f["shape"], np.int64))
            for n, f in built["feeds"].items()}
    fn, state, feeds, uses_rng = exe.build_callable(
        built["main"], feed, [built["loss"].name], scope)
    assert not uses_rng
    st = {n: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                  sharding=sharding_of("state", n))
          for n, v in state.items()}
    fd = {n: jax.ShapeDtypeStruct(
        np.shape(v), jnp.int32 if np.asarray(v).dtype == np.int64
        else np.asarray(v).dtype, sharding=sharding_of("feed", n))
        for n, v in feeds.items()}
    return fn, st, fd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("lm", "decode", "dp4", "resnet"))
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--recompute", type=int)
    ap.add_argument("--pages", type=int)
    args = ap.parse_args()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    if args.what in ("lm", "resnet"):
        name = ("cerebras-gpt-1.3b" if args.what == "lm" else "resnet50")
        cfg = load("configs", name + ".json")
        traffic = load("traffic", "train-s2048.json" if args.what == "lm"
                       else "train-bs256.json")
        if args.layers:
            cfg["train"]["n_layer"] = args.layers
        if args.batch:
            traffic["batch"] = args.batch
            if "train" in cfg:
                cfg["train"]["batch"] = args.batch
        if args.recompute is not None:
            cfg["train"]["recompute"] = bool(args.recompute)
        fn, st, fd = step_shapes(cfg, traffic, lambda *_: one)
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(st, fd).compile()
        report(f"{name} step {cfg.get('train')} batch {traffic['batch']}",
               compiled)

    elif args.what == "dp4":
        cfg = load("configs", "resnet50.json")
        traffic = load("traffic", "train-dp4-bs1024.json")
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("dp",))
        fn, st, fd = step_shapes(
            cfg, traffic, lambda kind, n: NamedSharding(
                mesh, P("dp") if kind == "feed" else P()))
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(st, fd).compile()
        report(f"resnet50 dp=4 global batch {traffic['batch']} (bytes per "
               "device)", compiled)

    else:
        from paddle_tpu.decode import model as dm

        cfg = load("configs", "cerebras-gpt-1.3b.json")
        g = cfg["generate"]
        slots = load("traffic", "generate-chat.json")["gen_slots"]
        pages = args.pages or g["num_pages"]
        d, L, H = cfg["n_embd"], cfg["n_layer"], cfg["n_head"]
        params = jax.eval_shape(
            lambda: dm._init_params(jax.random.key(0), cfg["vocab_size"],
                                    d, H, L, cfg["n_positions"]))
        sds = lambda a, dt=None: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape if hasattr(a, "shape") else a, dt or a.dtype,
            sharding=one)
        params = jax.tree_util.tree_map(sds, params)
        pool = sds((L, pages, g["page_size"], H, d // H), jnp.float32)
        compiled = dm._decode_step.lower(
            params, pool, pool,
            sds((slots, g["pages_per_seq"]), jnp.int32),
            sds((slots,), jnp.int32), sds((slots,), jnp.int32),
            heads=H, page_size=g["page_size"]).compile()
        report(f"decode step, {slots} slots, {pages} pages x "
               f"{g['page_size']} rows", compiled)


if __name__ == "__main__":
    main()
