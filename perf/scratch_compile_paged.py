"""Rehearsal 3 of the on-chip-measurement guide for a paged decoder
configuration (``perf/configs/<name>.json`` with a ``generate`` group
whose model is ``paddle_tpu/models/olmoe.py``): compile its decode
step, its top prefill bucket and a suffix chunk at their REAL sizes for
a *described* TPU v5e (no chip needed) and print the compiler's memory
plan and the kernels it placed.

    JAX_PLATFORMS=cpu python perf/scratch_compile_paged.py olmoe-1b-7b \
        [--pages N] [--slots S] [--what decode,prefill,chunk] [--dump DIR]

Nothing runs, so this says nothing about results or times.  A script,
not a test: it loads libtpu's compiler at its top level.
"""

import argparse
import functools
import os

import scratch_compile as sc   # sets the environment for a described chip

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from paddle_tpu.decode import model as dm  # noqa: E402
from paddle_tpu.models import olmoe  # noqa: E402


def report(tag, compiled, dump):
    total = sc.report(tag, compiled)
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, tag.split(",")[0].replace(" ", "_")
                               + ".hlo.txt"), "w") as f:
            f.write(compiled.as_text())
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--pages", type=int)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--what", default="decode,prefill,chunk")
    ap.add_argument("--dump")
    args = ap.parse_args()
    cfg = sc.load("configs", args.config + ".json")
    g = cfg["generate"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    dtype = jnp.dtype(g["dtype"])
    d, H, L = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_hidden_layers"])
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(
            olmoe.init_params, jax.random.key(0), vocab=cfg["vocab_size"],
            d=d, layers=L, experts=cfg["num_experts"],
            expert_width=cfg["intermediate_size"], dtype=dtype)))
    block = olmoe.OlmoeBlock(
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        top_k=cfg["num_experts_per_tok"])
    pages, pg, P, S = (args.pages or g["num_pages"], g["page_size"],
                       g["pages_per_seq"], args.slots)
    pool = sds((L, pages, pg, H, d // H), dtype)
    i32 = jnp.int32
    what = args.what.split(",")
    if "decode" in what:
        report(f"decode step, {S} slots, {pages} pages x {pg} rows",
               dm._decode_step.lower(
                   params, pool, pool, sds((S, P), i32), sds((S,), i32),
                   sds((S,), i32), heads=H, page_size=pg,
                   block=block).compile(), args.dump)
    if "prefill" in what:
        bucket = pg * P
        report(f"prefill bucket {bucket}, {pages} pages",
               dm._prefill_bucket.lower(
                   params, pool, pool, sds((bucket,), i32),
                   sds((bucket,), i32), sds((), i32), heads=H,
                   block=block).compile(), args.dump)
    if "chunk" in what:
        report(f"suffix chunk of 136 rows, {pages} pages",
               dm._prefill_chunk.lower(
                   params, pool, pool, sds((P,), i32), sds((), i32),
                   sds((136,), i32), heads=H, page_size=pg,
                   block=block).compile(), args.dump)


if __name__ == "__main__":
    main()
