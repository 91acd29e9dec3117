"""Record the small chip trace that ``test_trace.py`` reduces: three
steps of a tiny data-parallel program (a matmul and a psum over every
chip jax sees) under the same spans the drivers write.

    python perf/tests/record_small_trace.py chiprun_out/small_trace

Run on the chip, by hand, when the profiler's format changes; the
``.xplane.pb`` it leaves is copied to ``perf/tests/data/``.
"""

import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from perf.harness import runtime, trace as tr  # noqa: E402


def main(out_dir):
    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("dp",))
    x = jax.device_put(jnp.ones((len(devs) * 256, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("dp")))
    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16),
                       NamedSharding(mesh, P()))

    @jax.jit
    def step(x, w):
        g = jnp.einsum("bi,bj->ij", x, x @ w)       # summed over dp
        return w + 1e-3 * g.astype(w.dtype)

    w = step(x, w).block_until_ready()
    spans = runtime.Spans(True)
    with runtime.profiler_trace(True) as path:
        with spans.span(tr.WINDOW_SPAN):
            for _ in range(3):
                with spans.span("perf.exe_run"):
                    w = step(x, w)
                with spans.span("perf.loss_read"):
                    float(w[0, 0])
    os.makedirs(out_dir, exist_ok=True)
    src = tr.find_xplane(path)
    dst = os.path.join(out_dir, f"small_{devs[0].platform}_{len(devs)}.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(path, ignore_errors=True)
    print(dst, os.path.getsize(dst), "bytes")
    print(tr.summary(tr.load(dst)))


if __name__ == "__main__":
    main(sys.argv[1])
