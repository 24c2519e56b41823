"""Record a small real trace on the chip for ``tests/data``: a few matmuls
with host sleeps between them, a clock anchor and two host spans, so the
test of the reduction has device events, idle gaps and something to
attribute them to.

    python3 benchmark/tools/record_small_trace.py <out_dir>
"""
import glob
import json
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("benchmark.clock_anchor"):
        anchor = time.perf_counter()
    t_started = time.perf_counter()
    spans = []
    for name, naps in (("host.prepare", 0.004), ("host.fetch", 0.008)):
        t0 = time.perf_counter()
        time.sleep(naps)
        spans.append({"name": name, "t0": t0, "t1": time.perf_counter()})
        for _ in range(3):
            f(x).block_until_ready()
    t_stopped = time.perf_counter()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(os.path.join(
        tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(src, os.path.join(out_dir, "small_v5e.xplane.pb"))
    with open(os.path.join(out_dir, "small_v5e.json"), "w") as fh:
        json.dump({"anchor_perf_counter": anchor, "t_started": t_started,
                   "t_stopped": t_stopped, "spans": spans,
                   "device_kind": jax.devices()[0].device_kind}, fh)
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(os.path.join(out_dir, "small_v5e.xplane.pb")))


if __name__ == "__main__":
    main(sys.argv[1])
