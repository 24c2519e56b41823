"""Compile the chained decode executable (``Executor.run_chained`` over the
decode program: a scan whose carry is the donated KV caches) for a v5e that
is described and not attached, and say what ``deviceless.py`` cannot: the
compiler's memory for it (``--hlo`` writes the optimized HLO's text, where
a ``copy`` of cache shape in the loop body would show).

    python3 benchmark/tools/deviceless_decode.py [--slots 64,80] [--hlo out.txt]

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; the compiler's
bytes, no measurement.
"""
import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
from tools.deviceless import (_DescribedPlace, describe_v5e,  # noqa: E402
                              memory_of)


def compile_chained_decode(cfg: dict, dev):
    """The executable ``run_chained(decode, steps=decode_chunk)`` builds,
    compiled for ``dev`` from shapes alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu.core.types import np_dtype

    net = importlib.import_module(f"families.{cfg['family']}").build(cfg)
    program = net["decode"]["main"]
    steps = cfg["serving"]["generation"]["decode_chunk"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.place = _DescribedPlace(dev)
    step, _ = exe._lookup_chained(
        program, program, {}, [net["decode"]["next_token"].name], steps,
        fluid.Scope(), None)
    on_dev = SingleDeviceSharding(dev)
    block = program.global_block

    def var(name):
        v = block.var(name)
        dt = jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype)))
        return jax.ShapeDtypeStruct(tuple(int(d) for d in v.shape), dt,
                                    sharding=on_dev)

    def placed(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_dev)

    donated = [var(n) for n in step.donated_names]
    kept = [var(n) for n in step.kept_names]
    ro = [var(n) for n in step.ro_names]
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), steps))
    state = jax.eval_shape(step.base_step, [], donated + kept, ro,
                           jax.eval_shape(lambda: jax.random.key(0)))[1]
    at = {n: i for i, n in enumerate(step.io["state_out"])}
    wo = [placed(state[at[n]]) for n in step.wo_names]
    return step.fn.lower([], donated, kept, ro, placed(keys), wo,
                         placed(jax.ShapeDtypeStruct((), jnp.float32))
                         ).compile()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="gpt2-base-serve")
    ap.add_argument("--slots", default="")
    ap.add_argument("--hlo", default="", help="write the last HLO text here")
    a = ap.parse_args()
    dev = describe_v5e()
    for slots in [int(s) for s in a.slots.split(",") if s] or [None]:
        cfg = harness.load_json(HERE, "configs", a.config + ".json")
        if slots:
            cfg["serving"]["slots"] = slots
        row = {"slots": cfg["serving"]["slots"],
               "program": "chained decode"}
        try:
            compiled = compile_chained_decode(cfg, dev)
        except Exception as e:      # the compiler's refusal is the answer
            print(json.dumps(dict(row, fits=False,
                                  error=str(e).split("\n")[0][:300])),
                  flush=True)
            continue
        print(json.dumps(dict(row, **memory_of(compiled))), flush=True)
        if a.hlo:
            with open(a.hlo, "w") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
