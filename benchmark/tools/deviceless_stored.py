"""Compile a ``serve_stored`` configuration's executables, as the engine
builds them (the expert statistics among the fetches), for a v5e that is
described and not attached: each prefill bucket through ``Executor.run``
and the chained decode scan through ``run_chained``. Prints the compiler's
bytes for each (``memory_analysis()``) and the Mosaic calls by name; with
``--record`` writes them into the configuration's file under
``deviceless_memory_analysis``.

    python3 benchmark/tools/deviceless_stored.py [--config command-a-plus-ep8-serve] [--record] [--hlo DIR]

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; no number printed
here is a measurement.
"""
import argparse
import collections
import importlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
from tools.deviceless import (_DescribedPlace, compile_run_program,  # noqa: E402
                              describe_v5e, memory_of)

_MOSAIC = re.compile(r"%([\w\-]+?)[.\d]* = [^\n]*"
                     r'custom_call_target="tpu_custom_call"')


def build(cfg: dict) -> dict:
    reference = importlib.import_module(f"reference.{cfg['family']}")
    cfg["model"] = reference.model_config(cfg)
    return importlib.import_module(f"families.{cfg['family']}").build(cfg)


def _names(net: dict, first: str):
    stats = net.get("expert_stats")
    return [net[first].name] + ([stats.name] if stats is not None else [])


def compile_chained(program, fetch_names, steps: int, dev):
    """The executable ``run_chained(program, fetch_list=fetch_names,
    steps=steps)`` builds, compiled for ``dev`` from shapes alone (as
    ``tools/deviceless_decode.py`` does for one fixed fetch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu.core.types import np_dtype

    exe = fluid.Executor(fluid.CPUPlace())
    exe.place = _DescribedPlace(dev)
    step, _ = exe._lookup_chained(program, program, {}, list(fetch_names),
                                  steps, fluid.Scope(), None)
    on_dev = SingleDeviceSharding(dev)
    block = program.global_block

    def var(name):
        v = block.var(name)
        dt = jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype)))
        return jax.ShapeDtypeStruct(tuple(int(d) for d in v.shape), dt,
                                    sharding=on_dev)

    placed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                            sharding=on_dev)
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


def compile_all(cfg: dict, dev) -> dict:
    """name -> compiled executable, for every program of the engine."""
    net = build(cfg)
    out = {f"prefill:{b}": compile_run_program(
        p["main"], _names(p, "first_token"), dev)
        for b, p in net["prefill"].items()}
    out["chained decode"] = compile_chained(
        net["decode"]["main"], _names(net["decode"], "next_token"),
        cfg["serving"]["generation"]["decode_chunk"], dev)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="command-a-plus-ep8-serve")
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--hlo", default="", help="write each HLO text here")
    a = ap.parse_args()
    path = os.path.join(HERE, "configs", a.config + ".json")
    cfg = harness.load_json(path)
    rows = {}
    for name, compiled in compile_all(dict(cfg), describe_v5e()).items():
        text = compiled.as_text()
        m = memory_of(compiled)
        rows[name] = {"arguments": m["argument_size_in_bytes"],
                      "temp": m["temp_size_in_bytes"],
                      "mosaic_calls": dict(collections.Counter(
                          _MOSAIC.findall(text)))}
        print(json.dumps({"program": name, **rows[name]}), flush=True)
        if a.hlo:
            os.makedirs(a.hlo, exist_ok=True)
            with open(os.path.join(a.hlo, name.replace(":", "_").replace(
                    " ", "_") + ".txt"), "w") as f:
                f.write(text)
    if a.record:
        cfg["deviceless_memory_analysis"] = dict(
            how=f"python3 benchmark/tools/deviceless_stored.py --config "
                f"{a.config} --record (the compiler's own numbers for a "
                f"described v5e; bytes; not a measurement)",
            slots=cfg["serving"]["slots"], **rows)
        with open(path, "w") as f:
            json.dump(cfg, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
