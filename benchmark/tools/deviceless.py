"""Compile the program's executables for a v5e that is described and not
attached, and print what the compiler says each needs
(``memory_analysis()``): whether a geometry fits is known before any chip
time is spent. Only the programs that go through ``Executor.run`` (prefill
buckets, the chunk program, a training step); the chained decode scan is
built inside the executor and is small.

    python3 benchmark/tools/deviceless.py --config gpt2-base-serve [--slots 96,64]
    python3 benchmark/tools/deviceless.py --config bert-base-pretrain

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; no number printed
here is a measurement.
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


class _DescribedPlace:
    """Stands where the executor asks its place for the device."""

    def __init__(self, dev):
        self._dev = dev

    def jax_device(self):
        return self._dev


def describe_v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def compile_run_program(program, fetch_names, dev, batch=None):
    """The executable ``Executor.run(program, fetch_list=fetch_names)``
    would build, compiled for ``dev`` from shapes alone. ``batch`` fills a
    leading -1 of a feed."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu.core.types import np_dtype

    exe = fluid.Executor(fluid.CPUPlace())
    exe.place = _DescribedPlace(dev)
    block = program.global_block
    feeds = {n for n, v in block.vars.items() if getattr(v, "is_data", False)}
    step = exe._compile(program, feeds, list(fetch_names), fluid.Scope())
    sharding = SingleDeviceSharding(dev)

    def shaped(name):
        v = block.var(name)
        shape = tuple(batch if d in (-1, None) else int(d) for d in v.shape)
        dt = jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype)))
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sharding)
    args = ([shaped(n) for n in step.feed_names],
            [shaped(n) for n in step.donated_names],
            [shaped(n) for n in step.ro_names], key)
    return step.fn.lower(*args).compile()


def memory_of(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}


def serve_programs(cfg):
    import importlib

    net = importlib.import_module(f"families.{cfg['family']}").build(cfg)
    out = {f"prefill:{b}": (p["main"], [p["first_token"].name])
           for b, p in net["prefill"].items()}
    out[f"chunk:{net['prefill_chunk']}"] = (
        net["chunk"]["main"], [net["chunk"]["first_token"].name])
    return out


def train_programs(cfg):
    import importlib

    m = importlib.import_module(f"families.{cfg['family']}").build(cfg)
    return {"train step": (m["main"], [m["loss"].name])}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", default="")
    ap.add_argument("--batch", type=int, default=32)
    a = ap.parse_args()
    import harness

    cfg = harness.load_json(HERE, "configs", a.config + ".json")
    dev = describe_v5e()
    if cfg["runner"] == "serve":
        for slots in [int(s) for s in a.slots.split(",") if s] or [
                cfg["serving"]["slots"]]:
            cfg["serving"]["slots"] = slots
            for name, (prog, fetch) in serve_programs(cfg).items():
                print(json.dumps({"slots": slots, "program": name,
                                  **memory_of(compile_run_program(
                                      prog, fetch, dev))}), flush=True)
    else:
        for name, (prog, fetch) in train_programs(cfg).items():
            print(json.dumps({"program": name, "batch": a.batch,
                              **memory_of(compile_run_program(
                                  prog, fetch, dev, batch=a.batch))}),
                  flush=True)


if __name__ == "__main__":
    main()
