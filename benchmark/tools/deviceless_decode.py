"""Compile the chained decode executable (``Executor.run_chained`` over the
decode program: a scan whose carry is the donated KV caches) for a v5e that
is described and not attached, and say what ``deviceless.py`` cannot: the
compiler's memory for it, and which instructions produce a whole cache
inside the loop and around it. A ``copy`` or a select of cache shape in the
loop body is paid every token; a ``dynamic-update-slice`` is the in-place
append.

    python3 benchmark/tools/deviceless_decode.py [--slots 64,80] [--hlo out.txt]

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; counts of
instructions and the compiler's bytes, no measurement.
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
from tools.deviceless import (_DescribedPlace, describe_v5e,  # noqa: E402
                              memory_of)

_HEAD = re.compile(r"^(ENTRY )?%?[\w.\-]+ \(.*\{$")
_PLUMBING = {"get-tuple-element", "parameter", "bitcast", "tuple", "while"}


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


def whole_cache_instructions(hlo_text: str, cache_shape) -> dict:
    """``{"loop": Counter, "entry": Counter}`` of the opcodes whose result
    is a whole cache (as stored, or reshaped to [B*H, S, D] for the
    kernel), tuple plumbing left out; fused computations are read through
    the fusion that calls them."""
    b, h, s, d = cache_shape
    shape = re.compile(r" = \(?f32\[(?:%d,%d,%d,%d|%d,%d,%d)\]\{.*?[})] "
                       r"([a-z][\w\-]*)\(" % (b, h, s, d, b * h, s, d))
    out = {"loop": collections.Counter(), "entry": collections.Counter()}
    where = None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head:
            where = None if "fused_computation" in line else (
                "entry" if head.group(1) else "loop")
        elif where:
            m = shape.search(line)
            if m and m.group(1) not in _PLUMBING:
                name = re.match(r"\s+(?:ROOT )?%?([\w\-]+?)[.\d]* = ", line)
                out[where][name.group(1)] += 1
    return out


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
        m, s = cfg["model"], cfg["serving"]
        row = {"slots": s["slots"], "program": "chained decode"}
        try:
            compiled = compile_chained_decode(cfg, dev)
        except Exception as e:      # the compiler's refusal is the answer
            print(json.dumps(dict(row, fits=False,
                                  error=str(e).split("\n")[0][:300])),
                  flush=True)
            continue
        text = compiled.as_text()
        found = whole_cache_instructions(text, (
            s["slots"], m["num_heads"], s["max_seq"],
            m["hidden_size"] // m["num_heads"]))
        print(json.dumps(dict(row, **memory_of(compiled),
                              whole_cache_in_loop=dict(found["loop"]),
                              whole_cache_at_entry=dict(found["entry"]))),
              flush=True)
        if a.hlo:
            with open(a.hlo, "w") as f:
                f.write(text)


if __name__ == "__main__":
    main()
