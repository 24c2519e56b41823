"""On the chip: build the serving configuration at several slot counts and
run ``warm_up()`` (every executable compiles and runs once on zeroed
state), printing each executable's compiler-reported memory. Finds the
largest geometry that fits; the chained decode scan cannot be compiled
deviceless by ``deviceless.py``.

    python3 benchmark/tools/probe_slots.py --config gpt2-base-serve --slots 80,64,48
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", required=True)
    ap.add_argument("--decode-only", action="store_true")
    a = ap.parse_args()
    import importlib

    import paddle_tpu as fluid

    harness.use_compile_cache()
    for slots in [int(s) for s in a.slots.split(",")]:
        cfg = harness.load_json(HERE, "configs", a.config + ".json")
        cfg["serving"]["slots"] = slots
        family = importlib.import_module(f"families.{cfg['family']}")
        net = family.build(cfg)
        exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
        exe.run(net["startup"], scope=scope)
        eng = family.engine(cfg, net, scope, exe)
        row = {"slots": slots}
        t0 = time.perf_counter()
        try:
            if a.decode_only:
                eng.reset_generation_state()
                for _ in range(3):
                    t1 = time.perf_counter()
                    exe.run_chained(net["decode"]["main"], feed={},
                                    fetch_list=[net["decode"]["next_token"]],
                                    steps=cfg["serving"]["generation"][
                                        "decode_chunk"], scope=scope)
                    row["decode_chunk_s"] = time.perf_counter() - t1
            else:
                row["executables"] = eng.warm_up()
            row["fits"] = True
        except Exception as e:
            row["fits"] = False
            row["error"] = str(e).split("\n")[0][:300]
        row["seconds"] = time.perf_counter() - t0
        row["memory"] = []
        for step in exe._cache.values():
            aot = getattr(step, "_aot", None)
            try:
                m = aot.memory_analysis()
                row["memory"].append({
                    "fetch": list(step.fetch_names),
                    "arguments": int(m.argument_size_in_bytes),
                    "temp": int(m.temp_size_in_bytes)})
            except Exception:
                continue
        print(json.dumps(row), flush=True)
        for name in list(scope.vars):
            scope.drop_var(name)
        del eng, exe, scope, net


if __name__ == "__main__":
    main()
