"""``tools/deviceless_stored.py`` for a ``serve_blocks`` configuration,
whose prefill streams no token and whose decode net yields blocks: the
same compiles for a described v5e (each prefill bucket through
``Executor.run``, the chained decode scan through ``run_chained``), with
the fetches the engine asks of such a model.

    python3 benchmark/tools/deviceless_blocks.py [--config sdar-30b-a3b-serve] [--record] [--hlo DIR]

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; no number printed
here is a measurement.
"""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from tools import deviceless_stored as stored                # noqa: E402
from tools.deviceless import compile_run_program             # noqa: E402


def compile_all(cfg: dict, dev) -> dict:
    """name -> compiled executable, for every program of the engine."""
    net = stored.build(cfg)
    out = {f"prefill:{b}": compile_run_program(
        p["main"], [p["expert_stats"].name], dev)
        for b, p in net["prefill"].items()}
    dec = net["decode"]
    out["chained decode"] = stored.compile_chained(
        dec["main"], [v.name for v in dec["yield"].values()]
        + [dec["expert_stats"].name],
        cfg["serving"]["generation"]["decode_chunk"], dev)
    return out


if __name__ == "__main__":
    if "--config" not in sys.argv:
        sys.argv += ["--config", "sdar-30b-a3b-serve"]
    stored.compile_all = compile_all
    stored.main()
    if "--record" in sys.argv:      # the record names the tool that made it
        import json
        path = os.path.join(HERE, "configs", sys.argv[
            sys.argv.index("--config") + 1] + ".json")
        text = open(path).read().replace("tools/deviceless_stored.py",
                                         "tools/deviceless_blocks.py")
        with open(path, "w") as f:
            f.write(json.dumps(json.loads(text), indent=2) + "\n")
