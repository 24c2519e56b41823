"""``tools/calibrate.py`` for a cell whose runner is ``serve_stored``: the
same readings (the program's logit gaps over sound runs, and the gaps of
the reference computed one precision below), with the session that makes
its weights a tensor at a time.

    python3 benchmark/tools/calibrate_stored.py --workload <cell> \\
        --seeds 1,2,3 [--control-seeds 1,2,3] [--seconds 8]

Prints one JSON line per seed; nothing here is a metric.
"""
import argparse
import importlib
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
from runners import serve, serve_stored                     # noqa: E402
from tools import calibrate                                 # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    ints = lambda t: [int(x) for x in t.split(",") if x]
    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell = harness.Cell(bench, a.workload, rehearse=a.rehearse)
    harness.use_compile_cache()
    chips = harness.find_chips(cell)
    reference = importlib.import_module(
        f"reference.{cell.config['family']}")
    cell.config["model"] = reference.model_config(cell.config)
    serve.Session = serve_stored.Session    # calibrate.serve builds this name
    calibrate.serve(cell, chips, ints(a.seeds), set(ints(a.control_seeds)),
                    a.control, a.seconds)


if __name__ == "__main__":
    main()
