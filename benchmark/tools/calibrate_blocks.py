"""``tools/calibrate_stored.py`` for a cell whose runner is
``serve_blocks``: the two sides of its two limits. Per seed, one window of
the cell's own traffic, then the comparison of ``runners/serve_blocks.py``
on the sampled requests: the program's ``served_logit_gap_max`` and
``reveal_confidence_gap_max``, and on ``--control-seeds`` those of the
reference computed one precision below (``--control``, fp8 e4m3 operands),
which reveals its own positions and tokens of the same block states.

    python3 benchmark/tools/calibrate_blocks.py --workload <cell> \\
        --seeds 1,2,3 [--control-seeds 1,2,3] [--seconds 40]

Prints one JSON line per seed; nothing here is a metric.
"""
import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
from runners import serve_blocks                            # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=40.0)
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
    s = serve_blocks.Session(cell, chips)
    off = harness.TraceWindow(False, a.seconds, cell.name)
    controls = set(ints(a.control_seeds))
    for seed in ints(a.seeds):
        s.load(seed, cell.traffic)
        w = s.window(cell.traffic, a.seconds, off)
        s.free_cache()
        logit, ctl_logit = s.gaps(w["finished"],
                                  a.control if seed in controls else "")
        row = {"seed": seed, "finished": len(w["finished"]),
               "failed": len(w["failed"]),
               "program_logit_gap_max": max(logit),
               "program_confidence_gap_max": max(s.confidence_gaps),
               "program_logit_gaps": logit,
               "program_confidence_gaps": s.confidence_gaps}
        if seed in controls:
            row.update(control_logit_gap_max=max(ctl_logit),
                       control_confidence_gap_max=max(
                           s.control_confidence_gaps),
                       control_logit_gaps=ctl_logit,
                       control_confidence_gaps=s.control_confidence_gaps)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
