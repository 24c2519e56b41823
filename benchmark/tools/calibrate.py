"""Read, on the chip, the two numbers every limit of ``correct`` is set
from: what sound runs of the program give over many seeds, and what the
control gives (the reference computed one precision below the
configuration's). One process for all the seeds of a cell:

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 8]

Prints one JSON line per seed; nothing here is a metric.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402


def train(cell, chips, seeds, control_seeds, control, seconds):
    from runners import train as runner

    s = runner.Session(cell, chips)
    n = int(s.cfg["check"]["steps"])
    for seed in seeds:
        s.load(seed)
        prog = s.first_steps(n)
        ref = s.follow(n)
        row = {"seed": seed, "program": _train_numbers(runner, prog, ref)}
        if seed in control_seeds:
            row["control"] = _train_numbers(
                runner, s.follow(n, precision=control), ref)
        print(json.dumps(row), flush=True)


def _train_numbers(runner, got, ref):
    return {"grad_direction_gap": runner.direction_gap(
                got["first_grad"], ref["first_grad"], ref["grad_norm"]),
            "loss_gap_by_step": [abs(a - b) for a, b in
                                 zip(got["losses"], ref["losses"])],
            "grad_norm_gap": runner.worst_leaf_gap(
                got["grad_norm"], ref["grad_norm"], "first gradient norm"),
            "delta_norm_gap": runner.worst_leaf_gap(
                got["delta_norm"], ref["delta_norm"],
                "parameter change norm")}


def serve(cell, chips, seeds, control_seeds, control, seconds):
    from runners import serve as runner

    s = runner.Session(cell, chips)
    off = harness.TraceWindow(False, seconds, cell.name)
    for seed in seeds:
        s.load(seed, cell.traffic)
        w = s.window(cell.traffic, seconds, off)
        s.free_cache()
        served, ctl = s.gaps(w["finished"],
                             control if seed in control_seeds else "")
        row = {"seed": seed, "finished": len(w["finished"]),
               "failed": len(w["failed"]),
               "program_gap_max": max(served), "program_gaps": served}
        if seed in control_seeds:
            row["control_gap_min"] = min(ctl)
            row["control_gaps"] = ctl
        print(json.dumps(row), flush=True)


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
    fn = {"train": train, "serve": serve}[cell.config["runner"]]
    fn(cell, chips, ints(a.seeds), set(ints(a.control_seeds)), a.control,
       a.seconds)


if __name__ == "__main__":
    main()
