"""Find the knee of an open-loop cell once, on the chip: the same mix at
several fixed rates in one process, each for ``--seconds``. For each rate:
requests failed, the backlog (queued + resident) at the middle of the
window and over its last tenth, and the tails. The knee is the highest
rate at which the backlog at the end is no larger than at the middle and
no request fails.

    python3 benchmark/tools/sweep.py --workload <cell> --rates 4,8,12 --seconds 20
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=77001)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    from runners import serve as runner

    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell = harness.Cell(bench, a.workload, rehearse=a.rehearse)
    harness.use_compile_cache()
    chips = harness.find_chips(cell)
    s = runner.Session(cell, chips)
    off = harness.TraceWindow(False, a.seconds, cell.name)
    for i, rate in enumerate(float(x) for x in a.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        s.load(a.seed + i, mix)
        w = s.window(mix, a.seconds, off)
        print(json.dumps({
            "rate_per_s": rate, "judged": len(w["judged"]),
            "failed": len(w["failed"]), "backlog_mid": w["backlog_mid"],
            "backlog_end": w["backlog_end"], **w["e2e"]}), flush=True)


if __name__ == "__main__":
    main()
