"""One process, one cell, one run:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration and traffic mix by name through
``BENCHMARK.json``, finds the chip (no TPU is an error), runs the
configuration's runner and prints the contract's one JSON line last.
``--rehearse`` drives the same control flow at the files' tiny
``rehearsal`` sizes on whatever JAX finds (the CPU) and prints no metric
under any name.
"""
import time

T_PROCESS = time.perf_counter()     # set-up counts from here

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402


def main(argv=None, broken=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no metric")
    args = ap.parse_args(argv)
    try:
        bench = harness.load_json(harness.REPO, "BENCHMARK.json")
        cell = harness.Cell(bench, args.workload, rehearse=args.rehearse)
        harness.use_compile_cache()
        chips = harness.find_chips(cell)
        runner = importlib.import_module(f"runners.{cell.config['runner']}")
        result = runner.run(cell, chips, args, T_PROCESS, broken=broken)
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return harness.print_result(cell, chips, result, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
