"""A cell's expert-matmul roofline through BOTH readers on ONE profile: the
cell run once, traced, as ``run.py`` runs it, and beside its result line

* ``window``: ``readers.kernel_roofline_in`` (the whole window's
  ``moe_expert_*`` counters over the calls they saw, against the traced
  slice's mean call), and
* ``slice``: ``readers.kernel_roofline_slice`` (the joined dispatches' own
  counts against the time of the kernel's operations inside their modules),
  over the decode chunks alone (``chained``) and over both paths,

A slice whose calls are lighter than the window's mean reads high through
``window`` (PERF.md section 5 has PR 51's readings of six cells).

    chiprun -- python3 benchmark/tools/roofline_readers.py \
        --workload <cell> --seed <n> [--seconds 40]

On the chip only: a CPU rehearsal has no device trace. Where the compiler
stages one of the kernel's weight stacks in fast memory ahead of the call
(GLM-4.7-Flash's decode program: PERF.md section 6, PR 51) both read over
100: the kernel's time then leaves out bytes that both count.
"""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
import run                                                  # noqa: E402
from readers import kernel_roofline_in, kernel_roofline_slice  # noqa: E402

PATTERN = r"^%?moe_expert_matmul[.\d]* = "


def both(cell, ctx, read=harness.read_layer_metrics):
    out = read(cell, ctx)
    # an expert's width is ``moe_intermediate_size`` where a configuration
    # has the key (``kernel_costs_hybrid`` hands it over), else
    # ``intermediate_size``
    module = ("kernel_costs_hybrid" if "moe_intermediate_size" in cell.config
              else "kernel_costs")
    got = {
        "window": kernel_roofline_in.read(
            ctx, PATTERN, module, "moe_expert_matmul_seconds"),
        "slice_chained": kernel_roofline_slice.read(
            ctx, PATTERN, module, "moe_expert_matmul_slice_seconds",
            path="chained"),
        "slice_both_paths": kernel_roofline_slice.read(
            ctx, PATTERN, module, "moe_expert_matmul_slice_seconds"),
    }
    harness.say(f"roofline_readers {cell.name}: {got}")
    return out


if __name__ == "__main__":
    harness.read_layer_metrics = both
    sys.exit(run.main(sys.argv[1:] + ["--trace", "1"]))
