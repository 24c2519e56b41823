"""The least time the chip could take for the work a kernel did, from the
shapes of its calls: the larger of its operations over the peak FLOP/s and
its bytes over the peak bytes/s (``peaks.json``). 2 operations a
multiply-add. What is counted is what the algorithm needs, never what a
tiling moves on top of it (padding rows, a weight block read again for a
second tile of the same expert), and of bytes only those that have to come
from HBM: parameters. An operand that an earlier operation of the same
program produced may sit in on-chip memory (the compiler places the
router's rows, weights and scores in memory space ``S(1)``, and its calls
take less time than their bytes would at 819 GB/s), so it bounds nothing.
A share of this over the trace's time then cannot pass 100%.

A function takes the configuration, the window's counter deltas and the
device's peaks, and returns ``(seconds, calls)``: the least seconds for
all the kernel's calls the counters saw, and how many calls those were; or
None where the program has no such counter. The calls of a data-dependent
kernel are known through the program's counters (``moe_expert_*``, one
child per layer and phase, fed by what the expert op itself counted on the
device): how many assignments reached the held experts and how many experts
were hit, in how many executions.
"""
from __future__ import annotations

from harness import sum_matching

PHASES = ("prefill", "decode")


def expert_matmul_cost(assignments: float, experts_hit: float, H: int,
                       F: int, w_bytes: int = 2):
    """(operations, bytes) of the grouped gate-up and down products for
    ``assignments`` token rows spread over ``experts_hit`` expert
    executions: three H x F products a row; each hit expert's three
    matrices read once."""
    return (2.0 * 3 * H * F * assignments,
            experts_hit * 3.0 * H * F * w_bytes)


def _least_seconds(ops: float, moved: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def moe_expert_matmul_seconds(config: dict, counters: dict, peaks: dict):
    """Phase by phase (a decode step is bound by the weights' bytes, a
    prefill by the MXU), summed."""
    H, F = config["hidden_size"], config["intermediate_size"]
    total = 0.0
    for phase in PHASES:
        ops, moved = expert_matmul_cost(
            sum_matching(counters, "moe_expert_tokens_total", phase=phase),
            sum_matching(counters, "moe_experts_hit_total", phase=phase),
            H, F)
        total += _least_seconds(ops, moved, peaks)
    # an execution of the expert op calls the kernel twice: gate-and-up,
    # then down
    calls = 2 * sum_matching(counters, "moe_expert_calls_total")
    return (total, calls) if total else None


def moe_expert_matmul_slice_seconds(config: dict, dispatches, peaks: dict):
    """The same over the traced slice's own dispatches
    (``readers.kernel_roofline_slice``): ``dispatches`` holds what the
    engine noted of each on its ``serving.settle`` span, among it what the
    dispatch's expert ops counted (``moe_expert_tokens``,
    ``moe_experts_hit``: assignments that reached the held experts and held
    experts hit, summed over the dispatch's steps and layers). A dispatch
    at a time: a decode chunk is bound by the hit experts' bytes, a prefill
    by the MXU. Least seconds for all of them, or None where no span
    carries the counts (the parent commit, a model without experts)."""
    H, F = config["hidden_size"], config["intermediate_size"]
    total = sum(_least_seconds(*expert_matmul_cost(
        float(d["moe_expert_tokens"]), float(d["moe_experts_hit"]), H, F),
        peaks) for d in dispatches if "moe_experts_hit" in d)
    return total or None
