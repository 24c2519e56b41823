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


def router_cost(tokens: float, H: int, E: int):
    """(operations, bytes) of the router's products over ``tokens`` rows in
    all. No byte of it has to come from HBM: rows and scores are other
    operations' results, and the weights (2 MB in f32) are converted once
    outside the decode loop and stay where the compiler put them."""
    return 2.0 * tokens * H * E, 0.0


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


def moe_router_seconds(config: dict, counters: dict, peaks: dict):
    """The router scores every row of a dispatch: ``slots`` rows a decode
    step, ``prefill_rows x bucket`` rows a prefill (one bucket in this
    configuration)."""
    s = config["serving"]
    rows = {"decode": s["slots"],
            "prefill": s.get("prefill_rows", s["slots"])
            * max(s["prompt_buckets"])}
    H, E = config["hidden_size"], config["deployment"]["num_experts_total"]
    total = 0.0
    for phase in PHASES:
        calls = sum_matching(counters, "moe_expert_calls_total", phase=phase)
        total += _least_seconds(*router_cost(calls * rows[phase], H, E),
                                peaks)
    calls = sum_matching(counters, "moe_expert_calls_total")
    return (total, calls) if total else None
