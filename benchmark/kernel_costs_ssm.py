"""``kernel_costs`` for a decoder with Mamba-2 layers and small experts
(``configs/granite-4.0-h-small-ep2-serve.json``): the least time the chip
could take for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a chunking adds; of bytes
only those that have to cross HBM; rows of real tokens only). A function
takes the configuration, the window's counter deltas and the device's peaks
and returns ``(seconds, calls)``, or None where the program has no such
counter.

* The expert matmul: ``kernel_costs.moe_expert_matmul_seconds`` as it is
  (this model's ``intermediate_size`` is one expert's width).
* The selective scan's decode step: per token and head the state ``[P, N]``
  f32 is read and written once (64 slots x 128 heads x 32 KB = 268 MB a
  layer: it cannot sit on the chip between steps), and two ``P x N``
  products are made (decay aside: ``u B^T`` into the state, ``S C`` out of
  it). Bound by the bytes.
* The scan over a prompt: the same two products a head and row, which is
  the least any chunking needs (the dual form adds the products inside a
  chunk on top), and its operands' bytes: ``u`` in and ``y`` out (``H P``
  each), ``B`` and ``C`` (``N`` each) and the log-decay (``H``), f32, once.
  Only rows of real tokens count (``ssm_tokens_total``): padding stands
  still.
"""
from __future__ import annotations

from harness import sum_matching
from kernel_costs import (_least_seconds, expert_matmul_cost,  # noqa: F401
                          moe_expert_matmul_seconds)

F32 = 4


def ssd_step_cost(tokens: float, H: int, P: int, N: int):
    """(operations, bytes) of ``tokens`` single steps of the scan: two
    P x N products a head; the head's state read and written."""
    return (tokens * H * 2 * 2.0 * P * N, tokens * H * 2.0 * P * N * F32)


def ssd_scan_cost(tokens: float, H: int, P: int, N: int):
    """(operations, bytes) of the scan over ``tokens`` rows of prompts: the
    recurrent form's two products a head and row; u and y (H heads of P), B
    and C (N) and the log-decay (H) in f32, once each."""
    return (tokens * H * 2 * 2.0 * P * N,
            tokens * (2.0 * H * P + 2 * N + H) * F32)


def _heads(config: dict):
    return (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"])


def _seconds(cost, phase: str, config: dict, counters: dict, peaks: dict):
    tokens = sum_matching(counters, "ssm_tokens_total", phase=phase)
    calls = sum_matching(counters, "ssm_calls_total", phase=phase)
    least = _least_seconds(*cost(tokens, *_heads(config)), peaks)
    return (least, calls) if least else None


def ssd_step_seconds(config: dict, counters: dict, peaks: dict):
    return _seconds(ssd_step_cost, "decode", config, counters, peaks)


def ssd_scan_seconds(config: dict, counters: dict, peaks: dict):
    return _seconds(ssd_scan_cost, "prefill", config, counters, peaks)
