"""``kernel_costs`` for a decoder with recurrent layers and many small
experts (``configs/qwen3-next-ep2-serve.json``): the least time the chip
could take for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a tiling adds; of bytes
only those that have to cross HBM). A function takes the configuration,
the window's counter deltas and the device's peaks and returns ``(seconds,
calls)``, or None where the program has no such counter.

* The expert matmul: ``kernel_costs.expert_matmul_cost`` with an expert's
  width read from ``moe_intermediate_size`` (this model's
  ``intermediate_size`` is a dense width that no layer uses).
* The gated delta rule's decode step: per token and value head the state
  ``[Dk, Dv]`` f32 is read and written once (64 slots x 32 heads x 64 KB =
  134 MB a layer: it cannot sit on the chip between steps), and three
  ``Dk x Dv`` products are made (decay aside: ``S^T k``, ``k u^T``,
  ``S^T q``). Bound by the bytes.
* The rule's scan over a prompt: the same three products a head and row,
  which is the least any chunking needs (a chunked form adds the products
  inside a chunk on top), and its operands' bytes: q, k, v in and o out,
  f32, once. Only rows of real tokens count (``gdn_tokens_total``): padding
  stands still.
"""
from __future__ import annotations

import kernel_costs
from harness import sum_matching
from kernel_costs import _least_seconds, expert_matmul_cost  # noqa: F401

F32 = 4


def moe_expert_matmul_seconds(config: dict, counters: dict, peaks: dict):
    """``kernel_costs.moe_expert_matmul_seconds`` itself, handed an
    expert's width under the key it reads."""
    return kernel_costs.moe_expert_matmul_seconds(
        dict(config, intermediate_size=config["moe_intermediate_size"]),
        counters, peaks)


def moe_expert_matmul_slice_seconds(config: dict, dispatches, peaks: dict):
    """``kernel_costs.moe_expert_matmul_slice_seconds`` itself, handed an
    expert's width under the key it reads."""
    return kernel_costs.moe_expert_matmul_slice_seconds(
        dict(config, intermediate_size=config["moe_intermediate_size"]),
        dispatches, peaks)


def gdn_step_cost(tokens: float, Hv: int, Dk: int, Dv: int):
    """(operations, bytes) of ``tokens`` single steps of the rule: three
    Dk x Dv products a head; the head's state read and written."""
    return (tokens * Hv * 3 * 2.0 * Dk * Dv,
            tokens * Hv * 2.0 * Dk * Dv * F32)


def gdn_scan_cost(tokens: float, Hk: int, Hv: int, Dk: int, Dv: int):
    """(operations, bytes) of the rule over ``tokens`` rows of prompts:
    the recurrent form's three products a head and row; q and k (Hk heads),
    v and o (Hv heads) in f32, once each."""
    return (tokens * Hv * 3 * 2.0 * Dk * Dv,
            tokens * 2.0 * (Hk * Dk + Hv * Dv) * F32)


def _heads(config: dict):
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"])


def gdn_step_seconds(config: dict, counters: dict, peaks: dict):
    _, Hv, Dk, Dv = _heads(config)
    tokens = sum_matching(counters, "gdn_tokens_total", phase="decode")
    calls = sum_matching(counters, "gdn_calls_total", phase="decode")
    least = _least_seconds(*gdn_step_cost(tokens, Hv, Dk, Dv), peaks)
    return (least, calls) if least else None


def gdn_scan_seconds(config: dict, counters: dict, peaks: dict):
    tokens = sum_matching(counters, "gdn_tokens_total", phase="prefill")
    calls = sum_matching(counters, "gdn_calls_total", phase="prefill")
    least = _least_seconds(*gdn_scan_cost(tokens, *_heads(config)), peaks)
    return (least, calls) if least else None
