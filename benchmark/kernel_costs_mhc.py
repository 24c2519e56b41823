"""``kernel_costs`` for a decoder whose residual path is ``hc_mult``
streams mixed by manifold-constrained hyper-connections
(``configs/xing4.0-29b-a4b-ep8-serve.json``): the least time the chip could
take for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a tiling adds; of bytes
only those that have to cross HBM). A function takes the configuration, the
window's counter deltas and the device's peaks and returns ``(seconds,
calls)``, or None where the program has no such counter.

* The hyper-connection of one sublayer, whatever implements it. Always:
  the three projections' weights ``[n (n + 2), n C]`` f32, parameters,
  once a call, and the operations (the projections' ``2 n C n (n + 2)`` a
  token row, the two mixes' ``2 n C (n + 2)``). The streams themselves (a
  token row's ``n x C`` f32 numbers: read once for the read, from which
  the flattened norm, the projections and ``H_pre X`` all come, and read
  once and written once for the write ``H_res X + H_post^T y``) are an
  earlier operation's result and may sit in on-chip memory: a decode
  step's 256 rows x 57 KB = 14.7 MB do (the compiled decode program keeps
  14 of a step's 16 rewritten streams in memory space ``S(1)`` and stages
  the other two; the kernels then run at 1.6 times what their bytes would
  allow at 819 GB/s: my chip run, PR 52), and so do 8 of the 16 of a
  prefill of 768 or of 1,024 rows. They bound a call only where they cannot: where one copy coming in
  and one going out, ``2 x rows x n C x 4`` B, pass the chip's
  ``ON_CHIP_BYTES`` (1,280 and 1,536 rows: the compiled programs keep
  none of those on the chip), and then three passes of them count. The
  sublayer's output ``y`` and the read's ``u`` (a quarter of a stream
  each, which the compiler does keep on the chip) and the coefficients
  bound nothing. A call's rows are the label ``call_rows`` of
  ``hyper_connection_rows_total`` / ``_calls_total`` (padding rows of a
  bucket among them, which the op does mix). One call of the op pair is
  two device operations (``hc_read``, ``hc_write``), so the calls returned
  are twice the counter's, as ``kernel_costs.moe_expert_matmul_seconds``
  counts its two.
* The latent decode kernel: ``kernel_costs_latent.mla_decode_seconds``,
  which reads this configuration's heads and widths from its file (32
  heads on rows of 512 + 64).
* The expert matmul over the traced slice's own dispatches:
  ``kernel_costs.moe_expert_matmul_slice_seconds`` with an expert's width
  read from ``moe_intermediate_size`` (this model's ``intermediate_size``
  is the dense layers'). The compiled decode program stages no expert
  stack ahead of the kernel (``bf16[8,3584,1024]``, 58.7 MB, appears in no
  ``ConcatBitcast``), so the kernel's own time holds the bytes counted.
"""
from __future__ import annotations

import kernel_costs
from kernel_costs import _least_seconds
from kernel_costs_latent import mla_decode_seconds  # noqa: F401

# a v5e chip's on-chip vector memory, which the TPU compiler also places
# operands in (memory space S(1)): 128 MiB (Google Cloud documentation,
# "TPU v5e" system architecture, as ``peaks.json``)
ON_CHIP_BYTES = 128 * 1024 * 1024


def hyper_connection_cost(rows: float, calls: float, n: int, C: int):
    """(operations, bytes) of ``calls`` read-and-write pairs of ``rows /
    calls`` token rows each: f32 throughout; the streams' three passes
    where a call's do not fit the chip beside their rewritten copy."""
    m = n * (n + 2)
    ops = rows * (2.0 * n * C * m + 2.0 * n * C * (n + 2))
    moved = calls * 4.0 * m * n * C
    if calls and 2 * (rows / calls) * n * C * 4 > ON_CHIP_BYTES:
        moved += rows * 4.0 * 3 * n * C
    return ops, moved


def _by_call_rows(counters: dict, name: str) -> dict:
    """``{rows of a call: the family's sum over its other labels}``."""
    out = {}
    for key, v in counters.items():
        fam, _, rest = key.partition("{")
        if fam != name:
            continue
        labels = dict(kv.split("=", 1)
                      for kv in rest.partition("}")[0].split(",") if kv)
        if "call_rows" in labels:
            n = int(labels["call_rows"])
            out[n] = out.get(n, 0.0) + v
    return out


def hyper_connection_seconds(config: dict, counters: dict, peaks: dict):
    """A size of call at a time, summed; the calls are device operations
    (two a pair)."""
    n, C = config["hc_mult"], config["hidden_size"]
    calls = _by_call_rows(counters, "hyper_connection_calls_total")
    total = sum(_least_seconds(*hyper_connection_cost(
        size * count, count, n, C), peaks)
        for size, count in calls.items() if count)
    return (total, 2 * sum(calls.values())) if total else None


def moe_expert_matmul_slice_seconds(config: dict, dispatches, peaks: dict):
    return kernel_costs.moe_expert_matmul_slice_seconds(
        dict(config, intermediate_size=config["moe_intermediate_size"]),
        dispatches, peaks)
