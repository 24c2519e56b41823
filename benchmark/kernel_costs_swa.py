"""``kernel_costs`` for a decoder whose layers are of two kinds, window and
full, with their own head counts, and whose keys are wider than its values
(``configs/mimo-v2-flash-ep16-serve.json``): the least time the chip could
take for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a tiling or a layout
adds; of bytes only those that have to cross HBM).

Every kernel here is read over the traced slice's own dispatches
(``readers.kernel_roofline_slice``): a function takes the configuration,
what the engine noted of each such dispatch (the attributes of its
``serving.settle`` span) and the device's peaks, and returns the least
seconds for all of them, or None.

* The expert matmul: ``kernel_costs.expert_matmul_cost`` with an expert's
  width read from ``moe_intermediate_size`` (this model's
  ``intermediate_size`` is the dense first layer's), a dispatch at a time
  from what its expert ops counted (``moe_expert_tokens``,
  ``moe_experts_hit``: assignments that reached the held experts, and held
  experts hit, summed over the dispatch's steps and layers): a decode
  chunk is bound by the hit experts' bytes, a prefill by the MXU.

* The decode kernel: the cache rows its walk fetched, by kind of cache
  (``attn_rows_full`` / ``attn_rows_window``: whole k-blocks up to each
  sequence's last live one; a ring, all of it once the window is passed), a
  key/value head's counted once: each is read once for its group's query
  heads, ``(head_dim + v_head_dim)`` numbers of the cache's type a row and
  key/value head (a key's 192; the zeros that pad its row to 256 lanes are
  the layout's and are not counted), and every query head makes one product
  over the key and one over the value. At 16 query heads a key/value head
  that is 16 operations a byte against the chip's 240: bound by the bytes.
* The flash forward of a prefill: per layer, query head and REAL prompt
  row ``i`` the keys the mask allows (``i + 1``, or ``min(i + 1, window)``
  in a window layer), a product over the key's 192 and one over the value's
  128 each. Padding rows of a bucket stand still. No byte is counted: q, k
  and v are the projections' results of the same program, and where they
  lie between the two is the compiler's choice (``kernel_costs``' rule for
  operands an earlier operation produced). No entry reads it yet: the
  traced slice of this cell's mix holds no prefill (PERF.md section 7);
  PERF.md section 6 sets it against a one-off profile's times.
"""
from __future__ import annotations

from kernel_costs import _least_seconds
# ``kernel_costs.moe_expert_matmul_slice_seconds`` handed an expert's width
# under the key it reads
from kernel_costs_hybrid import moe_expert_matmul_slice_seconds  # noqa: F401
from kernel_costs_latent import _ITEMSIZE

KINDS = (("full", ""), ("window", "swa_"))


def decode_attention_cost(rows: float, heads: int, kv_heads: int, qk: int,
                          vd: int, itemsize: int = 2):
    """(operations, bytes) of the decode kernel over ``rows`` cache rows a
    key/value head: every query head a product over a key and one over a
    value a row; the row's key and value read once a key/value head."""
    return (rows * heads * 2.0 * (qk + vd),
            rows * kv_heads * float(qk + vd) * itemsize)


def decode_attention_slice_seconds(config: dict, dispatches, peaks: dict):
    ops = moved = 0.0
    size = _ITEMSIZE[config["storage_dtype"]]
    for d in dispatches:
        for kind, pre in KINDS:
            o, b = decode_attention_cost(
                float(d.get(f"attn_rows_{kind}", 0)),
                config[pre + "num_attention_heads"],
                config[pre + "num_key_value_heads"],
                config[pre + "head_dim"], config[pre + "v_head_dim"], size)
            ops, moved = ops + o, moved + b
    return _least_seconds(ops, moved, peaks) or None


def flash_fwd_cost(lengths, layers: int, heads: int, qk: int, vd: int,
                   window: int):
    """(operations, bytes) of ``layers`` flash forwards over prompts of
    ``lengths`` real rows."""
    ops = 0.0
    for n in lengths:
        w = min(window, n) if window else n
        allowed = w * (w + 1) / 2.0 + (n - w) * w
        ops += layers * heads * allowed * 2.0 * (qk + vd)
    return ops, 0.0

