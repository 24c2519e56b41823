"""``kernel_costs`` for a decoder with latent attention and a dense first
layer (``configs/glm-4.7-flash-ep8-serve.json``): the least time the chip
could take for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a tiling adds; of bytes
only those that have to cross HBM). A function takes the configuration,
the window's counter deltas and the device's peaks and returns ``(seconds,
calls)``, or None where the program has no such counter.

* The expert matmul: ``kernel_costs.expert_matmul_cost`` with an expert's
  width read from ``moe_intermediate_size`` (this model's
  ``intermediate_size`` is the dense first layer's). No entry reads it
  since PR 51: in this configuration's decode program the compiler stages
  the gate stack of six of the seven expert layers (8 x 2048 x 1536 bf16,
  50 MB: it fits the fast memory) ahead of the kernel through asynchronous
  slices, so the kernel's own time leaves out two sevenths of the bytes
  this function counts, and both readers read 104-109% (PERF.md section 6,
  PR 51). ``tools/roofline_readers.py`` still reads both.
* The latent decode kernel: a cache row ``[c (dc) | k_rope (dr)]`` is
  fetched once for all heads (``(dc + dr)`` numbers of the cache's type:
  128 slots x 1,750 rows x 1,152 B is 258 MB a layer, it cannot sit on
  the chip between steps; the zeros that pad a row to whole lane tiles
  are the layout's, not the algorithm's, and are not counted), and each
  head makes one product over the whole row (``q . k``) and one over its
  ``c`` part (``p . v``): ``2 x heads x (2 dc + dr)`` operations a row. The rows are the ones the program says
  its walk fetched (``latent_attention_rows_total``, phase ``decode``:
  whole blocks up to each sequence's last live one), which is what the
  kernel has to move and score given its tile; the absorbed query and the
  output are other operations' results and bound nothing. At 20 heads that
  is 38 operations a byte against the chip's 240: bound by the bytes, if
  the 20 heads' rows fill the MXU's 128.
"""
from __future__ import annotations

from harness import sum_matching
from kernel_costs import _least_seconds
# ``kernel_costs.moe_expert_matmul_seconds`` handed an expert's width under
# the key it reads
from kernel_costs_hybrid import moe_expert_matmul_seconds  # noqa: F401

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def mla_decode_cost(rows: float, heads: int, dc: int, dr: int,
                    itemsize: int = 2):
    """(operations, bytes) of the latent decode kernel over ``rows`` cache
    rows: per row and head a product over ``dc + dr`` and one over ``dc``;
    the row's ``dc + dr`` numbers read once."""
    return (rows * heads * 2.0 * (2 * dc + dr),
            rows * float(dc + dr) * itemsize)


def mla_decode_seconds(config: dict, counters: dict, peaks: dict):
    rows = sum_matching(counters, "latent_attention_rows_total",
                        phase="decode")
    calls = sum_matching(counters, "latent_attention_calls_total",
                         phase="decode")
    least = _least_seconds(*mla_decode_cost(
        rows, config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_rope_head_dim"],
        _ITEMSIZE[config["storage_dtype"]]), peaks)
    return (least, calls) if least else None
