"""``kernel_costs`` for a decoder that generates by diffusion over blocks
(``configs/sdar-30b-a3b-serve.json``): the least time the chip could take
for the work the kernels did, by the same rules (2 operations a
multiply-add; what the algorithm needs, never what a tiling adds; of bytes
only those that have to cross HBM). A function takes the configuration,
the window's counter deltas and the device's peaks and returns ``(seconds,
calls)``, or None where the program has no such counter.

* The expert matmul: ``kernel_costs.expert_matmul_cost`` with an expert's
  width read from ``moe_intermediate_size`` (this model's
  ``intermediate_size`` is a dense layer's, which it has none of).
* The decode kernel under the in-block mask: a decode forward carries a
  block of ``L`` rows a sequence, and every row sees the sequence's rows
  before the block and the whole block: ``keys`` rows. Each of them is
  read once, key and value, from each key/value head's cache (64 slots x
  1,000 rows x 2 KB a layer cannot sit on the chip between forwards), for
  all the ``group`` query heads that share the head and all ``L`` rows
  of the block; each query head and row makes one product with the key
  and one with the value: ``4 x heads x L x head_dim`` operations a key
  row. The key rows are the ones the serving layer counts from the
  lengths it holds (``decode_attention_keys_total``: per forward, resident
  sequence and layer), whatever the kernel's tile fetches past them; the
  block's own queries and the output are other operations' results and
  bound nothing. At 32 query heads over 4 and blocks of 4 that is 32
  operations a byte against the chip's 240: bound by the bytes.
"""
from __future__ import annotations

from harness import sum_matching
from kernel_costs import _least_seconds
# ``kernel_costs.moe_expert_matmul_seconds`` handed an expert's width under
# the key it reads
from kernel_costs_hybrid import moe_expert_matmul_seconds  # noqa: F401
from kernel_costs_latent import _ITEMSIZE


def block_attention_cost(keys: float, heads: int, kv_heads: int,
                         head_dim: int, block: int, itemsize: int = 2):
    """(operations, bytes) of the decode kernel over ``keys`` visible key
    rows (summed over sequences and calls): per row, query head and row of
    the block a product with the key and one with the value; the row's key
    and value read once a key/value head."""
    return (keys * 4.0 * heads * block * head_dim,
            keys * 2.0 * kv_heads * head_dim * itemsize)


def block_attention_seconds(config: dict, counters: dict, peaks: dict):
    keys = sum_matching(counters, "decode_attention_keys_total")
    calls = sum_matching(counters, "decode_attention_calls_total")
    least = _least_seconds(*block_attention_cost(
        keys, config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["block_diffusion"]["block_length"],
        _ITEMSIZE[config["storage_dtype"]]), peaks)
    return (least, calls) if least else None
