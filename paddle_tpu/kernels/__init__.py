"""Pallas TPU kernels — the reference's `operators/jit/` + `operators/fused/`
role (xbyak runtime codegen and hand-fused kernels) rebuilt as Mosaic
kernels. Everything here must also run under `interpret=True` on CPU (minus
PRNG-dependent paths) so numerics are testable without hardware."""
from .flash_attention import (classify_shapes, flash_attention,
                              flash_attention_bwd, flash_attention_with_lse,
                              flash_block_visits, flash_forward_grid,
                              supports_shapes, window_block_visits)
from .decode_attention import (KERNEL_ROWS, decode_attention_reference,
                               decode_grid_steps, decode_walk_blocks,
                               flash_attention_decode,
                               fold_rows, kv_append, paged_kv_append,
                               paged_kv_append_rows, rows_minor, window_fold)
from .latent_attention import (mla_decode_attention,
                               mla_decode_attention_reference)

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_bwd", "supports_shapes", "classify_shapes",
           "flash_attention_decode", "kv_append", "paged_kv_append", "paged_kv_append_rows", "KERNEL_ROWS", "decode_walk_blocks",
           "decode_grid_steps",
           "decode_attention_reference", "rows_minor", "fold_rows",
           "window_fold", "window_block_visits", "flash_block_visits",
           "flash_forward_grid",
           "mla_decode_attention", "mla_decode_attention_reference"]
