"""Operations the algorithm needs, from a configuration's sizes. 2 per
multiply-add; backward counts twice the forward; recomputation is not
counted; embeddings lookups, layer norms, softmax and element-wise work
are not counted (they are not matmul work)."""
from __future__ import annotations


def bert_pretrain_flops_per_token(cfg: dict) -> float:
    """Forward + backward matmul FLOPs of one token of a BERT pretraining
    step at the configuration's sequence length.

    Per encoder layer and token: Q, K, V, output projections 4 * H*H
    multiply-adds; FFN 2 * H*F; attention scores and context 2 * S*H (every
    token against all S keys, over all heads together). Heads: MLM
    transform H*H and the vocabulary projection H*V for every position (the
    program computes full-sequence logits); pooler and NSP are per row and
    negligible, counted as (H*H + 2*H) / S per token."""
    m, S = cfg["model"], cfg["seq_len"]
    H, F, V, L = (m["hidden_size"], m["intermediate_size"],
                  m["vocab_size"], m["num_layers"])
    layer_macs = 4 * H * H + 2 * H * F + 2 * S * H
    head_macs = H * H + H * V + (H * H + 2 * H) / S
    forward = 2.0 * (L * layer_macs + head_macs)
    return 3.0 * forward
