"""BERT pretraining through the program's own builder."""
from __future__ import annotations

import numpy as np


def build(cfg: dict) -> dict:
    """The program's pretraining model for the configuration's sizes:
    ``build_bert_pretrain`` exactly as a user calls it."""
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    m = cfg["model"]
    bc = BertConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        intermediate_size=m["intermediate_size"],
        max_position=m["max_position"],
        type_vocab_size=m["type_vocab_size"],
        hidden_dropout=m["hidden_dropout"],
        attention_dropout=m["attention_dropout"],
        initializer_range=m["initializer_range"])
    with un.guard():
        return build_bert_pretrain(bc, seq_len=cfg["seq_len"],
                                   lr=cfg["learning_rate"],
                                   amp=cfg["amp"])


def make_batches(cfg: dict, job: dict, seed: int) -> list:
    """``job['pool']`` seeded pretraining feeds of ``job['batch']`` rows
    (host numpy): random tokens, full-length rows, every 7th position
    masked for the MLM head. Own copy of the program's
    ``synthetic_pretrain_batch`` recipe, so that a PR to the program cannot
    change what is fed; every batch and every row differs."""
    m, S, B = cfg["model"], cfg["seq_len"], job["batch"]
    rng = np.random.default_rng([int(seed), 0xBE27])
    out = []
    for _ in range(job["pool"]):
        label = np.full((B, S), -100, np.int64)
        label[:, ::7] = rng.integers(0, m["vocab_size"], label[:, ::7].shape)
        out.append({
            "src_ids": rng.integers(0, m["vocab_size"], (B, S),
                                    dtype=np.int64),
            "pos_ids": np.tile(np.arange(S, dtype=np.int64), (B, 1)),
            "sent_ids": np.zeros((B, S), np.int64),
            "input_mask": np.ones((B, S), np.float32),
            "mask_label": label,
            "next_sent_label": rng.integers(0, 2, (B, 1), dtype=np.int64),
        })
    return out


def tokens_per_step(cfg: dict, job: dict) -> int:
    return job["batch"] * cfg["seq_len"]
