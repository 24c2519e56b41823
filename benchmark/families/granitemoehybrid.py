"""The ``granitemoehybrid`` decoder through the program's own builder and
engine; sizes from ``reference.granitemoehybrid.model_config``."""
from __future__ import annotations

from families.qwen3_next import engine  # noqa: F401  (the same operator's start)


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.granite_moe_hybrid import (
        GraniteMoeHybridConfig, build_granite_moe_hybrid_generative)

    m, s = cfg["model"], cfg["serving"]
    mc = GraniteMoeHybridConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        mamba_n_heads=m["mamba_n_heads"], mamba_d_head=m["mamba_d_head"],
        mamba_d_state=m["mamba_d_state"], mamba_d_conv=m["mamba_d_conv"],
        mamba_expand=m["mamba_expand"], mamba_n_groups=m["mamba_n_groups"],
        mamba_chunk_size=m["mamba_chunk_size"],
        intermediate_size=m["intermediate_size"],
        shared_intermediate_size=m["shared_intermediate_size"],
        num_experts=m["num_experts_total"],
        experts_held=m["num_local_experts"],
        expert_offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        attention_multiplier=m["attention_multiplier"],
        logits_scaling=m["logits_scaling"], rms_norm_eps=m["rms_norm_eps"],
        initializer_range=m["initializer_range"],
        embedding_range=m["embedding_range"], dtype=m["storage"])
    with un.guard():
        return build_granite_moe_hybrid_generative(
            mc, batch_slots=s["slots"], max_seq=s["max_seq"],
            page_size=s["page_size"],
            prompt_buckets=tuple(s["prompt_buckets"]),
            prefill_rows=s.get("prefill_rows"))
