"""The ``cohere2_moe`` decoder through the program's own builder and
engine; sizes from ``reference.cohere2_moe.model_config``."""
from __future__ import annotations


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.cohere_moe import (CohereMoeConfig,
                                              build_cohere_moe_generative)

    m, s = cfg["model"], cfg["serving"]
    mc = CohereMoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], layer_types=m["layer_types"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        intermediate_size=m["intermediate_size"],
        num_experts=m["num_experts_total"], experts_held=m["num_experts"],
        expert_offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        sliding_window=m["sliding_window"], rope_theta=m["rope_theta"],
        layer_norm_eps=m["layer_norm_eps"], logit_scale=m["logit_scale"],
        initializer_range=m["initializer_range"], dtype=m["storage"])
    with un.guard():
        return build_cohere_moe_generative(
            mc, batch_slots=s["slots"], max_seq=s["max_seq"],
            page_size=s["page_size"],
            prompt_buckets=tuple(s["prompt_buckets"]),
            prefill_rows=s.get("prefill_rows"))


def engine(cfg: dict, net: dict, scope, exe):
    """``GenerativeEngine`` as an operator starts it: every field the
    configuration does not name stays at its flag's default."""
    from paddle_tpu import serving

    s = cfg["serving"]
    return serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=s["slots"],
                                     deadline_s=s["deadline_s"]),
        gen_config=serving.GenerationConfig(**s["generation"]))
