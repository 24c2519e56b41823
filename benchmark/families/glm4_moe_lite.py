"""The ``glm4_moe_lite`` decoder through the program's own builder and
engine; sizes from ``reference.glm4_moe_lite.model_config``."""
from __future__ import annotations


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig, build_glm4_moe_lite_generative)

    m, s = cfg["model"], cfg["serving"]
    mc = Glm4MoeLiteConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"], q_lora_rank=m["q_lora_rank"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=m["rope_theta"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        first_k_dense=m["first_k_dense_replace"],
        num_experts=m["num_experts_total"],
        experts_held=m["n_routed_experts"],
        expert_offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        num_shared_experts=m["n_shared_experts"],
        route_scale=m["routed_scaling_factor"],
        rms_norm_eps=m["rms_norm_eps"],
        initializer_range=m["initializer_range"], dtype=m["storage"])
    with un.guard():
        return build_glm4_moe_lite_generative(
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
