"""The ``sdar_moe`` decoder through the program's own builder and engine;
sizes from ``reference.sdar_moe.model_config``."""
from __future__ import annotations


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.sdar_moe import (SdarMoeConfig,
                                            build_sdar_moe_generative)

    m, s = cfg["model"], cfg["serving"]
    mc = SdarMoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"],
        intermediate_size=m["moe_intermediate_size"],
        num_experts=m["num_experts_total"], experts_held=m["num_experts"],
        expert_offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        rms_norm_eps=m["rms_norm_eps"], block_length=m["block_length"],
        denoising_steps=m["denoising_steps"],
        mask_token_id=m["mask_token_id"],
        initializer_range=m["initializer_range"], dtype=m["storage"])
    with un.guard():
        return build_sdar_moe_generative(
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
