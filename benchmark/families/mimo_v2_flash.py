"""The ``mimo_v2_flash`` decoder through the program's own builder and
engine; sizes from ``reference.mimo_v2_flash.model_config``."""
from __future__ import annotations


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.mimo_v2_flash import (
        MimoV2FlashConfig, build_mimo_v2_flash_generative)

    m, s = cfg["model"], cfg["serving"]
    mc = MimoV2FlashConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        v_head_dim=m["v_head_dim"],
        swa_num_heads=m["swa_num_attention_heads"],
        swa_num_kv_heads=m["swa_num_key_value_heads"],
        swa_head_dim=m["swa_head_dim"], swa_v_head_dim=m["swa_v_head_dim"],
        rope_theta=m["rope_theta"], swa_rope_theta=m["swa_rope_theta"],
        partial_rotary_factor=m["partial_rotary_factor"],
        sliding_window=m["sliding_window"],
        value_scale=m["attention_value_scale"],
        swa_sink=m["add_swa_attention_sink_bias"],
        full_sink=m["add_full_attention_sink_bias"],
        layer_pattern=m["hybrid_layer_pattern"],
        moe_layer_freq=m["moe_layer_freq"],
        intermediate_size=m["moe_intermediate_size"],
        dense_intermediate_size=m["intermediate_size"],
        num_experts=m["num_experts_total"],
        experts_held=m["n_routed_experts"],
        expert_offset=m["expert_offset"], top_k=m["num_experts_per_tok"],
        rms_norm_eps=m["layernorm_epsilon"],
        initializer_range=m["initializer_range"],
        sink_init_range=m["sink_init_range"], dtype=m["storage"])
    with un.guard():
        return build_mimo_v2_flash_generative(
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
