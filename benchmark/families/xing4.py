"""The ``xing4_0`` decoder through the program's own builder and engine;
sizes from ``reference.xing4.model_config``."""
from __future__ import annotations

from families.glm4_moe_lite import engine  # noqa: F401  the same engine


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.xing4 import Xing4Config, build_xing4_generative

    m, s = cfg["model"], cfg["serving"]
    mc = Xing4Config(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"], q_lora_rank=m["q_lora_rank"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_theta=m["rope_theta"], rope_scaling=m["rope_scaling"],
        hc_mult=m["hc_mult"], hc_sinkhorn_iters=m["hc_sinkhorn_iters"],
        hc_eps=m["hc_eps"],
        hc_res_clamp=(m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"]),
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
        return build_xing4_generative(
            mc, batch_slots=s["slots"], max_seq=s["max_seq"],
            page_size=s["page_size"],
            prompt_buckets=tuple(s["prompt_buckets"]),
            prefill_rows=s.get("prefill_rows"))
