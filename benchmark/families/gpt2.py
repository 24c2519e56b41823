"""GPT-2 serving through the program's own builder and engine."""
from __future__ import annotations


def build(cfg: dict) -> dict:
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.gpt import GptConfig, build_gpt_generative

    m, s = cfg["model"], cfg["serving"]
    gc = GptConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_layers"], num_heads=m["num_heads"],
        intermediate_size=m["intermediate_size"],
        max_position=m["max_position"],
        initializer_range=m["initializer_range"])
    with un.guard():
        return build_gpt_generative(
            gc, batch_slots=s["slots"], max_seq=s["max_seq"],
            page_size=s["page_size"],
            prompt_buckets=tuple(s["prompt_buckets"]),
            prefill_chunk=s["prefill_chunk"], spec_k=s["spec_k"])


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
