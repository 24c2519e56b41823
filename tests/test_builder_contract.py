"""What a builder hands ``serving.GenerativeEngine`` (docs/SERVING.md "What
a builder hands the engine"): the eight builders' dicts satisfy the contract
the engine reads, the engine hands what a dispatch counted to the function
the net lists beside it and knows no more of it, a net outside the contract
is refused, and no model's module leans on another's."""
import ast
import os

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.models.decoder import PREFILL_FEEDS

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "models")
BUILDERS = {
    "gpt": ("GptConfig", "build_gpt_generative"),
    "cohere_moe": ("CohereMoeConfig", "build_cohere_moe_generative"),
    "qwen3_next": ("Qwen3NextConfig", "build_qwen3_next_generative"),
    "glm4_moe_lite": ("Glm4MoeLiteConfig", "build_glm4_moe_lite_generative"),
    "sdar_moe": ("SdarMoeConfig", "build_sdar_moe_generative"),
    "granite_moe_hybrid": ("GraniteMoeHybridConfig",
                           "build_granite_moe_hybrid_generative"),
    "mimo_v2_flash": ("MimoV2FlashConfig", "build_mimo_v2_flash_generative"),
    "xing4": ("Xing4Config", "build_xing4_generative"),
}
KINDS = {"full", "window", "latent", "recurrent"}


def _build(name, **kw):
    import importlib
    module = importlib.import_module(f"paddle_tpu.models.{name}")
    config, build = (getattr(module, n) for n in BUILDERS[name])
    with un.guard():
        return build(config.tiny(), batch_slots=4, max_seq=64, page_size=8,
                     prompt_buckets=(8, 16), **kw)


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_a_builders_dict_satisfies_what_the_engine_reads(name):
    net = _build(name)
    slots = net["batch_slots"]
    assert sorted(net["prefill"]) == list(net["prompt_buckets"]) == [8, 16]
    nets = [("decode", net["decode"])] + sorted(net["prefill"].items())
    for bucket, pf in net["prefill"].items():
        assert 1 <= pf["rows"] <= slots and pf["feeds"] == PREFILL_FEEDS
        block = pf["main"].global_block
        assert [block.var(n).shape for n in PREFILL_FEEDS] == [
            (pf["rows"], bucket)] * 3 + [(pf["rows"], 1)] * 3
    # every layer's state is named, of a kind the engine knows, and in the
    # table the engine plants zeros from; so is the decode gate
    names = [n for layer in net["cache_vars"] for n in layer]
    assert len(set(names)) == len(names) and names
    assert set(net["cache_kinds"]) == set(names)
    assert set(net["cache_kinds"].values()) <= KINDS
    assert set(names) | {net["active_var"]} <= set(net["state_vars"])
    # how the tokens come out: one a forward, or what ``yield`` says
    dec = net["decode"]
    if net.get("block_length"):
        assert set(dec["yield"]) == {"tokens", "count", "revealed_at"}
        assert all("first_token" not in pf for pf in net["prefill"].values())
    else:
        assert "yield" not in dec and dec["next_token"].shape == (slots, 1)
        assert all(pf["first_token"].shape == (pf["rows"], 1)
                   for pf in net["prefill"].values())
    # each listed counter is a variable of its program with what counts it
    # beside it, and the keys the benchmark's tools read name the same
    for _, phase in nets:
        block = phase["main"].global_block
        listed = phase.get("counted", ())
        for var, count in listed:
            assert block.var(var.name) is var and callable(count)
        stats = [phase[k] for k in ("expert_stats", "rule_stats",
                                    "latent_stats", "fold_stats",
                                    "hc_stats")
                 if k in phase]
        assert [v.name for v in stats] == [v.name for v, _ in listed]
        assert not {"expert_layers", "rule_layers", "rule_family"} & set(
            phase)
    assert bool(dec.get("counted")) == (name != "gpt")
    serving.GenerativeEngine(net, executor=fluid.Executor(fluid.CPUPlace()),
                             scope=fluid.Scope())


def test_the_engine_hands_a_dispatchs_counts_to_what_counts_them():
    """A made-up counter on a prefill net and on the decode net: called once
    a settled dispatch with the phase, the fetched array (a chained decode
    stacks its steps in front) and the engine's own sums."""
    net = _build("gpt", fetch_logits=True, spec_k=1, prefill_rows=2)
    calls = []

    def count(phase, stats, sums):
        sums["seen"] += 1
        calls.append((phase, stats.shape, sums["seen"]))

    for phase, key in ((net["decode"], "logits"),
                       (net["prefill"][8], "last_logits")):
        phase["counted"] = [(phase[key], count)]
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    eng.warm_up()
    monitor.reset()
    del calls[:]
    seen = eng._sums["seen"]
    with eng:
        out = eng.submit(np.arange(1, 8), max_new_tokens=6).result(
            timeout=300)[0]
    assert len(out) == 6
    dispatches = {
        "prefill": monitor.metric_value("serving_prefill_seconds")["count"],
        "decode": monitor.metric_value(
            "serving_decode_chunk_seconds")["count"]}
    assert dispatches == {"prefill": 1, "decode": 2}
    vocab = net["config"].vocab_size
    assert [c[:2] for c in calls] == [("prefill", (2, vocab))] + [
        ("decode", (4, 4, vocab))] * 2
    assert [c[2] for c in calls] == [seen + 1, seen + 2, seen + 3]
    # another engine over the same net starts its sums anew
    assert serving.GenerativeEngine(net, scope=scope,
                                    executor=exe)._sums["seen"] == 0


@pytest.mark.parametrize("gone", ["rows", "cache_kinds"])
def test_a_net_outside_the_contract_is_refused(gone):
    net = _build("gpt")
    if gone == "rows":
        del net["prefill"][8]["rows"]
    else:
        del net["cache_kinds"]["gpt_kv_v_1"]
    with pytest.raises(ValueError, match="missing: .*" + (
            r"prefill\[8\]\['rows'\]" if gone == "rows"
            else r"cache_kinds\['gpt_kv_v_1'\]")):
        serving.GenerativeEngine(
            net, executor=fluid.Executor(fluid.CPUPlace()),
            scope=fluid.Scope())


def test_no_models_module_imports_anothers():
    """What builders share is in ``models/decoder.py``; the package's
    ``__init__`` alone imports them all."""
    files = sorted(f[:-3] for f in os.listdir(MODELS)
                   if f.endswith(".py") and f != "__init__.py")
    models = set(files) - {"decoder"}
    assert set(BUILDERS) <= models
    for name in files:
        with open(os.path.join(MODELS, name + ".py")) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                path = (node.module or "").split(".")
                # ``from .x import``, ``from . import x``,
                # ``from paddle_tpu.models.x import``
                reached = set(path[-1:]) if path[-1] else set()
                if node.level == 1 and not node.module or \
                        path[-1] == "models":
                    reached = {a.name for a in node.names}
                if node.level > 1 or (node.level == 0 and
                                      "models" not in path):
                    continue
            elif isinstance(node, ast.Import):
                reached = {a.name.split(".")[-1] for a in node.names
                           if ".models." in a.name}
            else:
                continue
            assert not reached & (models - {name}), (name, reached)
