"""The one place a decode dispatch's yield is read
(``serving.generate._Yield``), and what it must not change: the four
autoregressive builders stream the tokens, and make the dispatches, that
they did before a decode net could say how its tokens come out. The pinned
tokens and counts are those of the commit before the yield contract
(PR 40), read there with this file's weights and prompts."""
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.serving.generate import _Yield

GEOMETRY = dict(batch_slots=2, max_seq=64, page_size=8, prompt_buckets=(16,))
SIZES = [(5, 9), (12, 6), (3, 1), (16, 4)]
# builder -> the answers to SIZES' prompts
WAS = {
    "gpt": [[58, 58, 58, 58, 99, 99, 99, 99, 99], [99, 99, 99, 99, 99, 99],
            [58], [83, 83, 83, 83]],
    "cohere_moe": [[125] * 9, [41] * 6, [58], [31] * 4],
    "qwen3_next": [[119, 35, 96, 119, 75, 108, 32, 41, 99],
                   [75, 31, 4, 127, 15, 19], [13], [69, 73, 105, 107]],
    "glm4_moe_lite": [[46, 104, 104, 104, 61, 61, 61, 61, 61], [97] * 6,
                      [73], [2, 2, 2, 2]],
}


def _build(name):
    if name == "gpt":
        from paddle_tpu.models.gpt import GptConfig, build_gpt_generative
        return build_gpt_generative(GptConfig.tiny(), **GEOMETRY)
    if name == "cohere_moe":
        from paddle_tpu.models.cohere_moe import (
            CohereMoeConfig, build_cohere_moe_generative)
        return build_cohere_moe_generative(
            CohereMoeConfig.tiny(dtype="float32"), prefill_rows=1,
            **GEOMETRY)
    if name == "qwen3_next":
        from paddle_tpu.models.qwen3_next import (
            Qwen3NextConfig, build_qwen3_next_generative)
        return build_qwen3_next_generative(
            Qwen3NextConfig.tiny(dtype="float32"), prefill_rows=1,
            **GEOMETRY)
    from paddle_tpu.models.glm4_moe_lite import (
        Glm4MoeLiteConfig, build_glm4_moe_lite_generative)
    return build_glm4_moe_lite_generative(
        Glm4MoeLiteConfig.tiny(dtype="float32"), prefill_rows=1, **GEOMETRY)


def _count(name):
    fam = monitor.get_registry().to_dict().get(name, {"values": []})
    return sum(v["value"]["count"] if isinstance(v["value"], dict)
               else v["value"] for v in fam["values"])


@pytest.mark.parametrize("name", sorted(WAS))
def test_an_autoregressive_builder_streams_what_it_did(name):
    """Four requests, one after the other, in chunks of 4: the tokens of
    the commit before, one prefill a request and ``ceil((n - 1) / 4)``
    decode dispatches, every token counted once and every token but a
    request's first timed."""
    with un.guard():
        net = _build(name)
    assert "yield" not in net["decode"] and not net.get("block_length")
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    rng = np.random.default_rng(7)
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        w = (rng.uniform(0.9, 1.1, have.shape) if p.name.endswith("_scale")
             else rng.normal(size=have.shape) * 0.05)
        scope.set_var(p.name, w.astype(have.dtype))
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=4, prefix_cache=False, chunked_prefill=False))
    eng.warm_up()
    names = ("serving_decode_chunk_seconds", "serving_prefill_seconds",
             "serving_decode_tokens_total", "serving_intertoken_seconds",
             "serving_block_forwards_total")
    before = {n: _count(n) for n in names}
    prompts = np.random.default_rng(3)
    got = []
    with eng:
        for P, G in SIZES:
            fut = eng.submit(prompts.integers(1, 128, P), max_new_tokens=G)
            got.append([int(t) for t in fut.result(timeout=300)[0]])
            assert fut.revealed_at() == []
    assert got == WAS[name]
    moved = {n: _count(n) - before[n] for n in names}
    assert moved == {
        "serving_decode_chunk_seconds": sum(-(-(g - 1) // 4)
                                            for _, g in SIZES),
        "serving_prefill_seconds": len(SIZES),
        "serving_decode_tokens_total": sum(g for _, g in SIZES),
        "serving_intertoken_seconds": sum(g - 1 for _, g in SIZES),
        "serving_block_forwards_total": 0}
    assert eng.accounting()["exact"]


def test_a_token_a_forward():
    """Three forwards over two slots, one token each: a request takes its
    budget, the forwards that gave it, and nothing is dropped."""
    out = _Yield([np.arange(6).reshape(3, 2, 1)], 3, 2, 0)
    take, at, forwards, dropped = out.of(1, 10)
    assert (list(take), list(at), forwards, dropped) == ([1, 3, 5], [], 3, 0)
    take, at, forwards, dropped = out.of(0, 2)
    assert (list(take), forwards, dropped) == ([0, 2], 2, 0)
    stop = lambda t: t[:list(t).index(3) + 1] if 3 in t else t
    take, _, forwards, _ = out.of(1, 10, stop)
    assert (list(take), forwards) == ([1, 3], 2)
    assert list(out.moved(0)) == [0, 1, 2] and out.rows == 1


def test_a_block_at_a_time():
    """Five forwards of blocks of 4: slot 0 commits at forwards 1 (three
    tokens: its first block opened with a prompt token) and 4; slot 1
    commits nothing."""
    toks = np.zeros((5, 2, 4), np.int64)
    cnt = np.zeros((5, 2), np.int64)
    at = np.zeros((5, 2, 4), np.int64)
    toks[1, 0, :3], cnt[1, 0], at[1, 0, :3] = [7, 8, 9], 3, [0, 1, 0]
    toks[4, 0], cnt[4, 0], at[4, 0] = [1, 2, 3, 4], 4, [1, 1, 0, 0]
    out = _Yield([toks, cnt, at], 5, 2, 4)
    take, when, forwards, dropped = out.of(0, 20)
    assert list(take) == [7, 8, 9, 1, 2, 3, 4]
    assert list(when) == [0, 1, 0, 1, 1, 0, 0] and (forwards, dropped) == (5,
                                                                           0)
    # a budget that ends inside the second block: its tail is dropped
    take, when, forwards, dropped = out.of(0, 5)
    assert (list(take), list(when), forwards, dropped) == (
        [7, 8, 9, 1, 2], [0, 1, 0, 1, 1], 5, 2)
    # one that ends with the first: the later forwards were not its own
    take, _, forwards, dropped = out.of(0, 2)
    assert (list(take), forwards, dropped) == ([7, 8], 2, 1)
    take, when, forwards, dropped = out.of(1, 9)
    assert (list(take), list(when), forwards, dropped) == ([], [], 5, 0)
    assert list(out.moved(0)) == [0, 0, 4, 4, 4]
    assert list(out.moved(1)) == [0] * 5


@pytest.mark.parametrize("chunk", [2, 3])
def test_a_block_models_stop_is_found_one_dispatch_late(chunk):
    """What a block model yields cannot be counted before it exists, so
    under the lookahead every request is found done at the settle, with the
    next dispatch already launched: its answer is the reference loop's all
    the same, the tokens that dispatch yielded its slot are dropped and
    counted, and its slot is seated one turn later."""
    import jax
    from test_sdar_moe import (SdarMoeConfig, _count, _engine, _ref_cfg,
                               _session, ref)

    cfg = SdarMoeConfig.tiny(dtype="float32")
    net, exe, scope, params = _session(cfg, batch_slots=2, prefill_rows=1)
    rc = _ref_cfg(cfg)
    eng = _engine(net, exe, scope, chunk)
    names = ("serving_lookahead_dropped_tokens_total",
             "serving_decode_tokens_total")
    before = {n: _count(n) for n in names}
    lag0 = (_count("serving_seat_lag_turns"),
            monitor.metric_value("serving_seat_lag_turns",
                                 {"sum": 0.0})["sum"])
    behind0 = _count("serving_launches_total", phase="decode",
                     queued_behind="running")
    sizes = [(3, 9), (16, 5), (12, 7), (7, 14), (21, 8), (9, 12)]
    rng = np.random.default_rng(chunk)
    prompts = [rng.integers(1, 128, n) for n, _ in sizes]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, sizes)]
        outs = [f.result(timeout=300)[0] for f in futs]
    fn = {}
    for p, (_, m), o, f in zip(prompts, sizes, outs, futs):
        total = -(-(len(p) + m) // cfg.block_length) * cfg.block_length
        if total not in fn:
            fn[total] = jax.jit(lambda t: ref.logits(params, t, rc))
        want, at, _ = ref.generate(params, p, m, rc, fn[total])
        assert list(o) == list(want) and f.revealed_at() == list(at)
    assert eng.accounting()["exact"] and not eng._inflight
    assert eng.generation_stats()["decode_recompiles"] == 0
    got = {n: _count(n) - before[n] for n in names}
    assert got["serving_decode_tokens_total"] == sum(m for _, m in sizes)
    # whole blocks, of the dispatches that ran past an answer's end
    late = got["serving_lookahead_dropped_tokens_total"]
    assert late > 0 and late % cfg.block_length == 0
    assert _count("serving_launches_total", phase="decode",
                  queued_behind="running") - behind0 >= 6
    seats = _count("serving_seat_lag_turns") - lag0[0]
    turns = monitor.metric_value("serving_seat_lag_turns")["sum"] - lag0[1]
    assert seats == len(sizes) - 2 and turns == seats
