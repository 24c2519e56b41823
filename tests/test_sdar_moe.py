"""The sdar_moe decoder (``models/sdar_moe.py``): a Qwen3-MoE layer that
generates by diffusion over blocks, through prefill, cache, the chained
decode program and ``serving.GenerativeEngine``, against the benchmark's
plain reference (``benchmark/reference/sdar_moe.py``: f32, HIGHEST, a full
pass over the whole sequence for every forward, no cache, nothing of the
program imported), at small sizes on the CPU with seeded weights.

Tolerance, and why. With f32 storage the program's products are the CPU's
f32 products and differ from the reference's in the order of accumulation
only (a cache against a full pass, the op's expert buffers against a
gather): rows read 2e-7 to 9e-7 on logits of order 1, and 2e-5, the
tolerance of the three stored builders' f32 comparisons
(``tests/test_glm4_moe_lite.py``), holds every row of every forward. A
cache that held a block's rows from a forward in which some of its
positions were still masked moves the next block's logits by 1e-2 and more
(``test_a_dropped_commit_moves_the_next_block``).
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.models.sdar_moe import (SdarMoeConfig,
                                        build_sdar_moe_generative)
from paddle_tpu.resilience.deadline import Deadline

_BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
sys.path.insert(0, _BENCHMARK)
try:
    from reference import sdar_moe as ref                   # noqa: E402
finally:
    sys.path.remove(_BENCHMARK)

F32_TOL = 2e-5
M = 0                                   # the mask id


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "expert_offset": cfg.expert_offset,
            "rms_norm_eps": cfg.rms_norm_eps,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "mask_token_id": cfg.mask_token_id}


def _session(cfg, seed=3, **geometry):
    """The builder's programs, and seeded weights drawn as the benchmark
    draws them (norm scales around 1), planted in the scope."""
    geometry = dict(dict(batch_slots=4, max_seq=64, page_size=8,
                         prompt_buckets=(16, 32), prefill_rows=2), **geometry)
    with un.guard():
        net = build_sdar_moe_generative(cfg, **geometry)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))
    rng = np.random.default_rng(seed)
    params = {}
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        w = (rng.uniform(0.9, 1.1, have.shape) if p.name.endswith("_scale")
             else rng.normal(size=have.shape) * cfg.initializer_range)
        scope.set_var(p.name, w.astype(have.dtype))
        params[p.name] = jnp.asarray(scope.find_var(p.name))
    return net, exe, scope, params


def _prefill(net, exe, scope, bucket, prompts, slots):
    R = net["prefill"][bucket]["rows"]
    feed = {"prompt_ids": np.zeros((R, bucket), np.int64),
            "prompt_pos": np.tile(np.arange(bucket, dtype=np.int64), (R, 1)),
            "prompt_mask": np.zeros((R, bucket), np.float32),
            "prompt_len": np.ones((R, 1), np.int64),
            "slot_mask": np.zeros((R, 1), np.float32),
            "slot_ids": np.zeros((R, 1), np.int64)}
    for r, (p, slot) in enumerate(zip(prompts, slots)):
        feed["prompt_ids"][r, :len(p)] = p
        feed["prompt_mask"][r, :len(p)] = 1.0
        feed["prompt_len"][r, 0] = len(p)
        feed["slot_mask"][r, 0] = 1.0
        feed["slot_ids"][r, 0] = slot
    exe.run(net["prefill"][bucket]["main"], feed=feed, scope=scope,
            fetch_list=[net["prefill"][bucket]["expert_stats"]])


def _state(scope, name):
    return np.array(scope.find_var(f"sdar_gen_{name}"))


def _forward(net, exe, scope):
    """One decode forward: the block states it ran on ([slots, L], first
    rows [slots]), its logits [slots, L, V] and what it yielded."""
    dec = net["decode"]
    toks, start = _state(scope, "tokens"), _state(scope, "pos")[:, 0]
    y = dec["yield"]
    lg, out, cnt, at = exe.run(
        dec["main"], feed={}, scope=scope,
        fetch_list=[dec["logits"], y["tokens"], y["count"],
                    y["revealed_at"]])
    B, L = toks.shape
    return (toks, start, np.asarray(lg).reshape(B, L, -1), np.asarray(out),
            np.asarray(cnt)[:, 0], np.asarray(at))


_LOGITS = {}


def _ref_logits(params, rc, ids):
    """The reference's full pass over ``ids`` (one compile a length)."""
    key = (id(params), len(ids))
    if key not in _LOGITS:
        _LOGITS[key] = jax.jit(lambda t: ref.logits(params, t, rc))
    return np.asarray(_LOGITS[key](jnp.asarray(ids)))


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_every_forward_of_every_block_equals_the_reference(steps):
    """Prompts of every remainder ``P % L`` (one shorter than a block),
    through prefill, cache and the decode program: at every forward of
    every block the program's logits at the block's rows are the
    reference's full pass over ``[prompt; earlier blocks; the block's
    state]`` under the block mask, what the forward reveals is what the
    reference's rule reveals of those logits, and a committed block comes
    out from its first answer position on."""
    cfg = SdarMoeConfig.tiny(dtype="float32", denoising_steps=steps)
    net, exe, scope, params = _session(cfg)
    rc, L = _ref_cfg(cfg), cfg.block_length
    rng = np.random.default_rng(steps)
    prompts = [rng.integers(1, 128, n) for n in (3, 8, 13, 18)]
    _prefill(net, exe, scope, 16, prompts[:2], [2, 0])
    _prefill(net, exe, scope, 32, prompts[2:], [1, 3])
    known = {s: list(p) for s, p in zip([2, 0, 1, 3], prompts)}
    worst, commits = 0.0, 0
    for _ in range(3 * (steps + 1)):
        toks, start, lg, out, cnt, at = _forward(net, exe, scope)
        new_toks, step = _state(scope, "tokens"), _state(scope, "step")
        for s, seq in known.items():
            assert start[s] == len(seq) // L * L
            ids = np.array(seq[:start[s]] + list(toks[s]))
            want = _ref_logits(params, rc, ids)[start[s]:]
            worst = max(worst, float(np.abs(lg[s] - want).max()))
            masked = toks[s] == M
            if not masked.any():            # a commit: the block goes out
                n_prompt = len(seq) - start[s]
                assert cnt[s] == L - n_prompt
                assert list(out[s, :cnt[s]]) == list(toks[s, n_prompt:])
                assert (at[s, :cnt[s]] >= 0).all()
                assert (new_toks[s] == M).all() and step[s, 0] == 0
                known[s] = seq[:start[s]] + list(toks[s])
                commits += 1
                continue
            assert cnt[s] == 0
            t = int(step[s, 0]) - 1
            x0, _, chosen = ref.reveal(want, masked,
                                       ref.n_transfer(t, L, steps), M)
            expect = toks[s].copy()
            expect[chosen] = x0[chosen]
            assert list(new_toks[s]) == list(expect)
    assert worst <= F32_TOL, worst
    assert commits >= 8


def test_a_dropped_commit_moves_the_next_block():
    """The cache has to hold the K/V of a block's FINAL tokens. Leave the
    commit forward out (the block's rows then hold what its last denoise
    forward wrote, with positions still masked) and the next block's
    logits leave the reference by far more than the tolerance; with it
    they agree."""
    cfg = SdarMoeConfig.tiny(dtype="float32")
    rc, L = _ref_cfg(cfg), cfg.block_length
    prompt = np.random.default_rng(5).integers(1, 128, 8)
    errs = {}
    for commit in (True, False):
        net, exe, scope, params = _session(cfg)
        _prefill(net, exe, scope, 16, [prompt], [0])
        for _ in range(cfg.denoising_steps):
            _forward(net, exe, scope)
        block = _state(scope, "tokens")[0]
        assert (block != M).all()
        if commit:
            _forward(net, exe, scope)
        else:                           # move on without running the block
            for name, v in (("tokens", np.full_like(block, M)[None]),
                            ("pos", [[len(prompt) + L]]), ("step", [[0]])):
                cur = _state(scope, name)
                cur[:1] = v
                scope.set_var(f"sdar_gen_{name}", cur)
        toks, start, lg, *_ = _forward(net, exe, scope)
        assert start[0] == len(prompt) + L and (toks[0] == M).all()
        ids = np.array(list(prompt) + list(block) + list(toks[0]))
        want = _ref_logits(params, rc, ids)[start[0]:]
        errs[commit] = float(np.abs(lg[0] - want).max())
    assert errs[True] <= F32_TOL, errs
    assert errs[False] > 100 * F32_TOL, errs


def _engine(net, exe, scope, chunk):
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        gen_config=serving.GenerationConfig(
            decode_chunk=chunk, prefix_cache=False, chunked_prefill=False))
    assert eng.warm_up() == len(net["prompt_buckets"]) + 1
    return eng


def _count(name, **lab):
    return sum(v["value"] if not isinstance(v["value"], dict)
               else v["value"]["count"]
               for v in monitor.get_registry().to_dict().get(
                   name, {"values": []})["values"]
               if all(v["labels"].get(k) == w for k, w in lab.items()))


# prompt and answer lengths: a prompt shorter than one block, every
# remainder P % L, an answer of one token, answers that end inside a block
SIZES = [(3, 9), (16, 5), (29, 3), (12, 7), (7, 6), (2, 1), (32, 6),
         (21, 8), (9, 12), (18, 1)]


@pytest.mark.parametrize("chunk,steps", [(1, 2), (3, 2), (4, 2), (5, 4),
                                         (2, 1)])
def test_the_engine_streams_the_references_tokens_in_its_order(chunk, steps):
    """Ten requests on three slots, dispatches of ``chunk`` forwards (which
    end inside blocks unless ``chunk`` is a whole number of them), so that
    requests join and leave slots at every phase of their neighbours'
    blocks: every answer is the reference loop's, token for token, and
    each token was revealed at the forward of its block at which the
    reference revealed it. Exact accounting, no compile after warm-up, and
    the block counters add up."""
    cfg = SdarMoeConfig.tiny(dtype="float32", denoising_steps=steps)
    net, exe, scope, params = _session(cfg, batch_slots=3)
    rc = _ref_cfg(cfg)
    eng = _engine(net, exe, scope, chunk)
    names = ("serving_decode_tokens_total", "serving_blocks_committed_total",
             "serving_block_tail_tokens_total",
             "serving_tokens_revealed_per_forward",
             "moe_dropped_assignments_total")
    before = {n: _count(n) for n in names}
    fw0 = {k: _count("serving_block_forwards_total", kind=k)
           for k in ("commit", "denoise")}
    rng = np.random.default_rng(chunk)
    prompts = [rng.integers(1, 128, n) for n, _ in SIZES]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, SIZES)]
        outs = [f.result(timeout=300)[0] for f in futs]
    fn = {}
    for p, (_, m), o, f in zip(prompts, SIZES, outs, futs):
        total = -(-(len(p) + m) // cfg.block_length) * cfg.block_length
        if total not in fn:
            fn[total] = jax.jit(lambda t: ref.logits(params, t, rc))
        want, at, _ = ref.generate(params, p, m, rc, fn[total])
        assert list(o) == list(want)
        assert f.revealed_at() == list(at)
        assert f.tokens() == list(o)
    assert eng.accounting()["exact"]
    assert eng.generation_stats()["decode_recompiles"] == 0
    got = {n: _count(n) - before[n] for n in names}
    L = cfg.block_length
    blocks = sum(-(-(n + m) // L) - n // L for n, m in SIZES)
    tail = sum(-(n + m) % L for n, m in SIZES)
    assert got["serving_decode_tokens_total"] == sum(m for _, m in SIZES)
    assert got["serving_blocks_committed_total"] == blocks
    assert got["serving_block_tail_tokens_total"] == tail
    assert got["moe_dropped_assignments_total"] == 0
    commit = _count("serving_block_forwards_total", kind="commit") \
        - fw0["commit"]
    denoise = _count("serving_block_forwards_total", kind="denoise") \
        - fw0["denoise"]
    assert commit == blocks
    # a block takes as many denoise forwards as its masked positions need
    need = lambda k: next(t for t in range(steps + 1) if sum(
        ref.n_transfer(i, L, steps) for i in range(t)) >= k)
    assert denoise == sum(
        need(L - (n % L if b == n // L else 0))
        for n, m in SIZES for b in range(n // L, -(-(n + m) // L)))
    assert got["serving_tokens_revealed_per_forward"] == denoise
    assert monitor.get_registry().to_dict()[
        "decode_attention_walk_share"]["values"]


def test_a_deadline_between_two_forwards_of_a_block():
    """A request that expires after the first forward of a block settles
    typed with the whole blocks it had streamed, and the request that
    takes its slot starts from its own prompt: its answer is the
    reference's."""
    cfg = SdarMoeConfig.tiny(dtype="float32")
    net, exe, scope, params = _session(cfg, batch_slots=1,
                                           prefill_rows=1)
    rc = _ref_cfg(cfg)
    eng = _engine(net, exe, scope, 1)       # one forward a dispatch
    orig, calls = eng._run_decode_chunk, []

    def expiring():
        calls.append(1)
        if len(calls) == 5:         # forwards 0-2 made a block; 3, then this
            eng._slots[0].deadline = Deadline(1e-9, what="expired mid-block")
        orig()

    eng._run_decode_chunk = expiring
    rng = np.random.default_rng(9)
    first, second = rng.integers(1, 128, 8), rng.integers(1, 128, 6)
    with eng:
        fut = eng.submit(first, max_new_tokens=20)
        err = fut.exception(timeout=120)
        assert isinstance(err, serving.DeadlineExceeded)
        assert len(fut.tokens()) == cfg.block_length    # one block, whole
        out = eng.submit(second, max_new_tokens=7).result(timeout=120)[0]
    want, _, _ = ref.generate(params, second, 7, rc)
    assert list(out) == list(want)
    acct = eng.accounting()
    assert acct["exact"] and acct["deadline_exceeded"] == 1 \
        and acct["completed"] == 1


def test_the_phases_this_model_has_none_of_are_refused():
    cfg = SdarMoeConfig.tiny(dtype="float32")
    net, exe, scope, _ = _session(cfg)
    with pytest.raises(ValueError, match="block at a time"):
        serving.GenerativeEngine(
            net, scope=scope, executor=exe,
            gen_config=serving.GenerationConfig(speculative=True))
    eng = _engine(net, exe, scope, 3)
    with pytest.raises(ValueError, match="largest prompt bucket"):
        eng.submit(np.arange(1, 40), max_new_tokens=4)
    with pytest.raises(ValueError, match="whole blocks"):
        with un.guard():
            build_sdar_moe_generative(cfg, max_seq=64, page_size=8,
                                      prompt_buckets=(18,))
