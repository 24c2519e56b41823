"""Prefix-reuse KV cache + chunked prefill + speculative decoding
(ISSUE 20).

Layers under test:
* kernels — q_len>1 chunk attention vs. the reference oracle (per-row
  causal masks), per-row-clamped chunk appends at the cache edge;
* ops — ``spec_accept``'s longest-agreeing-prefix rule;
* prefix cache — chain hashing, LRU bounds, and the copy-in/copy-out
  invariant (eviction can never corrupt a resident);
* serving — copy-on-write divergence at a mid-page boundary, chunked
  prefill interleaved with resident decode, greedy speculative
  bit-exactness, retired-slot clamp hygiene, and the negative controls
  (prefix cache off => zero hits; speculation off => no acceptance
  histogram).
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels import (decode_attention_reference,
                                flash_attention_decode,
                                paged_kv_append_rows)
from paddle_tpu.models.gpt import GptConfig, build_gpt_generative
from paddle_tpu.serving.prefix_cache import PrefixCache

RNG = np.random.RandomState(20)


# ---------------------------------------------------------------------------
# kernel layer: chunk attention + per-row clamped appends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_len", [2, 4, 8])
def test_chunk_kernel_matches_reference(q_len):
    """q_len>1 rides the same 8-row sublane tile with a per-row causal
    mask: query row i sees lengths + i keys."""
    B, H, S, D, P = 3, 2, 32, 64, 8
    BH = B * H
    q = jnp.asarray(RNG.randn(BH, q_len, D).astype(np.float32))
    k = jnp.asarray(RNG.randn(BH, S, D).astype(np.float32))
    v = jnp.asarray(RNG.randn(BH, S, D).astype(np.float32))
    lens = np.asarray([3, 9, 24 - q_len], np.int32)
    o = flash_attention_decode(q, k, v, lens, num_heads=H, page_size=P,
                               interpret=True)
    o_ref = decode_attention_reference(
        q, k, v, jnp.asarray(np.repeat(lens, H)), D ** -0.5)
    assert o.shape == (BH, q_len, D)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("masked", [(), (1,), (0, 1)])
@pytest.mark.parametrize("C", [4, 16])
def test_paged_kv_append_rows_clamps_per_row(C, masked):
    """A chunk whose tail crosses the cache end collapses the overflow
    onto the LAST row (never shifts back over real rows the way a
    whole-block dynamic_update_slice start-clamp would): the verify chunk's
    4 rows and a chunked-prefill slice's 16 through the same one scatter,
    on a cache with no heads dimension; a masked-out sequence writes
    nothing."""
    B, S, D = 2, 24, 4
    cache = jnp.zeros((B, S, D), np.float32)
    new = jnp.asarray(
        np.arange(1, B * C * D + 1, dtype=np.float32).reshape(B, C, D))
    mask = None if not masked else jnp.asarray(
        [[float(b not in masked)] for b in range(B)])
    # batch 0 lies in range; batch 1 starts two rows before the end
    out = np.asarray(paged_kv_append_rows(cache, new, np.array([2, S - 2]),
                                          mask))
    want = np.zeros((B, S, D), np.float32)
    if 0 not in masked:
        want[0, 2:2 + C] = np.asarray(new)[0]
    if 1 not in masked:
        # rows S-2, S-1 get chunk rows 0, 1; the overflow rows all clamp
        # onto row S-1 — LAST writer wins, earlier rows intact
        want[1, S - 2] = np.asarray(new)[1, 0]
        want[1, S - 1] = np.asarray(new)[1, C - 1]
    np.testing.assert_array_equal(out, want)


def test_spec_accept_longest_agreeing_prefix():
    from paddle_tpu import layers

    with un.guard():
        main, start = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, start):
            s = layers.data("s", shape=[3, 4], dtype="int64",
                            append_batch_size=False)
            d = layers.data("d", shape=[3, 3], dtype="int64",
                            append_batch_size=False)
            p = layers.data("p", shape=[3, 1], dtype="int64",
                            append_batch_size=False)
            acc, tok, pos = layers.spec_accept(s, d, p)
    exe = fluid.Executor(fluid.CPUPlace())
    sampled = np.array([[10, 11, 12, 13],     # full agreement
                        [20, 99, 22, 23],     # disagree at draft 0
                        [30, 31, 77, 33]],    # disagree at draft 1
                       np.int64)
    drafts = np.array([[10, 11, 12],
                       [21, 22, 23],
                       [30, 31, 32]], np.int64)
    start_pos = np.array([[5], [6], [7]], np.int64)
    a, t, npos = exe.run(main, feed={"s": sampled, "d": drafts,
                                     "p": start_pos},
                         fetch_list=[acc, tok, pos])
    np.testing.assert_array_equal(a.ravel(), [3, 0, 2])
    # NewTok is the bonus token Sampled[:, m]
    np.testing.assert_array_equal(t.ravel(), [13, 20, 77])
    np.testing.assert_array_equal(npos.ravel(), [5 + 4, 6 + 1, 7 + 3])


# ---------------------------------------------------------------------------
# prefix cache unit
# ---------------------------------------------------------------------------

def _fake_pages(i):
    """Deterministic per-page K/V payloads (1 layer)."""
    return ([np.full((2, 4, 3), float(i) + 0.5, np.float32)],
            [np.full((2, 4, 3), float(i) + 0.25, np.float32)])


def test_prefix_cache_match_insert_and_last_token_rule():
    pc = PrefixCache(page_size=4, capacity_pages=8)
    prompt = np.arange(100, 109, dtype=np.int64)     # 9 tokens -> 2 pages
    rows, entries = pc.match(prompt)
    assert rows == 0 and entries == [] and pc.misses == 1
    assert pc.insert(prompt, _fake_pages) == 2
    rows, entries = pc.match(prompt)
    assert rows == 8 and len(entries) == 2 and pc.hits == 1
    np.testing.assert_array_equal(entries[1]["k"][0], _fake_pages(1)[0][0])
    # exactly one page + the never-cached last token: 8 tokens -> 1 page
    rows, _ = pc.match(prompt[:8])
    assert rows == 4
    # a mid-page-divergent prompt shares page 0 only
    div = prompt.copy()
    div[6] = 777
    rows, entries = pc.match(div)
    assert rows == 4 and len(entries) == 1
    # a first-page mismatch shares nothing (chain hash, not per-page)
    div0 = prompt.copy()
    div0[0] = 777
    assert pc.match(div0)[0] == 0


def test_prefix_cache_lru_eviction_is_bounded():
    pc = PrefixCache(page_size=4, capacity_pages=3)
    prompts = [np.concatenate([[1000 + i], np.arange(8)]).astype(np.int64)
               for i in range(5)]   # distinct page-0 chains
    for p in prompts:
        pc.insert(p, _fake_pages)
    # 5 prompts x 2 pages inserted, capacity 3 -> 7 LRU evictions
    assert len(pc) == 3 and pc.evictions == 7
    # oldest entries evicted; the newest survive
    assert pc.match(prompts[0])[0] == 0
    assert pc.match(prompts[-1])[0] > 0
    st = pc.stats()
    assert st["pages"] == 3 and st["capacity_pages"] == 3
    assert pc.evict_all() == 3 and len(pc) == 0


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

def _build_net(**kw):
    with un.guard():
        return build_gpt_generative(GptConfig.tiny(), **kw)


@pytest.fixture(scope="module")
def net():
    """2 slots, 64-row KV in 8-row pages, one 16 bucket, chunk=8, k=4."""
    return _build_net(batch_slots=2, max_seq=64, page_size=8,
                      prompt_buckets=(16,), prefill_chunk=8, spec_k=4)


def _engine(net, **gen_kw):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=2, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=2, **gen_kw))
    return eng


def _run_one(eng, prompt, max_new=10):
    return list(eng.submit(prompt, max_new_tokens=max_new)
                .result(timeout=120)[0])


def test_prefix_hit_skips_prefill_and_is_bit_exact(net):
    """The tentpole contract: a repeated prefix provably skips bucket
    prefill (hit counters + chunk-suffix path) and the output stream is
    bit-identical to the cold run."""
    shared = RNG.randint(1, 128, 12).astype(np.int64)   # spans 1 page
    p1 = np.concatenate([shared, [5, 6]])
    p2 = np.concatenate([shared, [7, 8, 9]])
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=False)
    base_eng.warm_up()
    with base_eng:
        cold1 = _run_one(base_eng, p1)
        cold2 = _run_one(base_eng, p2)
    eng = _engine(net, prefix_cache=True, chunked_prefill=True)
    eng.warm_up()
    with eng:
        assert _run_one(eng, p1) == cold1          # miss: publishes pages
        assert _run_one(eng, p2) == cold2          # hit: chunked suffix
        st = eng.generation_stats()
    pc = st["prefix_cache"]
    assert pc["hits"] == 1 and pc["misses"] == 1
    assert pc["pages_reused"] >= 1 and pc["pages"] >= 1
    assert st["prefill_chunks"] >= 1               # the suffix slices
    assert st["decode_recompiles"] == 0
    assert eng.accounting()["exact"]


def test_cow_divergence_at_mid_page_boundary(net):
    """Two prompts agreeing past a page boundary but diverging MID-page:
    the second request reuses only whole agreed pages and its divergent
    suffix never leaks into the first stream's pages (copy-in CoW)."""
    shared = RNG.randint(1, 128, 10).astype(np.int64)
    p1 = np.concatenate([shared, [11, 12, 13]])    # 13 tokens
    p2 = p1.copy()
    p2[9] = 99                                     # diverges inside page 1
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=False)
    base_eng.warm_up()
    with base_eng:
        cold1 = _run_one(base_eng, p1)
        cold2 = _run_one(base_eng, p2)
    eng = _engine(net, prefix_cache=True, chunked_prefill=True)
    eng.warm_up()
    with eng:
        assert _run_one(eng, p1) == cold1
        # p2 shares page 0 (rows 0..7) but not page 1 (divergent row 9)
        assert _run_one(eng, p2) == cold2
        # p1 resubmitted AFTER p2's divergent run: its pages are intact
        assert _run_one(eng, p1) == cold1
        st = eng.generation_stats()
    assert st["prefix_cache"]["hits"] >= 2
    assert eng.accounting()["exact"]


def test_eviction_while_resident_decodes_never_corrupts(net):
    """Evict every prefix page while a stream that admitted THROUGH the
    cache is still decoding: the resident owns copies, so its tokens
    stay bit-exact (refuse-or-copy, never corrupt)."""
    shared = RNG.randint(1, 128, 12).astype(np.int64)
    p1 = np.concatenate([shared, [3, 4]])
    p2 = np.concatenate([shared, [5, 6, 7]])
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=False)
    base_eng.warm_up()
    with base_eng:
        cold = _run_one(base_eng, p2, max_new=24)
    eng = _engine(net, prefix_cache=True, chunked_prefill=True)
    eng.warm_up()
    with eng:
        _run_one(eng, p1)                         # publish the pages
        f = eng.submit(p2, max_new_tokens=24)     # admits via prefix hit
        it = f.stream(timeout=120)
        first = next(it)     # first token proves the hit-admission ran
        # evict mid-stream, repeatedly, while the resident decodes
        for _ in range(20):
            eng._prefix_cache.evict_all()
        assert [first] + list(it) == cold
    assert eng.generation_stats()["prefix_cache"]["hits"] >= 1
    assert eng.accounting()["exact"]


def test_chunked_prefill_interleaves_with_resident_decode(net):
    """A prompt past the largest bucket (16) admits via chunk slices
    while a resident keeps decoding; both streams bit-match their
    solo cold runs."""
    p_short = RNG.randint(1, 128, 6).astype(np.int64)
    p_long = RNG.randint(1, 128, 30).astype(np.int64)   # > bucket 16
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=True)
    base_eng.warm_up()
    with base_eng:
        cold_short = _run_one(base_eng, p_short, max_new=20)
        cold_long = _run_one(base_eng, p_long, max_new=8)
    eng = _engine(net, prefix_cache=False, chunked_prefill=True)
    eng.warm_up()
    with eng:
        f_short = eng.submit(p_short, max_new_tokens=20)
        f_long = eng.submit(p_long, max_new_tokens=8)
        assert list(f_short.result(timeout=120)[0]) == cold_short
        assert list(f_long.result(timeout=120)[0]) == cold_long
        st = eng.generation_stats()
    assert st["prefill_chunks"] >= 4    # ceil(30 / 8) slices
    assert st["decode_recompiles"] == 0
    assert eng.accounting()["exact"]


def test_over_bucket_prompt_refused_without_chunked_prefill(net):
    eng = _engine(net, prefix_cache=False, chunked_prefill=False)
    with pytest.raises(ValueError, match="chunked_prefill"):
        eng._build_gen_request(RNG.randint(1, 128, 20).astype(np.int64),
                               4, 0, None)


def test_speculative_greedy_is_bit_exact_and_accepts(net):
    """The tentpole bit-exactness contract: greedy speculative output ==
    greedy non-speculative output, with a non-trivial acceptance rate
    (the n-gram draft exploits the tiny model's repetitive stream)."""
    monitor.reset()
    prompts = [RNG.randint(1, 128, 5 + i).astype(np.int64)
               for i in range(4)]
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                       speculative=False)
    base_eng.warm_up()
    with base_eng:
        cold = [_run_one(base_eng, p, max_new=16) for p in prompts]
    eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                  speculative=True)
    # prefill:16 + decode + verify (no chunk program: both chunked
    # prefill and the prefix cache are off)
    assert eng.warm_up() == 3
    with eng:
        hot = [_run_one(eng, p, max_new=16) for p in prompts]
        st = eng.generation_stats()
    assert hot == cold
    assert st["speculative"]["enabled"] and st["speculative"]["chunks"] > 0
    assert st["speculative"]["accepted_tokens"] > 0
    assert st["decode_recompiles"] == 0
    h = monitor.metric_value("serving_spec_accepted_len", default=None)
    assert h and h["count"] == st["speculative"]["chunks"] \
        and h["max"] >= 1
    assert eng.accounting()["exact"]


def test_spec_capacity_guard_falls_back_to_plain_decode(net):
    """Near KV capacity the verify chunk would overflow the cache: the
    engine must fall back to plain decode chunks, still bit-exact."""
    L = 16
    p = RNG.randint(1, 128, L).astype(np.int64)
    max_new = 64 - L            # fills the cache to the brim
    base_eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                       speculative=False)
    base_eng.warm_up()
    with base_eng:
        cold = _run_one(base_eng, p, max_new=max_new)
    eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                  speculative=True)
    eng.warm_up()
    with eng:
        assert _run_one(eng, p, max_new=max_new) == cold
    assert eng.accounting()["exact"]


def test_retired_slot_stays_frozen_and_readmits(net):
    """OOB-clamp x retired slots: after a stream retires, later decode
    and verify dispatches leave its cache rows bit-untouched (the decode
    gate is cleared host-side), and the slot re-admits cleanly."""
    eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                  speculative=True)
    eng.warm_up()
    p1 = RNG.randint(1, 128, 4).astype(np.int64)
    p2 = RNG.randint(1, 128, 7).astype(np.int64)
    with eng:
        _run_one(eng, p1, max_new=2)     # retires quickly
        # retire clears the decode gate from the dispatcher thread;
        # result() may resolve a beat earlier, so poll briefly
        import time as _time
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            active = np.array(eng._scope.find_var("gpt_gen_active"))
            if float(active.sum()) == 0.0:
                break
            _time.sleep(0.01)
        assert float(active.sum()) == 0.0, "retire must clear the gate"
        # snapshot the slot cache rows AFTER retire
        k0_name = "gpt_kv_k_0"
        snap = np.array(eng._scope.find_var(k0_name))
        _run_one(eng, p2, max_new=12)    # long stream, spec dispatches
        # p2 reuses a slot; the OTHER slot's rows are bit-identical
        after = np.array(eng._scope.find_var(k0_name))
        other = [s for s in range(2)
                 if not np.array_equal(snap[s], after[s])]
        assert len(other) <= 1, \
            "a retired slot's cache rows changed without an admission"
    assert eng.accounting()["exact"]


def test_negative_controls_prefix_off_spec_off(net):
    """prefix cache off => stats None and zero hit counters; speculation
    off => no acceptance histogram ever observed."""
    monitor.reset()
    eng = _engine(net, prefix_cache=False, chunked_prefill=False,
                  speculative=False)
    eng.warm_up()
    shared = RNG.randint(1, 128, 12).astype(np.int64)
    with eng:
        for tail in ([1, 2], [3, 4, 5]):
            _run_one(eng, np.concatenate([shared, tail]))
        st = eng.generation_stats()
    assert st["prefix_cache"] is None
    assert not st["speculative"]["enabled"]
    assert st["speculative"]["chunks"] == 0
    assert monitor.metric_value("serving_prefix_hits_total", 0.0) == 0.0
    assert monitor.metric_value("serving_spec_accepted_len",
                                default=None) is None
    assert eng.accounting()["exact"]


# ---------------------------------------------------------------------------
# one dispatch ahead (ISSUE 42): a turn that needs fetched values drains first
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("needs", ["chunked", "speculative", "prefix_hit"])
def test_a_turn_that_needs_fetched_values_drains_first(net, needs):
    """A chunk slice, a verify round (it drafts from the tokens before it)
    and a prefix-cache copy-in (it writes pages through the host) cannot be
    launched behind a dispatch whose results are still on the device: when
    a turn holds one of them the loop settles what is in flight first, and
    the tokens are the serial order's. A resident that needs none of it
    beside them still decodes a chunk ahead on the turns in between."""
    p_res = RNG.randint(1, 128, 6).astype(np.int64)
    shared = RNG.randint(1, 128, 12).astype(np.int64)
    p_new = {"chunked": RNG.randint(1, 128, 30).astype(np.int64),
             "speculative": RNG.randint(1, 128, 9).astype(np.int64),
             "prefix_hit": np.concatenate([shared, [7, 8, 9]])}[needs]
    kw = {"chunked": dict(prefix_cache=False, chunked_prefill=True),
          "speculative": dict(prefix_cache=False, chunked_prefill=False,
                              speculative=True),
          "prefix_hit": dict(prefix_cache=True, chunked_prefill=True)}[needs]
    base_eng = _engine(net, prefix_cache=False,
                       chunked_prefill=(needs == "chunked"))
    base_eng.warm_up()
    with base_eng:
        cold_res = _run_one(base_eng, p_res, max_new=30)
        cold_new = _run_one(base_eng, p_new, max_new=8)
    eng = _engine(net, **kw)
    eng.warm_up()
    monitor.reset()
    seen = []
    site = {"chunked": "_run_chunk_slices", "speculative": "_run_spec_chunk",
            "prefix_hit": "_copy_in_prefix"}[needs]
    orig = getattr(eng, site)

    def spied(*a, **k):
        seen.append(len(eng._inflight))
        return orig(*a, **k)

    setattr(eng, site, spied)
    with eng:
        if needs == "prefix_hit":
            _run_one(eng, np.concatenate([shared, [5, 6]]))   # publishes
        f_res = eng.submit(p_res, max_new_tokens=30)
        next(f_res.stream(timeout=120))       # resident and decoding
        f_new = eng.submit(p_new, max_new_tokens=8)
        assert list(f_new.result(timeout=120)[0]) == cold_new
        assert list(f_res.result(timeout=120)[0]) == cold_res
    assert seen and not any(seen), seen
    assert eng.accounting()["exact"] and not eng._inflight
    behind = monitor.metric_value("serving_launches_total", 0.0,
                                  phase="decode", queued_behind="running")
    # a verify round every turn: nothing is ever launched behind another
    assert (behind == 0) if needs == "speculative" else (behind > 0)
