"""Generative inference end-to-end: GPT decoder, paged KV cache,
prefill/decode serving (ISSUE 11).

Layers under test:
* kernels — decode flash attention vs. the reference softmax oracle
  across positions/pages, paged KV append at page boundaries, shape
  classification;
* ops/models — sampling determinism, prefill->decode logits continuity
  (decoding token t+1 from the cache equals the full-sequence forward),
  donated-KV proof through ``run_chained``'s scan + PT71x cleanliness;
* serving — streaming futures (partial results vs. exactly-one terminal
  outcome), mid-stream deadline expiry, the bucketed-recompile guard, and
  chaos (a killed in-flight batch settles every affected stream typed).
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor, serving
from paddle_tpu.core.types import np_dtype
from paddle_tpu.kernels import (classify_shapes, decode_attention_reference,
                                flash_attention_decode, paged_kv_append,
                                supports_shapes)
from paddle_tpu.models.gpt import (GptConfig, build_gpt_decode,
                                   build_gpt_generative)
from paddle_tpu.resilience import fault_plan_guard
from slow_device import slow_device

RNG = np.random.RandomState(11)


# ---------------------------------------------------------------------------
# kernel layer
# ---------------------------------------------------------------------------

def test_classify_shapes_decode_and_prefill():
    kind, why = classify_shapes(1, 32, block_k=8)
    assert kind == "decode" and "page" in why
    assert classify_shapes(256, 256)[0] == "prefill"
    # unsupported decode tiling refuses with a clear message, never a
    # silent dense fallback
    kind, why = classify_shapes(1, 33, block_k=8)
    assert kind == "unsupported"
    assert "page" in why and "33" in why
    kind, why = classify_shapes(100, 256, block_q=64)
    assert kind == "unsupported" and "divide" in why
    assert supports_shapes(1, 32) and not supports_shapes(1, 33, block_k=8)
    assert supports_shapes(128, 256) \
        and not supports_shapes(100, 256, block_q=64)


def test_route_always_refuses_unsupported_decode_shape():
    from paddle_tpu.ops.generation import _route_decode

    fluid.set_flags({"FLAGS_use_flash_attention": "always"})
    try:
        with pytest.raises(ValueError, match="no kernel tiling"):
            _route_decode(33, 8)
        assert _route_decode(32, 8) in ("pallas", "pallas-interpret")
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})


@pytest.mark.parametrize("lengths", [(1, 5, 8), (8, 9, 16), (24, 31, 32)])
def test_decode_kernel_matches_reference_across_positions(lengths):
    """Bit-level agreement sweep: early, page-boundary and cache-full
    positions, q_len=1 against a block-tiled cache with a length mask."""
    B, H, S, D, P = 3, 2, 32, 64, 8
    BH = B * H
    q = jnp.asarray(RNG.randn(BH, 1, D).astype(np.float32))
    k = jnp.asarray(RNG.randn(BH, S, D).astype(np.float32))
    v = jnp.asarray(RNG.randn(BH, S, D).astype(np.float32))
    lens = np.asarray(lengths, np.int32)
    o = flash_attention_decode(q, k, v, lens, num_heads=H, page_size=P,
                               interpret=True)
    o_ref = decode_attention_reference(
        q, k, v, jnp.asarray(np.repeat(lens, H)), D ** -0.5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=1e-4)


def test_decode_kernel_refuses_bad_shapes():
    q = jnp.zeros((2, 1, 16), np.float32)
    with pytest.raises(ValueError, match="whole pages"):
        flash_attention_decode(q, jnp.zeros((2, 33, 16)),
                               jnp.zeros((2, 33, 16)), np.array([1, 1]),
                               num_heads=1, page_size=8, interpret=True)
    # q_len 2..8 is the legal chunk range since ISSUE 20; past one
    # sublane tile the kernel refuses (the op routes to the primitive)
    with pytest.raises(ValueError, match="q_len<=8"):
        flash_attention_decode(jnp.zeros((2, 9, 16)),
                               jnp.zeros((2, 32, 16)),
                               jnp.zeros((2, 32, 16)), np.array([1, 1]),
                               num_heads=1, page_size=8, interpret=True)


def test_paged_kv_append_at_page_boundaries():
    """Single-row appends at positions straddling a page edge, bulk
    (prompt) appends, and the saturation clamp on the last row."""
    B, H, S, D, P = 3, 2, 32, 4, 8
    cache = jnp.asarray(RNG.randn(B, H, S, D).astype(np.float32))
    new = jnp.asarray(RNG.randn(B, H, 1, D).astype(np.float32))
    # last row of page 0, first row of page 1, last row of the cache
    pos = np.array([7, 8, 31], np.int32)
    out = np.asarray(paged_kv_append(cache, new, jnp.asarray(pos)))
    base = np.asarray(cache)
    for b in range(B):
        np.testing.assert_array_equal(out[b, :, pos[b]],
                                      np.asarray(new)[b, :, 0])
        untouched = [s for s in range(S) if s != pos[b]]
        np.testing.assert_array_equal(out[b, :, untouched],
                                      base[b, :, untouched])
    # bulk write of a whole page at position 0 (the prefill path)
    bulk = jnp.asarray(RNG.randn(B, H, P, D).astype(np.float32))
    out2 = np.asarray(paged_kv_append(cache, bulk,
                                      jnp.zeros((B,), jnp.int32)))
    np.testing.assert_array_equal(out2[:, :, :P], np.asarray(bulk))
    np.testing.assert_array_equal(out2[:, :, P:], base[:, :, P:])
    # out-of-range start clamps onto the final row (retired-slot shape)
    out3 = np.asarray(paged_kv_append(cache, new,
                                      jnp.full((B,), S + 5, jnp.int32)))
    for b in range(B):
        np.testing.assert_array_equal(out3[b, :, S - 1],
                                      np.asarray(new)[b, :, 0])


def _lower(op_type, ins, attrs=None):
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    return get_op_def(op_type).lower(LowerCtx(), ins, attrs or {})


def test_kv_cache_append_op_slot_mask():
    """The op face: a slot-masked append touches only masked sequences'
    rows (the continuous-batching refill invariant)."""
    B, H, S, D = 2, 1, 16, 4
    cache = jnp.asarray(RNG.randn(B, H, S, D).astype(np.float32))
    new = jnp.asarray(RNG.randn(B, H, 4, D).astype(np.float32))
    ins = {"Cache": [cache], "New": [new],
           "Positions": [jnp.zeros((B, 1), jnp.int32)],
           "SlotMask": [jnp.asarray([[1.0], [0.0]], jnp.float32)]}
    out = _lower("kv_cache_append", ins)["Out"][0]
    out = np.asarray(out)
    np.testing.assert_array_equal(out[0, :, :4], np.asarray(new)[0])
    np.testing.assert_array_equal(out[1], np.asarray(cache)[1])


def _masked_append_case(rows, positions, bulk=False):
    """Inputs of one masked append over 4 slots (mask 1, 0, 1, 0) and the
    old form's answer: the unmasked append written out in numpy, then
    ``where(mask, appended, cache)`` over the whole cache."""
    B, H, S, D = 4, 2, 32, 8
    rng = np.random.RandomState(26 + rows)
    cache = rng.randn(B, H, S, D).astype(np.float32)
    new = rng.randn(B, H, rows, D).astype(np.float32)
    mask = np.array([1.0, 0.0, 1.0, 0.0], np.float32)
    appended = cache.copy()
    for b, p in enumerate(positions):
        if bulk:                  # one block; an out-of-range START clamps
            p = min(p, S - rows)
            appended[b, :, p:p + rows] = new[b]
        else:                     # row by row onto min(p + i, S - 1)
            for i in range(rows):
                appended[b, :, min(p + i, S - 1)] = new[b, :, i]
    oracle = np.where(mask.reshape(B, 1, 1, 1) > 0, appended, cache)
    return cache, new, np.asarray(positions, np.int64)[:, None], \
        mask[:, None], oracle


def _decode_append(cache, new, pos, mask):
    """The caches ``fused_decode_attention`` returns (K and V alike)."""
    got = _lower("fused_decode_attention", {
        "Q": [new], "KNew": [new], "VNew": [new], "CacheK": [cache],
        "CacheV": [cache], "Positions": [pos], "SlotMask": [mask]},
        {"scale": 0.0, "page_size": 8})
    return got["CacheKOut"][0], got["CacheVOut"][0]


def _bulk_append(cache, new, pos, mask):
    return (_lower("kv_cache_append", {
        "Cache": [cache], "New": [new], "Positions": [pos],
        "SlotMask": [mask]})["Out"][0],)


# slots 2 and 3 sit where the write clamps at S_max - 1 = 31 (or, for the
# bulk write, where its start clamps): one masked in, one masked out
MASKED_APPENDS = {
    "decode_C1": (1, (3, 5, 40, 31), False),
    "verify_C4": (4, (3, 5, 30, 29), False),
    "chunk_C16_scatter": (16, (3, 5, 20, 25), False),
    "bulk_L16": (16, (0, 0, 20, 30), True),
}


@pytest.mark.parametrize("case", sorted(MASKED_APPENDS))
def test_masked_append_writes_rows_and_matches_where_oracle(case):
    """The slot mask gates the rows that are written: a masked-out slot's
    caches come back bit-identical, a masked-in slot's equal the old
    ``where(m, appended, cache)`` form, clamped positions included."""
    rows, positions, bulk = MASKED_APPENDS[case]
    cache, new, pos, mask, oracle = _masked_append_case(rows, positions,
                                                        bulk)
    outs = (_bulk_append if bulk else _decode_append)(
        *map(jnp.asarray, (cache, new, pos, mask)))
    for out in outs:
        out = np.asarray(out)
        for b in (1, 3):
            assert out[b].tobytes() == cache[b].tobytes()
        np.testing.assert_array_equal(out, oracle)


def test_masked_append_rules_make_no_cache_sized_elementwise_op():
    """What a CPU can guard of the chip's cost: traced with a mask,
    neither op rule holds an operation whose result is as large as a cache
    other than the updates themselves and the calls that contain them. A
    ``select_n`` over the cache (the old ``where(m, new_cache, cache)``)
    rewrites every row of every cache every token, and holds the old
    cache alive so that the update cannot be made in place."""
    import jax

    cache, new, pos, mask, _ = _masked_append_case(1, (3, 5, 40, 31))
    bulk = _masked_append_case(16, (0, 0, 20, 30), bulk=True)[1]
    may_hold_a_cache = {"dynamic_update_slice", "scatter", "while", "scan",
                        "cond", "pjit", "closed_call", "core_call",
                        "reshape", "pallas_call"}

    def cache_sized(jaxpr, found):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if getattr(v.aval, "size", 0) >= cache.size \
                        and eqn.primitive.name not in may_hold_a_cache:
                    found.append((eqn.primitive.name, v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                cache_sized(sub, found)
        return found

    for fn, rows in ((_decode_append, new), (_bulk_append, bulk)):
        jaxpr = jax.make_jaxpr(fn)(cache, rows, pos, mask).jaxpr
        assert cache_sized(jaxpr, []) == []
    # the guard does see the old form
    old = jax.make_jaxpr(lambda c, m: jnp.where(m.reshape(4, 1, 1, 1) > 0,
                                                c + 0.0, c))(cache, mask)
    assert {n for n, _ in cache_sized(old.jaxpr, [])} >= {"select_n"}


# ---------------------------------------------------------------------------
# model layer
# ---------------------------------------------------------------------------

def _plant_state(net, scope):
    for name, (shape, dt) in net["state_vars"].items():
        scope.set_var(name, np.zeros(shape, np_dtype(dt)))


def _build_net(**kw):
    with un.guard():
        return build_gpt_generative(GptConfig.tiny(), **kw)


@pytest.fixture(scope="module")
def gpt_net():
    """Shared tiny GPT (2 slots, 32-token KV in 8-token pages, one 16
    prompt bucket whose prefill carries a row a slot) with all-position
    logits for the continuity tests."""
    return _build_net(batch_slots=2, max_seq=32, page_size=8,
                      prompt_buckets=(16,), fetch_logits=True,
                      prefill_rows=2)


@pytest.fixture()
def gpt_session(gpt_net):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(gpt_net["startup"], scope=scope)
    _plant_state(gpt_net, scope)
    return exe, scope


def _prefill_feed(net, bucket, prompts, slot_mask=None, slots=None):
    """Row ``r`` carries ``prompts[r]`` (None: a row not in use) for slot
    ``slots[r]`` (default: slot ``r``)."""
    R = net["prefill"][bucket]["rows"]
    S = bucket
    ids = np.zeros((R, S), np.int64)
    mask = np.zeros((R, S), np.float32)
    plen = np.ones((R, 1), np.int64)
    smask = np.zeros((R, 1), np.float32)
    slot_ids = np.zeros((R, 1), np.int64)
    slot_ids[:len(prompts), 0] = (range(len(prompts)) if slots is None
                                  else slots)
    for r, p in enumerate(prompts):
        if p is None:
            continue
        ids[r, :len(p)] = p
        mask[r, :len(p)] = 1.0
        plen[r, 0] = len(p)
        smask[r, 0] = 1.0
    if slot_mask is not None:
        smask = slot_mask
    return {"prompt_ids": ids, "prompt_mask": mask, "prompt_len": plen,
            "slot_mask": smask, "slot_ids": slot_ids,
            "prompt_pos": np.tile(np.arange(S, dtype=np.int64), (R, 1))}


def test_prefill_decode_logits_continuity(gpt_net, gpt_session):
    """Decoding token t+1 from the KV cache must equal the full-sequence
    forward at the same position (teacher-forced) — the cache IS the
    prefix computation."""
    exe, scope = gpt_session
    pf = gpt_net["prefill"][16]
    dec = gpt_net["decode"]
    plen = np.array([5, 3])
    prompts = [RNG.randint(1, 128, L).astype(np.int64) for L in plen]
    feed = _prefill_feed(gpt_net, 16, prompts)
    first = exe.run(pf["main"], feed=feed,
                    fetch_list=[pf["first_token"]], scope=scope)[0]
    T = 3
    dec_logits, toks = [], [first.copy()]
    for _ in range(T):
        lg, nt = exe.run(dec["main"], feed={},
                         fetch_list=[dec["logits"], dec["next_token"]],
                         scope=scope)
        dec_logits.append(lg)
        toks.append(nt.copy())
    gen = np.concatenate(toks, axis=1)
    # teacher-forced forward of prompt + generated through the SAME
    # prefill program (slot_mask 0: state untouched)
    full = [np.concatenate([prompts[b], gen[b, :T + 1]]) for b in range(2)]
    feed2 = _prefill_feed(gpt_net, 16, full,
                          slot_mask=np.zeros((2, 1), np.float32))
    all_logits = exe.run(pf["main"], feed=feed2,
                         fetch_list=[pf["logits"]], scope=scope)[0]
    for t in range(T):
        for b in range(2):
            np.testing.assert_allclose(
                dec_logits[t][b], all_logits[b, plen[b] + t],
                atol=2e-4, rtol=1e-3,
                err_msg=f"decode step {t}, sequence {b}")


# -- a prefill's rows name their slots --------------------------------------

@pytest.fixture(scope="module")
def row_nets():
    """One tiny GPT of 4 slots built twice over the same weights: a
    prefill of 2 rows, and one with a row a slot."""
    kw = dict(batch_slots=4, max_seq=32, page_size=8, prompt_buckets=(16,))
    return _build_net(prefill_rows=2, **kw), _build_net(prefill_rows=4, **kw)


def _random_state(net, seed):
    """A state no prefill wrote: every slot mid-stream with its own rows."""
    rng = np.random.RandomState(seed)
    state = {}
    for name, (shape, dt) in net["state_vars"].items():
        if dt == "float32":
            state[name] = rng.randn(*shape).astype(np.float32)
        else:
            state[name] = rng.randint(1, 30, shape).astype(np.int64)
    state["gpt_gen_active"] = np.array([[1.], [0.], [1.], [0.]], np.float32)
    return state


def _run_prefill_on(net, exe, weights, state, feed):
    """The prefill of ``net`` over ``state`` (copied); returns the first
    tokens by row and the state after."""
    scope = fluid.Scope()
    for name, value in {**weights, **state}.items():
        scope.set_var(name, np.array(value))
    pf = net["prefill"][16]
    first = exe.run(pf["main"], feed=feed, fetch_list=[pf["first_token"]],
                    scope=scope)[0]
    return np.asarray(first), {n: np.asarray(scope.find_var(n))
                               for n in state}


@pytest.fixture(scope="module")
def row_weights(row_nets):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(row_nets[0]["startup"], scope=scope)
    names = [v.name for v in row_nets[0]["startup"].global_block.vars.values()
             if v.persistable]
    return exe, {n: np.asarray(scope.find_var(n)) for n in names}


def test_prefill_feeds_name_their_slots(row_nets):
    by_row, slot_wide = (n["prefill"][16] for n in row_nets)
    assert by_row["rows"] == 2 and slot_wide["rows"] == 4
    for pf in (by_row, slot_wide):
        assert pf["feeds"] == ("prompt_ids", "prompt_pos", "prompt_mask",
                               "prompt_len", "slot_mask", "slot_ids")
        block = pf["main"].global_block
        assert block.var("slot_ids").shape == (pf["rows"], 1)
        assert block.var("prompt_ids").shape == (pf["rows"], 16)
        assert pf["first_token"].shape[0] == pf["rows"]
    # one form: the same ops in the same order, whatever the rows
    assert [op.type for op in by_row["main"].global_block.ops] \
        == [op.type for op in slot_wide["main"].global_block.ops]
    # the chunk and verify programs stay a row a slot
    assert row_nets[0]["chunk"]["main"].global_block.var(
        "chunk_ids").shape[0] == 4


@pytest.mark.parametrize("slots", [(3, 1), (0, 2), (2, 3)])
def test_by_row_prefill_touches_only_the_slots_it_names(row_nets,
                                                        row_weights, slots):
    by_row, slot_wide = row_nets
    exe, weights = row_weights
    state = _random_state(by_row, seed=sum(slots))
    prompts = [RNG.randint(1, 128, L).astype(np.int64) for L in (11, 4)]
    first, after = _run_prefill_on(
        by_row, exe, weights, state,
        _prefill_feed(by_row, 16, prompts, slots=slots))
    others = [b for b in range(4) if b not in slots]
    for name, before in state.items():
        np.testing.assert_array_equal(after[name][others], before[others],
                                      err_msg=name)
    # against the program with a row a slot, row b for slot b
    wide = [None] * 4
    for p, b in zip(prompts, slots):
        wide[b] = p
    first_w, after_w = _run_prefill_on(
        slot_wide, exe, weights, state, _prefill_feed(slot_wide, 16, wide))
    np.testing.assert_array_equal(first.ravel(),
                                  first_w.ravel()[list(slots)])
    for name in ("gpt_gen_tokens", "gpt_gen_pos", "gpt_gen_active"):
        np.testing.assert_array_equal(after[name], after_w[name],
                                      err_msg=name)
    np.testing.assert_array_equal(after["gpt_gen_pos"][list(slots), 0],
                                  [11, 4])
    np.testing.assert_array_equal(after["gpt_gen_active"][list(slots), 0],
                                  [1.0, 1.0])
    for name in state:
        if name.startswith("gpt_kv_"):
            np.testing.assert_allclose(after[name], after_w[name],
                                       atol=1e-5, rtol=1e-5, err_msg=name)
            # rows past the bucket stay the slot's old ones
            np.testing.assert_array_equal(after[name][:, :, 16:],
                                          state[name][:, :, 16:])
            assert not np.array_equal(after[name][slots[0], :, :11],
                                      state[name][slots[0], :, :11])


@pytest.mark.parametrize("slot_ids", [(0, 0), (3, 3), (1, 2)])
def test_masked_row_writes_nothing_whatever_its_slot(row_nets, row_weights,
                                                     slot_ids):
    by_row, _ = row_nets
    exe, weights = row_weights
    state = _random_state(by_row, seed=5)
    prompts = [RNG.randint(1, 128, 9).astype(np.int64) for _ in range(2)]
    # both rows masked
    feed = _prefill_feed(by_row, 16, prompts, slots=slot_ids,
                         slot_mask=np.zeros((2, 1), np.float32))
    _, after = _run_prefill_on(by_row, exe, weights, state, feed)
    for name, before in state.items():
        np.testing.assert_array_equal(after[name], before, err_msg=name)
    # one row in use beside a masked row that names a slot too
    feed = _prefill_feed(by_row, 16, prompts, slots=slot_ids,
                         slot_mask=np.array([[0.], [1.]], np.float32))
    _, after = _run_prefill_on(by_row, exe, weights, state, feed)
    others = [b for b in range(4) if b != slot_ids[1]]
    for name, before in state.items():
        np.testing.assert_array_equal(after[name][others], before[others],
                                      err_msg=name)
    assert after["gpt_gen_pos"][slot_ids[1], 0] == 9


@pytest.mark.parametrize("slots,rows", [(1, 1), (2, 1), (4, 1), (8, 2),
                                        (64, 16)])
def test_default_prefill_rows_is_a_quarter_of_the_slots(slots, rows):
    net = _build_net(batch_slots=slots, max_seq=16, page_size=8,
                     prompt_buckets=(8, 16), spec_k=1)
    assert {pf["rows"] for pf in net["prefill"].values()} == {rows}
    assert _build_net(batch_slots=slots, max_seq=16, page_size=8,
                      prompt_buckets=(8,), spec_k=1,
                      prefill_rows=slots)["prefill"][8]["rows"] == slots


@pytest.mark.parametrize("rows", [5, -1])
def test_prefill_rows_outside_the_slots_are_refused(rows):
    with pytest.raises(ValueError, match="prefill rows"):
        _build_net(batch_slots=4, max_seq=16, page_size=8,
                   prompt_buckets=(8,), prefill_rows=rows)


def test_kv_cache_proven_donated_through_chained_scan(gpt_net, gpt_session):
    """The acceptance-critical donation proof: every paged KV cache and
    the generation state ride ``run_chained``'s scan carry DONATED (the
    liveness pass proved in-place update is safe)."""
    exe, scope = gpt_session
    dec = gpt_net["decode"]
    exe.run_chained(dec["main"], feed={},
                    fetch_list=[dec["next_token"]], steps=2, scope=scope)
    key = next(k for k in exe._cache if k[0] == "chained")
    step = exe._cache[key]
    cfg = gpt_net["config"]
    for i in range(cfg.num_layers):
        assert f"gpt_kv_k_{i}" in step.donated_names
        assert f"gpt_kv_v_{i}" in step.donated_names
    assert "gpt_gen_tokens" in step.donated_names
    assert "gpt_gen_pos" in step.donated_names


def test_gpt_programs_pt71x_clean(gpt_net):
    """PT710-PT713 (donation races) must be silent on both phases — the
    fused append-and-attend op is exactly what keeps the caches free of
    read-after-write hazards."""
    from paddle_tpu.analysis import default_pass_manager, Severity

    mgr = default_pass_manager()
    pf = gpt_net["prefill"][16]
    # lint against the full declared fetch surface (this module's net is
    # built with fetch_logits=True, so the logits heads are live too)
    cases = [
        (pf["main"], [pf["first_token"].name, pf["logits"].name]),
        (gpt_net["decode"]["main"],
         [gpt_net["decode"]["next_token"].name,
          gpt_net["decode"]["logits"].name]),
    ]
    allowed_dead = {"reshape2", "transpose2", "unsqueeze2", "layer_norm",
                    "fused_multihead_attention"}
    for prog, fetches in cases:
        r = mgr.run_pipeline(prog, ("schema", "dataflow", "lowerability",
                                    "liveness", "donation_race",
                                    "dead_code"),
                             fetch_names=fetches, verify="none")
        pt71x = [d for d in r.diagnostics if d.code.startswith("PT71")]
        assert not pt71x, [f"{d.code}: {d.message}" for d in pt71x]
        errors = [d for d in r.diagnostics if d.severity == Severity.ERROR]
        assert not errors, [f"{d.code}: {d.message}" for d in errors]
        # dead-code findings must stay within the lint gate's allowlisted
        # schema-echo classes (XShape / layer_norm Mean/Variance / the
        # attention's SoftmaxLse)
        for d in r.diagnostics:
            if d.code in ("PT720", "PT721", "PT722"):
                assert d.op_type in allowed_dead, f"{d.code} {d.op_type}"


def test_sample_token_greedy_and_topk_determinism():
    """greedy == argmax; 'sample' draws only from the top-k set and is
    reproducible for a fixed program.random_seed."""
    from paddle_tpu import layers

    def build(strategy, top_k, seed):
        with un.guard():
            main, startup = fluid.Program(), fluid.Program()
            main.random_seed = seed
            with fluid.program_guard(main, startup):
                lg = layers.data("lg", shape=[4, 16], dtype="float32",
                                 append_batch_size=False)
                tok = layers.sample_token(lg, strategy=strategy,
                                          temperature=0.7, top_k=top_k)
            return main, tok

    logits = RNG.randn(4, 16).astype(np.float32)
    main, tok = build("greedy", 0, 1)
    exe = fluid.Executor(fluid.CPUPlace())
    out = exe.run(main, feed={"lg": logits}, fetch_list=[tok])[0]
    np.testing.assert_array_equal(out.ravel(),
                                  logits.argmax(-1).astype(np.int64))

    draws = []
    for _ in range(2):
        main, tok = build("sample", 3, 7)
        e = fluid.Executor(fluid.CPUPlace())
        seqs = [e.run(main, feed={"lg": logits},
                      fetch_list=[tok])[0].ravel() for _ in range(3)]
        draws.append(np.stack(seqs))
    # same seed + same executor step sequence -> identical draws
    np.testing.assert_array_equal(draws[0], draws[1])
    top3 = np.argsort(logits, -1)[:, -3:]
    for s in draws[0]:
        for b in range(4):
            assert s[b] in top3[b]


# ---------------------------------------------------------------------------
# serving layer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_net():
    return _build_net(batch_slots=2, max_seq=32, page_size=8,
                      prompt_buckets=(8, 16))


def _engine(serving_net, **gen_kw):
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(serving_net["startup"], scope=scope)
    return serving.GenerativeEngine(
        serving_net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=2, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=2, **gen_kw))


def test_generative_engine_end_to_end(serving_net):
    monitor.reset()
    eng = _engine(serving_net)
    # two prefill buckets + one decode + the chunked-prefill program
    # (prefix cache + chunked prefill are on by default since ISSUE 20)
    assert eng.warm_up() == 4
    rng = np.random.RandomState(3)
    with eng:
        futs = [eng.submit(rng.randint(1, 128, 3 + i % 9),
                           max_new_tokens=2 + i % 4, priority=1)
                for i in range(6)]
        stream0 = list(futs[0].stream(timeout=120))
        results = [f.result(timeout=120) for f in futs]
    for i, r in enumerate(results):
        assert r[0].shape == (2 + i % 4,), (i, r)
        assert list(futs[i].tokens()) == list(r[0])
    assert stream0 == list(results[0][0])
    acct = eng.accounting()
    assert acct["exact"] and acct["completed"] == 6 and acct["pending"] == 0
    # the position-bucketed decode compiled exactly once per (phase,
    # bucket) even though sequences sat at different positions
    assert eng.decode_recompiles == 0
    stats = eng.generation_stats()
    assert set(stats["compiled_buckets"]) == {"prefill:8", "prefill:16",
                                              "decode:2", "chunk:8"}
    assert monitor.metric_value("serving_decode_tokens_total", 0.0) \
        == sum(2 + i % 4 for i in range(6))
    it = monitor.metric_value("serving_intertoken_seconds", default=None)
    assert it and it["count"] > 0 and it["p99"] is not None


def _serve_by_rows(rows, prompts, max_new):
    """The streamed tokens of ``prompts`` from an engine of 4 slots whose
    bucket prefill carries ``rows`` sequences, the newcomers of each
    scheduler turn and the prefill dispatches made."""
    net = _build_net(batch_slots=4, max_seq=32, page_size=8,
                     prompt_buckets=(16,), prefill_rows=rows)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=4, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(
            decode_chunk=2, prefix_cache=False, chunked_prefill=False))
    eng.warm_up()
    turns, run_prefill = [], eng._run_prefill

    def counted(newcomers):
        turns.append(len(newcomers))
        run_prefill(newcomers)

    eng._run_prefill = counted
    monitor.reset()
    with eng:
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        out = [list(f.result(timeout=120)[0]) for f in futs]
    assert eng.accounting()["exact"] and eng.decode_recompiles == 0
    return out, turns, monitor.metric_value("serving_prefill_seconds")["count"]


def test_more_newcomers_than_rows_take_several_dispatches_same_tokens():
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 128, 3 + 2 * i).astype(np.int64)
               for i in range(7)]
    want, turns, dispatches = _serve_by_rows(4, prompts, 5)
    assert dispatches == len(turns)          # a row a slot: one a turn
    for rows in (1, 2):
        got, turns, dispatches = _serve_by_rows(rows, prompts, 5)
        assert got == want, rows
        assert sum(turns) == len(prompts)
        assert dispatches == sum(-(-n // rows) for n in turns), (rows, turns)


def _serve_heads_of_64(mode, prompts, budgets):
    """The streamed tokens of ``prompts`` from an engine of 3 slots over a
    GPT of two layers whose caches are worked on rows in lanes (heads of
    64 in pages of 128), every kernel-routed op on the route that
    ``FLAGS_use_flash_attention=mode`` gives it on the CPU, and the
    lowerings ``kernel_route_total`` counted, as {(op, route): n}."""
    cfg = GptConfig(vocab_size=96, hidden_size=128, num_layers=2,
                    num_heads=2, intermediate_size=64, max_position=256)
    fluid.set_flags({"FLAGS_use_flash_attention": mode})
    monitor.reset()
    try:
        with un.guard():
            net = build_gpt_generative(cfg, batch_slots=3, max_seq=256,
                                       page_size=128, prompt_buckets=(128,),
                                       spec_k=0)
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(net["startup"], scope=scope)
        _seed_weights(net, scope)
        eng = serving.GenerativeEngine(
            net, scope=scope, executor=exe,
            config=serving.ServingConfig(max_batch=3, queue_depth=64,
                                         deadline_s=0),
            gen_config=serving.GenerationConfig(
                decode_chunk=4, prefix_cache=False, chunked_prefill=False))
        eng.warm_up()
        with eng:
            futs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            out = [list(f.result(timeout=600)[0]) for f in futs]
        assert eng.accounting()["exact"] and eng.decode_recompiles == 0
    finally:
        fluid.set_flags({"FLAGS_use_flash_attention": "auto"})
    routes = {}
    for labels, ctr in monitor.get_registry().get(
            "kernel_route_total").children():
        key = labels["op"], labels["route"]
        routes[key] = routes.get(key, 0) + int(ctr.value)
    return out, routes


def test_engine_streams_the_same_tokens_with_the_row_written_in_kernel():
    """Four requests on three slots through the chained decode dispatch,
    their contexts crossing from the first block of 128 rows into the
    second while they decode, one of them seated in a slot another left
    (its neighbours' rows masked meanwhile): with the decode kernel
    writing each step's K/V row itself (under the interpreter; two
    layers a lowering of the decode program, no ``kv_append``) the engine
    streams what it streams on
    the primitive route, where the rows are appended on the declared
    shape, token for token."""
    rng = np.random.default_rng(45)
    sizes = [(120, 20), (126, 9), (90, 50), (127, 12)]
    prompts = [rng.integers(1, 96, n) for n, _ in sizes]
    budgets = [m for _, m in sizes]
    want, plain = _serve_heads_of_64("never", prompts, budgets)
    got, routes = _serve_heads_of_64("always", prompts, budgets)
    assert got == want and [len(o) for o in got] == budgets
    in_kernel = "fused_decode_attention.append_in_kernel"
    n = routes[(in_kernel, "pallas-interpret")]
    assert n >= 2 and n % 2 == 0
    assert not any(op == "kv_append" for op, _ in routes)
    assert not any(op in (in_kernel, "kv_append") for op, _ in plain)


def test_recompile_guard_counts_warm_bucket_growth(serving_net):
    """Regression: a NEW executable appearing for an already-compiled
    (phase, bucket)'s program is a counted recompile — KV growth must
    never cause unbounded compiles. Compiles for OTHER programs on a
    shared executor must not count."""
    monitor.reset()
    eng = _engine(serving_net)
    eng.warm_up()
    # an unrelated program compiling on the shared executor: not ours
    with eng._exe._lock:
        eng._exe._cache[("chained", (999999, 0, 0), "other")] = object()
    eng._note_compiles("decode", len(eng._slots), eng._program)
    assert eng.decode_recompiles == 0
    # a NEW executable for the WARM decode program: a counted recompile
    serial = eng._program._serial
    with eng._exe._lock:
        eng._exe._cache[("chained", (serial, 1, 1), "forced")] = object()
    eng._note_compiles("decode", len(eng._slots), eng._program)
    assert eng.decode_recompiles == 1
    assert monitor.metric_value("serving_decode_recompiles_total", 0.0,
                                phase="decode",
                                bucket=str(len(eng._slots))) == 1.0
    # already-counted steps do not re-count
    eng._note_compiles("decode", len(eng._slots), eng._program)
    assert eng.decode_recompiles == 1


def test_streaming_future_unit():
    fut = serving.ServingFuture()
    fut._emit_tokens([1, 2])
    got = []
    it = fut.stream(timeout=5)
    got.append(next(it))
    got.append(next(it))
    fut._emit_tokens([3])
    fut._settle(result=[np.array([1, 2, 3])])
    got.extend(it)
    assert got == [1, 2, 3]
    assert fut.tokens() == [1, 2, 3]
    # emitting after the terminal outcome is an engine bug
    with pytest.raises(RuntimeError, match="after the request's terminal"):
        fut._emit_tokens([4])
    # error terminal: stream raises AFTER yielding the partials
    fut2 = serving.ServingFuture()
    fut2._emit_tokens([7])
    fut2._settle(error=serving.BatchFailed("boom"))
    out = []
    with pytest.raises(serving.BatchFailed):
        for t in fut2.stream(timeout=5):
            out.append(t)
    assert out == [7]


def test_mid_stream_deadline_settles_typed(serving_net):
    """A request whose deadline expires mid-generation reaches exactly one
    typed DeadlineExceeded; already-streamed tokens stay readable as
    partial results and the accounting stays exact."""
    import time

    monitor.reset()
    eng = _engine(serving_net)
    eng.warm_up()
    # pace the decode chunks so the deadline deterministically lands
    # MID-stream: after the first tokens, before the budget of 28
    orig = eng._run_decode_chunk

    def paced():
        time.sleep(0.06)
        orig()

    eng._run_decode_chunk = paced
    with eng:
        fut = eng.submit(np.array([5, 6, 7]), max_new_tokens=28,
                         deadline_s=0.16)
        err = fut.exception(timeout=120)
    assert isinstance(err, serving.DeadlineExceeded)
    partial = fut.tokens()
    assert 1 <= len(partial) < 28   # streamed some, then expired typed
    acct = eng.accounting()
    assert acct["exact"] and acct["deadline_exceeded"] == 1
    assert acct["completed"] == 0 and acct["pending"] == 0


def test_chaos_killed_batch_settles_typed_and_engine_continues(serving_net):
    monitor.reset()
    eng = _engine(serving_net)
    eng.warm_up()
    with eng:
        with fault_plan_guard("batch_dispatch:@2:RuntimeError"):
            f1 = eng.submit(np.array([5, 6, 7]), max_new_tokens=6)
            f2 = eng.submit(np.array([1, 2]), max_new_tokens=6)
            errs = [f.exception(timeout=120) for f in (f1, f2)]
        assert any(isinstance(e, serving.BatchFailed) for e in errs)
        for e in errs:
            assert e is None or isinstance(e, serving.BatchFailed)
        # the engine keeps serving after the kill
        f3 = eng.submit(np.array([9, 9]), max_new_tokens=3)
        assert len(f3.result(timeout=120)[0]) == 3
    acct = eng.accounting()
    assert acct["exact"] and acct["pending"] == 0
    assert acct["failed"] >= 1


def test_warm_up_refused_on_running_engine(serving_net):
    """warm_up resets the generation state, so on a running engine it
    would zero resident streams' caches mid-generation — refused."""
    eng = _engine(serving_net)
    eng.warm_up()
    with eng:
        with pytest.raises(RuntimeError, match="before start"):
            eng.warm_up()
    assert eng.accounting()["exact"]


def test_submit_validation(serving_net):
    eng = _engine(serving_net)
    # over-bucket prompts only refuse once chunked prefill is off
    # (default-on since ISSUE 20 they admit slice by slice instead)
    cold = _engine(serving_net, chunked_prefill=False, prefix_cache=False)
    with pytest.raises(ValueError, match="exceeds the largest prompt"):
        cold._build_gen_request(np.arange(40), 4, 0, None)
    with pytest.raises(ValueError, match="KV capacity"):
        eng._build_gen_request(np.arange(1, 9), 60, 0, None)
    with pytest.raises(ValueError, match="non-empty 1-D"):
        eng._build_gen_request(np.zeros((2, 3), np.int64), 4, 0, None)
    with pytest.raises(serving.EngineStopped):
        eng.submit(np.array([1, 2]))   # never started


def test_stop_without_drain_settles_resident_streams_typed(serving_net):
    eng = _engine(serving_net)
    eng.warm_up()
    eng.start()
    futs = [eng.submit(np.array([1, 2, 3]), max_new_tokens=24)
            for _ in range(3)]
    eng.stop(drain=False)
    outcomes = [f.exception(timeout=60) for f in futs]
    for e in outcomes:
        # either finished before the stop landed or typed EngineStopped
        assert e is None or isinstance(e, serving.EngineStopped)
    assert eng.accounting()["exact"]


# ---------------------------------------------------------------------------
# one dispatch ahead (ISSUE 42): a turn is launched while the chunk before
# it still runs, and that chunk is fetched and settled under its successor
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ahead_net():
    """4 slots, 64-row KV in 8-row pages, one 16 bucket of 2 rows a
    prefill, no chunk or verify program: every turn looks ahead."""
    return _build_net(batch_slots=4, max_seq=64, page_size=8,
                      prompt_buckets=(16,), prefill_rows=2)


def _seed_weights(net, scope, seed=7):
    """Weights that make the tiny model's answers vary (its initial ones
    repeat a token)."""
    rng = np.random.default_rng(seed)
    for p in net["decode"]["main"].global_block.all_parameters():
        have = np.asarray(scope.find_var(p.name))
        w = (rng.uniform(0.9, 1.1, have.shape) if p.name.endswith("_scale")
             else rng.normal(size=have.shape) * 0.05)
        scope.set_var(p.name, w.astype(have.dtype))


def _ahead_engine(net, chunk=4, **gen_kw):
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    _seed_weights(net, scope)
    gen_kw = dict(dict(prefix_cache=False, chunked_prefill=False), **gen_kw)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=4, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=chunk, **gen_kw))
    eng.warm_up()
    return eng


_REFERENCE = {}


def _greedy_reference(net, prompt, n):
    """The plain loop: the request alone in slot 0 of a fresh state, one
    prefill and then one ``Executor.run`` of the decode program a token.
    No engine, no chained dispatch, nothing in flight."""
    if id(net) not in _REFERENCE:
        exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
        exe.run(net["startup"], scope=scope)
        _seed_weights(net, scope)
        _REFERENCE[id(net)] = exe, scope
    exe, scope = _REFERENCE[id(net)]
    _plant_state(net, scope)
    bucket = net["prompt_buckets"][0]
    pf, dec = net["prefill"][bucket], net["decode"]
    first = exe.run(pf["main"], feed=_prefill_feed(net, bucket, [prompt]),
                    fetch_list=[pf["first_token"]], scope=scope)[0]
    toks = [int(np.asarray(first).reshape(-1)[0])]
    while len(toks) < n:
        nt = exe.run(dec["main"], feed={}, fetch_list=[dec["next_token"]],
                     scope=scope)[0]
        toks.append(int(np.asarray(nt)[0, 0]))
    return toks


def _counted(name, **labels):
    fam = monitor.get_registry().to_dict().get(name, {"values": []})
    return [v["value"] for v in fam["values"]
            if all(v["labels"].get(k) == w for k, w in labels.items())]


SIZES = [(5, 9), (12, 6), (3, 1), (16, 4), (7, 17), (9, 2), (4, 30),
         (11, 13), (6, 5), (14, 8)]


@pytest.mark.parametrize("chunk", [1, 4, 7])
def test_lookahead_streams_the_plain_loops_tokens(ahead_net, chunk):
    """Ten requests on four slots, a queue behind them: every answer is the
    plain greedy loop's, token for token; every decode chunk after the
    first was launched behind one still in flight; a request whose budget
    was counted out left its slot at its last chunk's launch, so its slot
    was seated again with no turn lost and no token was dropped."""
    monitor.reset()
    eng = _ahead_engine(ahead_net, chunk=chunk)
    rng = np.random.default_rng(chunk)
    prompts = [rng.integers(1, 128, n) for n, _ in SIZES]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, SIZES)]
        outs = [list(f.result(timeout=300)[0]) for f in futs]
    for p, (_, m), o in zip(prompts, SIZES, outs):
        assert o == _greedy_reference(ahead_net, p, m)
    assert eng.accounting()["exact"] and eng.decode_recompiles == 0
    assert not eng._inflight
    behind = sum(_counted("serving_launches_total", phase="decode",
                          queued_behind="running"))
    alone = sum(_counted("serving_launches_total", phase="decode",
                         queued_behind="idle"))
    assert alone <= 1 and behind >= 5
    # with a queue no slot stands empty for a decode dispatch, but the one
    # whose request of one token ended with its prefill (as in the serial
    # loop: the turn's chunk was launched without it)
    (lag,) = _counted("serving_seat_lag_turns")
    assert lag["count"] == len(SIZES) - 4 and lag["sum"] == 1
    assert _counted("serving_lookahead_dropped_tokens_total") == []


def test_closed_loop_of_callers_loses_one_turn_not_two(ahead_net):
    """Callers == slots, each sending its next request when its last
    completes, on a device that takes 60 ms a chunk: a completion is seen
    at the settle, behind the next chunk's launch, and the thread waits
    for the caller while that chunk runs (``await_newcomers``), so the slot
    stands empty for one decode dispatch, as in the serial loop, not
    two."""
    import threading

    monitor.reset()
    eng = _ahead_engine(ahead_net, chunk=4)
    slow_device(eng._exe, 0.06)
    outs = {}

    def caller(i):
        rng = np.random.default_rng(100 + i)
        for k in range(6):
            p, m = rng.integers(1, 128, 4 + i), 5 + 4 * ((i + k) % 3)
            outs[i, k] = (p, m, list(eng.submit(
                p, max_new_tokens=m).result(timeout=300)[0]))

    with eng:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for p, m, o in outs.values():
        assert o == _greedy_reference(ahead_net, p, m)
    assert eng.accounting()["exact"]
    (lag,) = _counted("serving_seat_lag_turns")
    assert lag["count"] == 20
    # never 0 (a caller cannot resubmit before it has its answer); 1 but
    # where this machine held a caller's thread back for tens of
    # milliseconds
    assert lag["count"] <= lag["sum"] <= lag["count"] + 3
    (waited,) = _counted("serving_loop_seconds", phase="await_newcomers")
    assert waited["count"] >= 4
    # a wait ends when the callers who have their answers are back, not at
    # the chunk's end: nobody is awaited for a slot whose request left it
    # at a launch and is still in flight
    # (but at the run's end, when callers have sent their last)
    assert waited["buckets"]["0.025"] >= waited["count"] / 2


def test_a_stop_token_is_found_one_dispatch_late_and_counted(ahead_net):
    """``eos_id``: only the tokens show the stop, so it is found at the
    settle with the next chunk already launched: the answer ends on the
    stop token as ever, that one chunk's tokens for the slot are dropped
    and counted, and the slot's next request starts clean."""
    prompt = np.random.default_rng(5).integers(1, 128, 6)
    ref = _greedy_reference(ahead_net, prompt, 40)
    # a token that first shows up some chunks in
    at = next(i for i in range(5, 30) if ref[i] not in ref[:i])
    monitor.reset()
    eng = _ahead_engine(ahead_net, chunk=4, eos_id=ref[at])
    other = np.random.default_rng(6).integers(1, 128, 9)
    want = _greedy_reference(ahead_net, other, 7)
    if ref[at] in want:
        want = want[:want.index(ref[at]) + 1]
    with eng:
        got = list(eng.submit(prompt, max_new_tokens=40)
                   .result(timeout=300)[0])
        assert list(eng.submit(other, max_new_tokens=7)
                    .result(timeout=300)[0]) == want
    assert got == ref[:at + 1]
    assert _counted("serving_lookahead_dropped_tokens_total")[0] == 4 * (
        1 + (want[-1] == ref[at] and len(want) < 7))
    assert eng.accounting()["exact"]


@pytest.mark.parametrize("chunk", [3, 4])
def test_an_overrun_on_the_caches_last_row_touches_nothing_else(ahead_net,
                                                                chunk):
    """A request that ends on the cache's last row and is found done one
    dispatch late (its length stop left to the settle, as a stop token's
    is) runs a whole chunk past its end. That chunk writes where a
    mid-chunk overrun writes: its own slot's last row. Its streamed
    tokens, its neighbour's tokens, and every other slot's cache rows are
    those of the serial order, bit for bit."""
    rng = np.random.default_rng(9)
    edge, neighbour = rng.integers(1, 128, 11), rng.integers(1, 128, 5)
    runs = {}
    for how in ("serial", "late"):
        eng = _ahead_engine(ahead_net, chunk=chunk)
        if how == "serial":
            eng._may_look_ahead = lambda newcomers: False
        else:
            eng._counts_ahead = False
        monitor.reset()
        with eng:
            f1 = eng.submit(edge, max_new_tokens=64 - len(edge))
            f2 = eng.submit(neighbour, max_new_tokens=64 - len(neighbour))
            toks = [list(f.result(timeout=300)[0]) for f in (f1, f2)]
        caches = [np.array(eng._scope.find_var(n))
                  for pair in ahead_net["cache_vars"] for n in pair]
        runs[how] = toks, caches, sum(_counted(
            "serving_lookahead_dropped_tokens_total"))
        assert eng.accounting()["exact"]
    (toks_s, caches_s, late_s), (toks_l, caches_l, late_l) = \
        runs["serial"], runs["late"]
    assert toks_l == toks_s
    assert toks_s[0] == _greedy_reference(ahead_net, edge, 64 - len(edge))
    assert late_s == 0 and late_l == 2 * chunk      # one chunk each
    for a, b in zip(caches_s, caches_l):
        np.testing.assert_array_equal(a[1:], b[1:])


def test_a_failure_at_the_deferred_fetch_settles_everyone_once(ahead_net):
    """The device's error surfaces when a chunk's fetch is taken, with the
    next chunk already launched on the state the failed one produced:
    every request the engine holds (in a slot, or out of it with its last
    chunk in flight) fails typed exactly once, the successor's results are
    dropped, the state is planted anew, and the engine serves again."""
    monitor.reset()
    eng = _ahead_engine(ahead_net, chunk=4)
    run_chained, calls = eng._exe.run_chained, []

    def failing(*a, **kw):
        pending = run_chained(*a, **kw)
        calls.append(pending)
        if len(calls) == 2:
            take = pending.take

            def broken():
                take()
                raise RuntimeError("device lost")

            pending.take = broken
        return pending

    eng._exe.run_chained = failing
    rng = np.random.default_rng(13)
    # the second chunk is the last of the 6-token request: it has left its
    # slot when the failure comes; the queued fifth has not been seated
    sizes = [(4, 6), (5, 30), (6, 30), (7, 30), (8, 3)]
    prompts = [rng.integers(1, 128, n) for n, _ in sizes]
    with eng:
        futs = [eng.submit(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, sizes)]
        errs = [f.exception(timeout=300) for f in futs]
        assert all(isinstance(e, serving.BatchFailed) for e in errs[:4])
        assert len(calls) >= 3 and not eng._inflight
        # the fifth was queued or just seated: failed with the rest, or
        # served after the reset
        if errs[4] is None:
            assert list(futs[4].result()[0]) == _greedy_reference(
                ahead_net, prompts[4], 3)
        again = eng.submit(prompts[1], max_new_tokens=9)
        assert list(again.result(timeout=300)[0]) == _greedy_reference(
            ahead_net, prompts[1], 9)
    acct = eng.accounting()
    assert acct["exact"] and acct["pending"] == 0
    assert acct["failed"] == 4 + (errs[4] is not None)
    assert acct["completed"] == 1 + (errs[4] is None)


def test_an_injected_fault_fires_before_the_launch_with_a_chunk_in_flight(
        ahead_net):
    """``batch_dispatch`` at the third decode launch: the chunk in flight
    is sound and is settled first (its tokens are streamed), then the
    streams of the batch that was not launched fail typed; the state is
    untouched and the engine serves on."""
    monitor.reset()
    eng = _ahead_engine(ahead_net, chunk=4)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 128, 5 + i) for i in range(2)]
    with eng:
        # launches: prefill, decode, decode, decode <- the fault
        with fault_plan_guard("batch_dispatch:@4:RuntimeError"):
            futs = [eng.submit(p, max_new_tokens=30) for p in prompts]
            errs = [f.exception(timeout=300) for f in futs]
        assert all(isinstance(e, serving.BatchFailed) for e in errs)
        for f, p in zip(futs, prompts):
            # the prefill's token and two chunks' came out before it
            assert f.tokens() == _greedy_reference(ahead_net, p, 9)
        out = eng.submit(prompts[0], max_new_tokens=6).result(timeout=300)
        assert list(out[0]) == _greedy_reference(ahead_net, prompts[0], 6)
    assert eng.accounting()["exact"] and not eng._inflight


def test_stop_settles_what_is_in_flight(ahead_net):
    """``stop(drain=False)`` with a chunk in flight: its tokens are
    streamed before the typed ``EngineStopped``, a request that ends in it
    completes, and every request has one outcome."""
    eng = _ahead_engine(ahead_net, chunk=4)
    slow_device(eng._exe, 0.05)
    eng.start()
    futs = [eng.submit(np.array([1, 2, 3 + i]), max_new_tokens=40)
            for i in range(4)]
    next(futs[0].stream(timeout=120))        # the first turn is launched
    eng.stop(drain=False)
    for f in futs:
        e = f.exception(timeout=60)
        assert isinstance(e, serving.EngineStopped)
        assert len(f.tokens()) % 4 == 1      # whole chunks after the first
    assert eng.accounting()["exact"] and not eng._inflight


def test_the_crash_guard_covers_both_dispatches_in_flight(ahead_net):
    """A bug on the dispatch thread while it settles one chunk with the
    next already launched: every request of both (one of them out of its
    slot already, its budget counted out at the launch) gets its typed
    outcome from the crash guard, and the queued one too."""
    eng = _ahead_engine(ahead_net, chunk=4)
    slow_device(eng._exe, 0.03)
    settles, observe_walk = [], eng._observe_walk

    def buggy(*a, **kw):
        settles.append(1)
        if len(settles) == 2:
            raise KeyError("a bug in the settle")
        return observe_walk(*a, **kw)

    eng._observe_walk = buggy
    rng = np.random.default_rng(21)
    sizes = [(4, 7), (5, 30), (6, 30), (7, 30), (8, 3)]
    with eng:
        futs = [eng.submit(rng.integers(1, 128, n), max_new_tokens=m)
                for n, m in sizes]
        errs = [f.exception(timeout=120) for f in futs]
    assert all(isinstance(e, serving.EngineStopped) for e in errs)
    assert len(futs[0].tokens()) == 5       # the prefill's and a chunk's
    acct = eng.accounting()
    assert acct["exact"] and acct["rejected_stopped"] == 5
