"""Every Pallas kernel compiles for the v5e — without a chip.

The installed libtpu compiles for a TPU topology it is only told about:
``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` returns four ``TPU v5 lite`` devices in a
sandbox that has none, and lowering a jitted function for
``ShapeDtypeStruct``s placed on one of them runs Mosaic and the TPU compiler
for real. So "the kernels compile" stays true in every PR at no chip time.
Compiling says nothing about results, run-time memory or speed: those are
``chip_smoke.py``'s and the benchmark's to find on the chip.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import flash_attention, flash_attention_decode


@pytest.fixture(scope="module")
def v5e():
    """ShapeDtypeStruct factory for one device of a deviceless v5e 2x2."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no libtpu in this installation
        pytest.skip(f"no deviceless TPU topology here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _compiles_with_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_flash_attention_fwd_bwd_compiles_at_the_bert_base_shape(v5e):
    """bs 32 x 12 heads, S 512, D 64, bf16, key bias, in-kernel dropout:
    the forward and both backward kernels."""
    def loss(q, k, v, bias):
        o = flash_attention(q, k, v, bias=bias, dropout_rate=0.1, seed=3,
                            num_heads=12)
        return o.astype(jnp.float32).sum()

    qkv = v5e((384, 512, 64), jnp.bfloat16)
    text = _compiles_with_mosaic(jax.grad(loss, argnums=(0, 1, 2)),
                                 qkv, qkv, qkv, v5e((32, 512), jnp.float32))
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_decode_compiles_at_the_gpt2_base_shape(v5e, dtype):
    """8 slots x 12 heads against a 1024-row cache in pages of 128, q_len 8
    (the speculative-verify chunk; q_len 1 rides the same tile): heads of
    64, which the kernel reads rows-minor, in the configuration's f32 and
    in bf16 (sublane tiles of 16)."""
    _compiles_with_mosaic(
        lambda q, k, v, n: flash_attention_decode(q, k, v, n, num_heads=12,
                                                  page_size=128),
        v5e((96, 8, 64), dtype), v5e((96, 1024, 64), dtype),
        v5e((96, 1024, 64), dtype), v5e((8,), jnp.int32))


def test_kernels_keep_their_names_in_the_compiled_hlo(v5e):
    """What a profiler's ``XLA Ops`` line prints is the instruction's own
    name: each Mosaic call carries the name the program chose, and the
    forward flash kernel ONE name whether reached plainly (a forward op)
    or under ``jvp`` (a grad op's recomputation) — docs/OBSERVABILITY.md
    "Device names"."""
    import re

    def loss(q, k, v):
        return flash_attention(q, k, v, num_heads=12).astype(
            jnp.float32).sum()

    def step(q, k, v, qd, kc, vc, n):
        with jax.named_scope("fused_attention"):
            fwd = flash_attention(q, k, v, num_heads=12)
        with jax.named_scope("fused_attention_grad"):
            # other operands than the forward's: two calls of the one
            # traced forward (PR 50) on the same operands are one call
            grads = jax.grad(loss, argnums=(0, 1, 2))(k, q, v)
        return (fwd, grads,
                flash_attention_decode(qd, kc, vc, n, num_heads=12,
                                       page_size=128))

    qkv = v5e((24, 512, 64), jnp.float32)
    text = _compiles_with_mosaic(
        step, qkv, qkv, qkv, v5e((96, 1, 64), jnp.float32),
        v5e((96, 1024, 64), jnp.float32), v5e((96, 1024, 64), jnp.float32),
        v5e((8,), jnp.int32))
    names = sorted(re.sub(r"[.\d]+$", "", m) for m in re.findall(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))
    assert names == ["decode_attention", "flash_attention_bwd_dkv",
                     "flash_attention_bwd_dq", "flash_attention_fwd",
                     "flash_attention_fwd"]


def _whole_cache_work(text, cache_shape, where="loops", dtype="f32"):
    """Names of the instructions that PRODUCE a whole cache of ``dtype``,
    as it is declared, in the rows-minor view (the last two dimensions
    swapped) or re-laid with its heads next to its lanes: a ``copy`` or a
    select of that shape. In-place updates (``dynamic-update-slice``,
    alone or as a fusion's root; a ``scatter``) and tuple plumbing are
    not. ``where``: outside the entry computation (so inside a ``while``
    body), or ``"entry"``, around the loop."""
    B, H, S, D = cache_shape
    shape = dtype + "\\[%d,(?:%d,%d,%d|%d,%d,%d|%d,%d,%d)\\]" % (
        B, H, S, D, H, D, S, S, H, D)
    made = re.compile(r"^\s+(?:ROOT )?%?((?:copy|[\w\-]*select[\w\-]*)"
                      r"[.\w]*) = \(?" + shape)
    found, entry = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY")
        m = made.match(line)
        if m and entry == (where == "entry") \
                and "dynamic-update-slice" not in m.group(1):
            found.append(m.group(1))
    return found


def _decode_chunk(B, H, S, D, form, layers=1, steps=4, *, rows=1,
                  sink=False, **attrs):
    """The decode chunk as ``run_chained`` runs it: a scan whose carry is
    the donated caches of ``layers`` layers, each step a masked append and
    the decode kernel a layer. ``form`` says how the step is made:
    ``"op"`` is ``fused_decode_attention``'s own rule, which appends in the
    view the kernel reads (``rows`` rows a step, a sink a query head and
    the op's ``attrs`` where given; the caches and rows it is called with
    say how many query heads and how wide the values); ``"logical_rows"``
    appends on the declared shape
    whatever the kernel reads (the rule before PR 32);
    ``"where_over_the_cache"`` selects between an appended cache and the
    old one (the rule before PR 26)."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.kernels import paged_kv_append_rows
    from paddle_tpu.lowering import LowerCtx

    def append(cache, new, pos, mask):
        if form == "logical_rows":
            return paged_kv_append_rows(cache, new, pos, mask)
        appended = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
            c, n, (jnp.int32(0), p, jnp.int32(0))))(cache, new, pos)
        m = (mask.reshape(B) > 0).reshape(B, 1, 1, 1)
        return jnp.where(m, appended, cache)

    def layer(ck, cv, q, kn, vn, pos, mask, sinks):
        if form == "op":
            ins = {"Q": [q], "KNew": [kn], "VNew": [vn], "CacheK": [ck],
                   "CacheV": [cv], "Positions": [pos.reshape(B, 1)],
                   "SlotMask": [mask]}
            if sink:
                ins["Sink"] = [sinks]
            got = get_op_def("fused_decode_attention").lower(
                LowerCtx(platform="tpu"), ins,
                {"scale": 0.0, "page_size": 128, **attrs})
            o = got["Out"][0]
            # the next layer's query depends on this one's attention
            return (got["CacheKOut"][0], got["CacheVOut"][0],
                    o if o.shape == q.shape else q + 0 * o[..., :1])
        ck, cv = append(ck, kn, pos, mask), append(cv, vn, pos, mask)
        o = flash_attention_decode(
            q.reshape(B * H, 1, D), ck.reshape(B * H, S, D),
            cv.reshape(B * H, S, D), jnp.minimum(pos + 1, S), num_heads=H,
            page_size=128)
        return ck, cv, o.reshape(B, H, 1, D)

    def chunk(caches, q, kn, vn, pos, mask, sinks):
        def body(carry, _):
            caches, pos, q = carry
            out = []
            for ck, cv in zip(caches[::2], caches[1::2]):
                ck, cv, q = layer(ck, cv, q, kn, vn, pos, mask, sinks)
                out += [ck, cv]
            step = rows * mask.reshape(B).astype(pos.dtype)
            return (out, pos + step, q), None
        return jax.lax.scan(body, (caches, pos, q), None, length=steps)[0]

    return jax.jit(chunk, donate_argnums=(0,)), layers


def _compiled_chunk(v5e, B, H, S, D, form, layers=1, dtype=jnp.float32,
                    Hq=None, Dv=None, **op):
    chunk, n = _decode_chunk(B, H, S, D, form, layers, **op)
    rows, Hq, Dv = op.get("rows", 1), Hq or H, Dv or D
    return chunk.lower(
        [v5e((B, H, S, D), dtype), v5e((B, H, S, Dv), dtype)] * n,
        v5e((B, Hq, rows, D), dtype), v5e((B, H, rows, D), dtype),
        v5e((B, H, rows, Dv), dtype), v5e((B,), jnp.int32),
        v5e((B, 1), jnp.float32), v5e((Hq,), jnp.float32)).compile()


@pytest.mark.parametrize("form", ["op", "logical_rows",
                                  "where_over_the_cache"])
def test_masked_append_keeps_the_scan_carry_in_place(v5e, form):
    """With the mask on the rows and the append in the kernel's view,
    nothing in the loop body produces a whole cache. The two old forms are
    kept here so that the guard is seen to see: ``where(m, appended,
    cache)`` costs a select and copies of every cache every token, and
    rows appended on the declared shape beside a kernel that reads heads
    of 64 rows-minor put the layout conversion inside the loop."""
    text = _compiled_chunk(v5e, 8, 12, 1024, 64, form).as_text()
    assert "tpu_custom_call" in text
    found = _whole_cache_work(text, (8, 12, 1024, 64))
    assert (found == []) if form == "op" else len(found) >= 2


def test_gpt2_chained_decode_appends_inside_the_decode_kernel(v5e):
    """The serving cell's geometry (64 slots x 12 heads x 1,024 rows x 64,
    f32; two layers of the twelve, a chunk of 4 steps): the runtime stores
    such a cache rows in lanes, the step appends and attends in that view,
    so no ``copy`` of a cache stands at the program's entry, at its exit or
    in the loop (two conversions a cache and 9.86 GB of scratch at twelve
    layers before PR 32). The step's row is written by the decode kernel
    itself (PR 45): no ``kv_append`` call (one a layer and cache since PR
    34, a loop over the sequences a cache before), one ``decode_attention``
    call a layer whose K and V cache operands are aliased to its second
    and third results (they stay in HBM, and the kernel copies the block
    it merged into them itself); the scan is the one ``while``, nothing
    else produces a whole cache, and the compiler holds no scratch for
    one."""
    compiled = _compiled_chunk(v5e, 64, 12, 1024, 64, "op", layers=2)
    text = compiled.as_text()
    shape = (64, 12, 1024, 64)
    assert _whole_cache_work(text, shape) == []
    assert _whole_cache_work(text, shape, "entry") == []
    assert len(re.findall(r" while\(", text)) == 1
    assert not re.search(r"%kv_append[.\d]* = ", text)
    cache = r"f32\[64,12,(?:1024,64|64,1024)\]\S*"
    calls = re.findall(
        r"%decode_attention[.\d]* = \((\S+), " + cache + ", " + cache
        + r"\) custom-call\(([^)]*)\)[^\n]*output_to_operand_aliasing="
        r"\{\{1\}: \((\d+), \{\}\), \{2\}: \((\d+), \{\}\)\}", text)
    assert len(calls) == 2
    for out, operands, k_at, v_at in calls:
        # the grid's bound (the walk's live steps: PR 53), its table,
        # lengths, keep, q, K cache, V cache, K columns, V columns
        assert out.startswith("f32[768,8,64]")
        assert len(operands.split(", ")) == 9
        assert (int(k_at), int(v_at)) == (5, 6)
    made = re.findall(r"%([a-zA-Z_\-]+)[.\w]* = " + cache + r" ([a-z\-]+)\(",
                      text)
    assert {op for _, op in made} <= {"parameter", "get-tuple-element",
                                      "bitcast"}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_gpt2_verify_chunk_keeps_one_append_call_a_row_and_cache(v5e):
    """A chunk of rows on the same caches (the speculative-verify chunk, 4
    rows a slot; one layer) may cross a block's edge: each row of each
    cache is one ``kv_append`` call whose cache operand is aliased to its
    result, the decode kernel after them appends nothing, and nothing
    else produces a whole cache."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    B, H, S, D, C = 64, 12, 1024, 64, 4

    def step(q, kn, vn, ck, cv, pos, mask):
        got = get_op_def("fused_decode_attention").lower(
            LowerCtx(platform="tpu"),
            {"Q": [q], "KNew": [kn], "VNew": [vn], "CacheK": [ck],
             "CacheV": [cv], "Positions": [pos], "SlotMask": [mask]},
            {"scale": 0.0, "page_size": 128})
        return got["Out"][0], got["CacheKOut"][0], got["CacheVOut"][0]

    rows, cache = v5e((B, H, C, D), jnp.float32), v5e((B, H, S, D),
                                                      jnp.float32)
    text = jax.jit(step, donate_argnums=(3, 4)).lower(
        rows, rows, rows, cache, cache, v5e((B, 1), jnp.int32),
        v5e((B, 1), jnp.float32)).compile().as_text()
    assert _whole_cache_work(text, (B, H, S, D), "entry") == []
    shape = r"f32\[64,12,(?:1024,64|64,1024)\]\S* "
    appends = re.findall(
        r"%kv_append[.\d]* = " + shape + r"custom-call\(([^)]*)\)"
        r"[^\n]*output_to_operand_aliasing=\{\{\}: \((\d+), \{\}\)\}", text)
    assert len(appends) == 2 * C
    for operands, aliased in appends:
        assert int(aliased) == len(operands.split(", ")) - 1 == 3
    attends = re.findall(r"%decode_attention[.\d]* = (\S+) custom-call\(",
                         text)
    assert len(attends) == 1 and attends[0].startswith("f32[768,8,64]")


# name: query heads, key/value heads, cache rows, head dim, window
OTHER_DECODERS = {
    "command-a-plus-full": (128, 8, 1024, 128, 0),
    "command-a-plus-ring": (128, 8, 1024, 128, 4096),
    "qwen3-next": (16, 2, 4096, 256, 0),
}


@pytest.mark.parametrize("decoder", sorted(OTHER_DECODERS))
def test_heads_of_whole_lane_tiles_take_one_scatter_a_cache(v5e, decoder):
    """The other decoders' decode steps (64 slots, bf16, heads of 128 and
    256: ``rows_minor`` is false) hold no ``kv_append`` call: their rows
    are whole lane tiles, and ONE scatter a cache writes every slot's (PR
    48), where a loop over the slots wrote them one
    ``dynamic-update-slice`` a slot."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    Hq, H, S, D, window = OTHER_DECODERS[decoder]
    B, bf = 64, jnp.bfloat16

    def step(q, kn, vn, ck, cv, pos, mask):
        got = get_op_def("fused_decode_attention").lower(
            LowerCtx(platform="tpu"),
            {"Q": [q], "KNew": [kn], "VNew": [vn], "CacheK": [ck],
             "CacheV": [cv], "Positions": [pos], "SlotMask": [mask]},
            {"scale": 0.0, "page_size": 128, "window": window})
        return got["Out"][0], got["CacheKOut"][0], got["CacheVOut"][0]

    row, cache = v5e((B, H, 1, D), bf), v5e((B, H, S, D), bf)
    text = jax.jit(step, donate_argnums=(3, 4)).lower(
        v5e((B, Hq, 1, D), bf), row, row, cache, cache,
        v5e((B, 1), jnp.int32), v5e((B, 1), jnp.float32)).compile().as_text()
    assert re.search(r"%decode_attention[.\d]* = ", text)
    assert not re.search(r"%kv_append[.\d]* = ", text)
    assert len(re.findall(r" scatter\(", text)) == 2
    assert not re.findall(r"dynamic-update-slice\(| while\(", text)
    assert not _copies_of(text, B, H, S, D)


# name: slots, key/value heads, cache rows, key width, then the op's own:
# query heads, value width, rows a step, sink, attrs
SCATTER_CHUNKS = {
    "mimo-v2-flash-full": (128, 4, 4096, 256, dict(Hq=64, Dv=128)),
    "mimo-v2-flash-ring": (128, 8, 128, 256,
                           dict(Hq=64, Dv=128, sink=True, window=128)),
    "sdar-block-of-4": (64, 4, 2048, 128,
                        dict(Hq=32, rows=4, whole_chunk=True)),
}


@pytest.mark.parametrize("case", sorted(SCATTER_CHUNKS))
def test_row_scatter_keeps_the_scan_carry_in_place(v5e, case):
    """The decode chunk at the cache shapes whose append was a loop over
    the slots until PR 48 (bf16; MiMo-V2-Flash's full layer and its ring,
    keys in 256 lanes beside values in 128; SDAR's block of 4 rows): the
    donated caches go through the scan with one ``scatter`` a cache a
    step, the scan is the one ``while``, nothing in it or around it
    produces a whole cache, and the compiler holds under a hundredth of a
    cache of scratch. A scatter with the heads inside its window holds a
    whole cache and copies it into the loop and out (``tools/
    probe_kv_append.py --deviceless``): this is where that fails."""
    B, H, S, D, op = SCATTER_CHUNKS[case]
    compiled = _compiled_chunk(v5e, B, H, S, D, "op", dtype=jnp.bfloat16,
                               **op)
    text = compiled.as_text()
    assert len(re.findall(r"%decode_attention[.\d]* = ", text)) == 1
    assert len(re.findall(r" scatter\(", text)) == 2
    assert len(re.findall(r" while\(", text)) == 1
    for width in (D, op.get("Dv") or D):
        shape = (B, H, S, width)
        assert _whole_cache_work(text, shape, dtype="bf16") == []
        assert _whole_cache_work(text, shape, "entry", dtype="bf16") == []
    values = B * H * S * (op.get("Dv") or D) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < values / 100


# -- the sparse-expert decoder's kernels at its published widths (PR 27) ------

@pytest.mark.parametrize("held,H,F,rows,tm", [
    (16, 4096, 4096, 768, 16), (16, 4096, 4096, 16640, 16),
    (16, 4096, 4096, 69632, 256),
    # SDAR's 128 experts of 2048 x 768: a forward of 64 blocks, and a
    # prefill of 1,024 rows (64 rows an expert)
    (128, 2048, 768, 4096, 16), (128, 2048, 768, 16384, 64)])
def test_grouped_expert_matmul_compiles_at_the_published_widths(
        v5e, held, H, F, rows, tm):
    """16 stacked experts of 4096 x 4096 in bf16 at the row buffer of a
    decode step of 64 tokens, of a prefill of 16 x 128, and of 64 x 128 in
    tiles of 256; 128 experts of 2048 x 768, a width no power of two
    divides into lanes, whose blocks hold ``K`` whole (3 MiB each, resident
    across an expert's tiles): the gated gate-and-up call, then down, under
    one name."""
    from paddle_tpu.kernels.moe import grouped_matmul

    def ffn(x, wg, wu, wd, tile_expert, n_valid):
        h = grouped_matmul(x, wg, tile_expert, n_valid, tm=tm, rhs2=wu,
                           out_dtype=jnp.bfloat16)
        return grouped_matmul(h, wd, tile_expert, n_valid, tm=tm)

    wgu = v5e((held, H, F), jnp.bfloat16)
    text = _compiles_with_mosaic(
        ffn, v5e((rows, H), jnp.bfloat16), wgu, wgu,
        v5e((held, F, H), jnp.bfloat16),
        v5e((rows // tm,), jnp.int32), v5e((), jnp.int32))
    assert len(re.findall(r"%moe_expert_matmul[.\d]* = ", text)) == 2


def test_router_and_grouped_query_attention_compile_in_bf16(v5e):
    """The f32 router over 128 experts; one decode step of 64 slots x 8
    key/value heads with the 16 query heads of a group in the sublane rows
    of one call, against bf16 caches of 1024 rows; the flash forward with
    128 query heads over 8 key/value heads and a window."""
    from paddle_tpu.kernels.moe import router_scores

    text = _compiles_with_mosaic(
        router_scores, v5e((8192, 4096), jnp.float32),
        v5e((4096, 128), jnp.float32))
    assert re.search(r"%moe_router[.\d]* = ", text)
    cache = v5e((512, 1024, 128), jnp.bfloat16)
    _compiles_with_mosaic(
        lambda q, k, v, n: flash_attention_decode(
            q, k, v, n, num_heads=8, page_size=128, group=16),
        v5e((512, 16, 128), jnp.bfloat16), cache, cache, v5e((64,), jnp.int32))
    kv = v5e((16 * 8, 256, 128), jnp.bfloat16)
    _compiles_with_mosaic(
        lambda q, k, v, b: flash_attention(q, k, v, bias=b, causal=True,
                                           num_heads=128, window=192),
        v5e((16 * 128, 256, 128), jnp.bfloat16), kv, kv,
        v5e((16, 256), jnp.float32))


# -- the hybrid decoder's kernels at its published widths (PR 31) -------------

def test_gated_delta_rule_kernels_compile_at_the_published_widths(v5e):
    """The chunked scan over two prompts of 3,072 rows (16 key heads, 32
    value heads of 128 x 128, f32) and the decode step of 64 slots, whose
    134 MB state is rewritten in place: donated, nothing of its shape is
    copied."""
    from paddle_tpu.kernels.gdn import gdn_chunk_scan, gdn_decode_step

    f32 = jnp.float32
    qk, v = v5e((2, 16, 3072, 128), f32), v5e((2, 32, 3072, 128), f32)
    gb = v5e((2, 32, 3072), f32)
    text = _compiles_with_mosaic(gdn_chunk_scan, qk, qk, v, gb, gb)
    assert re.search(r"%gdn_chunk_scan[.\d]* = ", text)
    vec, head = v5e((64, 32, 128), f32), v5e((64, 32), f32)
    text = jax.jit(gdn_decode_step, donate_argnums=(0,)).lower(
        v5e((64, 32, 128, 128), f32), vec, vec, vec, head,
        head).compile().as_text()
    assert re.search(r"%gdn_decode_step[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = f32\[64,32,128,128\]", text)


def test_selective_scan_kernels_compile_at_the_published_widths(v5e):
    """Mamba-2's chunked scan over eight prompts of 768 rows (128 heads of 64
    over a state of 128, f32, two heads a grid step, chunks of 256) from a
    start state, and the decode step of 64 slots, whose 268 MB state is
    rewritten in place: donated, nothing of its shape is copied. And the 36
    held experts of 4096 x 768 at a decode step's row buffer."""
    from paddle_tpu.kernels.moe import grouped_matmul
    from paddle_tpu.kernels.ssd import ssd_chunk_scan, ssd_decode_step

    f32 = jnp.float32
    rows = lambda *w: v5e((8, 768) + w, f32)
    text = _compiles_with_mosaic(ssd_chunk_scan, rows(128, 64), rows(128),
                                 rows(128), rows(128),
                                 v5e((8, 128, 64, 128), f32))
    assert re.search(r"%ssd_chunk_scan[.\d]* = ", text)
    text = jax.jit(ssd_decode_step, donate_argnums=(0,)).lower(
        v5e((64, 128, 64, 128), f32), v5e((64, 128, 64), f32),
        v5e((64, 128), f32), v5e((64, 128), f32),
        v5e((64, 128), f32)).compile().as_text()
    assert re.search(r"%ssd_decode_step[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = f32\[64,128,64,128\]", text)
    bf = jnp.bfloat16
    wgu, wd = v5e((36, 4096, 768), bf), v5e((36, 768, 4096), bf)

    def ffn(x, wg, wu, wd, tile_expert, n_valid):
        h = grouped_matmul(x, wg, tile_expert, n_valid, tm=16, rhs2=wu,
                           out_dtype=bf)
        return grouped_matmul(h, wd, tile_expert, n_valid, tm=16)

    _compiles_with_mosaic(ffn, v5e((1216, 4096), bf), wgu, wgu, wd,
                          v5e((76,), jnp.int32), v5e((), jnp.int32))


def test_softmax_router_and_head_256_attention_compile(v5e):
    """The softmax router over 512 experts; 256 stacked experts of 2048 x
    512 at a decode step's row buffer; one decode step of 64 slots x 2
    key/value heads of 256 dims with the 8 query heads of a group in one
    call, against bf16 caches of 4,096 rows; the causal flash forward at
    3,072 rows, 16 query heads over 2."""
    from paddle_tpu.kernels.moe import grouped_matmul, router_scores

    text = _compiles_with_mosaic(
        lambda x, w: router_scores(x, w, score_fn="softmax"),
        v5e((3072, 2048), jnp.float32), v5e((2048, 512), jnp.float32))
    assert re.search(r"%moe_router[.\d]* = ", text)
    bf = jnp.bfloat16
    wgu, wd = v5e((256, 2048, 512), bf), v5e((256, 512, 2048), bf)

    def ffn(x, wg, wu, wd, tile_expert, n_valid):
        h = grouped_matmul(x, wg, tile_expert, n_valid, tm=16, rhs2=wu,
                           out_dtype=bf)
        return grouped_matmul(h, wd, tile_expert, n_valid, tm=16)

    _compiles_with_mosaic(ffn, v5e((4736, 2048), bf), wgu, wgu, wd,
                          v5e((296,), jnp.int32), v5e((), jnp.int32))
    cache = v5e((128, 4096, 256), bf)
    _compiles_with_mosaic(
        lambda q, k, v, n: flash_attention_decode(
            q, k, v, n, num_heads=2, page_size=128, group=8),
        v5e((128, 8, 256), bf), cache, cache, v5e((64,), jnp.int32))
    kv = v5e((2, 3072, 256), bf)
    _compiles_with_mosaic(
        lambda q, k, v: flash_attention(q, k, v, causal=True, num_heads=16),
        v5e((16, 3072, 256), bf), kv, kv)


# -- the latent-attention decoder's kernels at its published widths (PR 33) ---

def test_mla_decode_attention_compiles_at_the_published_widths(v5e):
    """One decode step of 128 slots: 20 heads (32 sublanes) on a latent
    cache of 4,096 rows of 512 + 64 in 640 lanes, bf16, in blocks of 1,024
    rows; nothing of the cache's shape is copied on the way in."""
    from paddle_tpu.kernels.latent_attention import (latent_block_rows,
                                                     mla_decode_attention)

    bf = jnp.bfloat16
    assert latent_block_rows(4096, 640, bf, 128) == 1024
    text = _compiles_with_mosaic(
        lambda q, cache, n: mla_decode_attention(
            q, cache, n, latent_dim=512, scale=1 / 16, page_size=128),
        v5e((128, 20, 640), bf), v5e((128, 4096, 640), bf),
        v5e((128,), jnp.int32))
    assert re.search(r"%mla_decode_attention[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = bf16\[128,4096,640\]", text)


def _latent_op(v5e, mode, B, S, donate=True):
    """The op's rule lowered for a TPU on ``B`` sequences of ``S`` rows and
    a cache of 128 slots x 4,096 rows at the published widths."""
    import paddle_tpu  # noqa: F401  (registers the ops)
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    bf = jnp.bfloat16
    rule = get_op_def("latent_attention").lower

    def fn(q, c, kr, w, cache, pos, smask, slots):
        ins = {"Q": [q], "C": [c], "KRope": [kr], "KVBW": [w],
               "Cache": [cache], "Positions": [pos], "SlotMask": [smask]}
        if mode == "prefill":
            ins["Slots"] = [slots]
        out = rule(LowerCtx(platform="tpu"), ins,
                   {"mode": mode, "nope_dim": 192, "page_size": 128})
        return out["Out"][0], out["CacheOut"][0]

    col = lambda dt: v5e((B, 1), dt)
    return jax.jit(fn, donate_argnums=(4,)).lower(
        v5e((B, 20, S, 256), bf), v5e((B, S, 512), bf), v5e((B, S, 64), bf),
        v5e((512, 20 * 448), bf), v5e((128, 1, 4096, 640), bf),
        col(jnp.int32), col(jnp.float32), col(jnp.int32)).compile()


def test_latent_prefill_compiles_at_the_published_widths(v5e):
    """The op's prefill form for one prompt of 1,024 rows into a cache of
    128 slots: keys and values expanded from the latent rows, the causal
    flash forward over 20 heads of 256, and the bucket written into the
    named slot in place (donated: no copy of the cache)."""
    text = _latent_op(v5e, "prefill", 1, 1024).as_text()
    assert re.search(r"%flash_attention_fwd[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = bf16\[128,1,4096,640\]", text)


def test_latent_decode_appends_by_one_scatter_in_place(v5e):
    """The op's decode form for 128 slots: the step's rows go into the
    donated cache by one scatter, with no copy of the cache and no
    cache-sized scratch, and the kernel reads the result."""
    compiled = _latent_op(v5e, "decode", 128, 1)
    text = compiled.as_text()
    assert re.search(r"%mla_decode_attention[.\d]* = ", text)
    assert not re.search(r"%kv_append[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = bf16\[128,1,4096,640\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


# -- the block-diffusion decoder's two masks at its published widths (PR 41) --

def test_block_masks_compile_at_the_published_widths(v5e):
    """One decode forward of 64 slots x 4 key/value heads of 128 dims with
    a block of 4 rows of each of a group's 8 query heads (32 sublane rows)
    in one call, every row seeing the whole block, against bf16 caches of
    2,048 rows; the flash forward causal by blocks of 4 at 1,024 rows, 32
    query heads over 4, with a padding bias."""
    bf = jnp.bfloat16
    cache = v5e((64 * 4, 2048, 128), bf)
    text = _compiles_with_mosaic(
        lambda q, k, v, n: flash_attention_decode(
            q, k, v, n, num_heads=4, page_size=128, group=8,
            whole_chunk=True),
        v5e((64 * 4, 32, 128), bf), cache, cache, v5e((64,), jnp.int32))
    assert re.search(r"%decode_attention[.\d]* = ", text)
    kv = v5e((4, 1024, 128), bf)
    text = _compiles_with_mosaic(
        lambda q, k, v, b: flash_attention(q, k, v, bias=b, causal=True,
                                           num_heads=32, causal_block=4),
        v5e((32, 1024, 128), bf), kv, kv, v5e((1, 1024), jnp.float32))
    assert re.search(r"%flash_attention_fwd[.\d]* = ", text)


# -- MiMo-V2-Flash's attention at its published widths (PR 47) ----------------

# name: query heads, key/value heads, cache rows, window
MIMO_LAYERS = {"full": (64, 4, 4096, 0), "window": (64, 8, 128, 128)}


def _copies_of(text, B, H, S, D):
    """The ``copy`` instructions that produce a whole bf16 cache of this
    shape, in either order of its last two dimensions."""
    shapes = "|".join(f"{B},{H},{a},{b}" for a, b in ((S, D), (D, S)))
    return re.findall(r"(copy[.\w]*) = bf16\[(?:" + shapes + r")\]", text)


@pytest.mark.parametrize("kind", sorted(MIMO_LAYERS))
def test_sink_and_two_widths_decode_step_compiles_in_place(v5e, kind):
    """128 slots, bf16, a key cache of 256 lanes (a key's 192 numbers)
    beside a value cache of 128, a sink a query head on the window layer's
    ring: one ``decode_attention`` call, the row append in place (no copy
    of a whole cache), no ``kv_append`` call."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    Hq, H, S, window = MIMO_LAYERS[kind]
    B, bf = 128, jnp.bfloat16

    def step(q, kn, vn, ck, cv, pos, mask, sink):
        ins = {"Q": [q], "KNew": [kn], "VNew": [vn], "CacheK": [ck],
               "CacheV": [cv], "Positions": [pos], "SlotMask": [mask]}
        if window:
            ins["Sink"] = [sink]
        got = get_op_def("fused_decode_attention").lower(
            LowerCtx(platform="tpu"), ins,
            {"scale": 192 ** -0.5, "page_size": 128, "window": window})
        return got["Out"][0], got["CacheKOut"][0], got["CacheVOut"][0]

    text = jax.jit(step, donate_argnums=(3, 4)).lower(
        v5e((B, Hq, 1, 256), bf), v5e((B, H, 1, 256), bf),
        v5e((B, H, 1, 128), bf), v5e((B, H, S, 256), bf),
        v5e((B, H, S, 128), bf), v5e((B, 1), jnp.int32),
        v5e((B, 1), jnp.float32), v5e((Hq,), jnp.float32)
    ).compile().as_text()
    assert len(re.findall(r"%decode_attention[.\d]* = ", text)) == 1
    assert not re.search(r"%kv_append[.\d]* = ", text)
    assert not _copies_of(text, B, H, S, 256)
    assert not _copies_of(text, B, H, S, 128)


@pytest.mark.parametrize("kind,bucket", [("full", 3584), ("window", 3584),
                                         ("window", 256)])
def test_flash_forward_with_keys_of_192_and_a_sink_compiles(v5e, kind,
                                                            bucket):
    """One prompt of the longest bucket: 64 query heads of 192 over 4 or 8
    key/value heads, values of 128, and on a window layer the sink and the
    k axis cut to the two blocks a q-block's window touches."""
    Hq, H, _, window = MIMO_LAYERS[kind]
    bf = jnp.bfloat16

    def attend(q, k, v, sink):
        return flash_attention(q, k, v, causal=True, num_heads=Hq,
                               scale=192 ** -0.5, window=window,
                               sink=sink if window else None)

    text = _compiles_with_mosaic(
        attend, v5e((Hq, bucket, 192), bf), v5e((H, bucket, 192), bf),
        v5e((H, bucket, 128), bf), v5e((Hq,), jnp.float32))
    assert re.search(r"%flash_attention_fwd[.\d]* = ", text)


def test_window_fold_compiles_and_updates_the_rings_in_place(v5e):
    """A prompt of 3,584 rows into 128 slots' rings of 128 rows, keys in
    256 lanes and values in 128: plain XLA under the scope
    ``window_fold``, the rings its own results, no copy of them."""
    from paddle_tpu.kernels import window_fold

    bf = jnp.bfloat16

    def fold(ck, cv, k, v, n, mask, slots):
        return (window_fold(ck, k, n, mask, slots),
                window_fold(cv, v, n, mask, slots))

    text = jax.jit(fold, donate_argnums=(0, 1)).lower(
        v5e((128, 8, 128, 256), bf), v5e((128, 8, 128, 128), bf),
        v5e((1, 8, 3584, 256), bf), v5e((1, 8, 3584, 128), bf),
        v5e((1, 1), jnp.int32), v5e((1, 1), jnp.float32),
        v5e((1, 1), jnp.int32)).compile().as_text()
    assert re.search(r'op_name="[^"]*window_fold/', text)
    assert not _copies_of(text, 128, 8, 128, 256)
    assert not _copies_of(text, 128, 8, 128, 128)


# -- the flash forward's grid: forms, heights, layouts (PR 50) ----------------

# name: (query heads, key/value heads, rows, key width, value width, dtype,
# window, sink, sequences that bring a key bias (0: none), the heights forced
# beside 128)
FORWARD_SHAPES = {
    "mimo_full_256": (64, 4, 256, 192, 128, "bfloat16", 0, False, 1, (256,)),
    "mimo_full_3584": (64, 4, 3584, 192, 128, "bfloat16", 0, False, 1,
                       (256, 512)),
    "mimo_window_256": (64, 8, 256, 192, 128, "bfloat16", 128, True, 1,
                        (256,)),
    "mimo_window_3584": (64, 8, 3584, 192, 128, "bfloat16", 128, True, 1,
                         (256, 512)),
    "glm_latent_1024": (20, 20, 1024, 256, 256, "bfloat16", 0, False, 0,
                        (256, 512)),
    "gpt2_f32_biased_512": (96, 96, 512, 64, 64, "float32", 0, False, 8,
                            (256, 512)),
}


def _forward_cases():
    for name, shape in FORWARD_SHAPES.items():
        for form in ("dense", "guarded", "flat"):
            for layout in ("rows", "lanes"):
                if layout == "lanes" and shape[4] % 128:
                    continue            # values of no whole lane tile
                yield pytest.param(name, form, layout,
                                   id=f"{name}-{form}-{layout}")


@pytest.mark.parametrize("name,form,layout", list(_forward_cases()))
def test_every_form_of_the_flash_forward_compiles(v5e, name, form, layout):
    """MiMo-V2-Flash's two layers at its shortest and longest bucket (with
    the prompts' key bias, as its prefill calls them), GLM-4.7-Flash's
    latent prefill and GPT-2's f32 biased prefill: every
    form of the grid, at 128 rows and at the taller blocks, in both tile
    layouts, compiles for the v5e (one program a case, a call a height)."""
    import dataclasses
    import sys

    fa = sys.modules["paddle_tpu.kernels.flash_attention"]
    Hq, H, S, D, Dv, dtype, window, with_sink, seqs, tall = \
        FORWARD_SHAPES[name]

    def attend(q, k, v, bias, sink):
        outs = []
        for h in (128,) + tall:
            cfg, b, scalars = fa._prepare(
                q, k, bias if seqs else None, True, None, 0.0, 0, 0, 0,
                Hq // (seqs or 1), h, 128, False, window)
            if with_sink:
                cfg = dataclasses.replace(cfg, has_sink=True)
            outs.append(fa._fwd(cfg, q, k, v, b, scalars,
                                sink if with_sink else None, form=form,
                                lanes=layout == "lanes"))
        return outs

    text = _compiles_with_mosaic(
        attend, v5e((Hq, S, D), dtype), v5e((H, S, D), dtype),
        v5e((H, S, Dv), dtype), v5e((seqs or 1, S), jnp.float32),
        v5e((Hq // (seqs or 1),), jnp.float32))
    assert len(re.findall(r"%flash_attention_fwd[.\d]* = ", text)) \
        == 1 + len(tall)


@pytest.mark.parametrize("name", sorted(FORWARD_SHAPES))
def test_the_chosen_flash_forward_compiles(v5e, name):
    """The same shapes as the library itself calls them."""
    Hq, H, S, D, Dv, dtype, window, with_sink, seqs, _ = \
        FORWARD_SHAPES[name]

    def attend(q, k, v, bias, sink):
        return flash_attention(
            q, k, v, bias=bias if seqs else None, causal=True,
            num_heads=Hq // (seqs or 1), window=window,
            sink=sink if with_sink else None)

    _compiles_with_mosaic(
        attend, v5e((Hq, S, D), dtype), v5e((H, S, D), dtype),
        v5e((H, S, Dv), dtype), v5e((seqs or 1, S), jnp.float32),
        v5e((Hq // (seqs or 1),), jnp.float32))


@pytest.mark.parametrize("shape,dtype,bias", [
    ((96, 512, 64), "float32", True),       # GPT-2's 512 bucket: flat, split
    ((64, 3584, 128), "bfloat16", False),   # a tall causal grid, in lanes
])
def test_a_stack_of_layers_traces_and_lowers_one_forward_kernel(v5e, shape,
                                                                 dtype, bias):
    """The setup budget (PERF.md, PR 50), held without a clock: the twelve
    layers of a program call the forward with one configuration and one set
    of shapes, so ``_fwd_kernel``'s body runs once for all of them (twice
    at most; twelve times before PR 50) and the lowered module holds ONE
    Mosaic body, called from twelve sites."""
    import sys

    fa = sys.modules["paddle_tpu.kernels.flash_attention"]
    runs, real = [0], fa._fwd_kernel

    def counted(*a, **kw):
        runs[0] += 1
        return real(*a, **kw)

    def stack(q, k, v, b):
        for _ in range(12):
            q = flash_attention(q, k, v, bias=b if bias else None,
                                causal=True, num_heads=12)
        return q

    qkv = v5e(shape, dtype)
    jax.clear_caches()
    fa._fwd_kernel = counted
    try:
        text = jax.jit(stack).lower(
            qkv, qkv, qkv, v5e((shape[0] // 12 or 1, shape[1]),
                               jnp.float32)).as_text()
    finally:
        fa._fwd_kernel = real
    assert 1 <= runs[0] <= 2
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 1
    assert len(re.findall(r"call @\w*_fwd\w*\(", text)) == 12


@pytest.mark.parametrize("case,digest", [
    ("gpt2_128", "e1fb2e0b531ac55a"), ("command_a_plus_128",
                                       "c2e2335b9fed4f94"),
    ("bert_512", "8442b240d069834b")])
def test_a_call_with_nothing_to_cut_builds_the_kernel_it_always_had(case,
                                                                    digest):
    """A 1 x 1 grid (GPT-2's 128 bucket with its key bias, Command A+'s
    grouped heads) and a call that is not causal (BERT's forward, dropout
    in the kernel) have no pair to skip and take no taller block: the
    kernel's jaxpr is the parent form's to the letter (one step, no table,
    no second branch), so such a program pays nothing for the grids the
    other calls take. ``digest``: the first 16 hex digits of the SHA-256 of
    the jaxpr's text as commit a55dee3 (the parent of PR 50) built it with
    this installation's JAX (``/root/scratch`` copy, ``str(jaxpr)``)."""
    import hashlib

    sds = jax.ShapeDtypeStruct
    f32, bf = jnp.float32, jnp.bfloat16
    fn, args, grid = {
        "gpt2_128": (
            lambda q, k, v, b: flash_attention(q, k, v, bias=b, causal=True,
                                               num_heads=12),
            (sds((96, 128, 64), f32),) * 3 + (sds((8, 128), f32),),
            (96, 1, 1)),
        "command_a_plus_128": (
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            num_heads=128),
            (sds((128, 128, 128), bf),) + (sds((8, 128, 128), bf),) * 2,
            (128, 1, 1)),
        "bert_512": (
            lambda q, k, v, b: flash_attention(q, k, v, bias=b, seed=3,
                                               dropout_rate=0.1,
                                               num_heads=12),
            (sds((24, 512, 64), bf),) * 3 + (sds((2, 512), f32),),
            (24, 4, 4)),
    }[case]

    def pallas_calls(jaxpr, out):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out.append(e.params)
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    pallas_calls(inner, out)
        return out

    (call,) = pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert call["grid_mapping"].grid == grid
    assert call["grid_mapping"].num_index_operands == 1     # no table
    text = str(call["jaxpr"])
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest, text


# -- the four-stream decoder's kernels at its published widths (PR 52) --------

@pytest.mark.parametrize("rows", [256, 1536])
def test_hyper_connection_kernels_compile_at_the_published_widths(v5e, rows):
    """A decode step's 256 slots and the largest prefill bucket: four f32
    streams of 3,584, a tile of 128 token rows a grid step (7.3 MB, two of
    them in flight beside the projection and the outputs: the kernels ask
    for 48 MiB of VMEM), the projection a true f32 product with the tokens
    in the lanes; both keep their names."""
    from paddle_tpu.kernels.hyper_connection import hc_read, hc_write

    f32 = jnp.float32
    text = _compiles_with_mosaic(
        lambda x, p, a, b: hc_read(x, p, a, b, n=4),
        v5e((rows, 14336), f32), v5e((24, 14336), f32), v5e((3,), f32),
        v5e((24,), f32))
    assert re.search(r"%hc_read[.\d]* = ", text)
    text = _compiles_with_mosaic(
        lambda x, y, p, r: hc_write(x, y, p, r, n=4),
        v5e((rows, 14336), f32), v5e((rows, 3584), f32), v5e((rows, 4), f32),
        v5e((rows, 16), f32))
    assert re.search(r"%hc_write[.\d]* = ", text)
    assert not re.search(rf"%copy[.\d]* = f32\[{rows},14336\]", text)


def test_latent_prefill_with_yarns_scale_takes_the_flash_forward(v5e):
    """The latent op's prefill form at Xing4.0's widths: 32 heads of 128 +
    64 beside values of 128 (the flash forward with keys wider than
    values, which until PR 52 the op sent down its primitive route), the
    softmax scale an attribute; the decode form at 32 heads x 576."""
    import paddle_tpu  # noqa: F401  (registers the ops)
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.lowering import LowerCtx

    bf = jnp.bfloat16
    rule = get_op_def("latent_attention").lower

    def fn(mode):
        def run(q, c, kr, w, cache, pos, smask, slots):
            ins = {"Q": [q], "C": [c], "KRope": [kr], "KVBW": [w],
                   "Cache": [cache], "Positions": [pos], "SlotMask": [smask]}
            if mode == "prefill":
                ins["Slots"] = [slots]
            out = rule(LowerCtx(platform="tpu"), ins,
                       {"mode": mode, "nope_dim": 128, "page_size": 128,
                        "scale": 0.14468})
            return out["Out"][0], out["CacheOut"][0]
        return run

    def lower(mode, B, S):
        col = lambda dt: v5e((B, 1), dt)
        return jax.jit(fn(mode), donate_argnums=(4,)).lower(
            v5e((B, 32, S, 192), bf), v5e((B, S, 512), bf),
            v5e((B, S, 64), bf), v5e((512, 32 * 256), bf),
            v5e((256, 1, 2048, 640), bf), col(jnp.int32), col(jnp.float32),
            col(jnp.int32)).compile().as_text()

    text = lower("prefill", 1, 1024)
    assert re.search(r"%flash_attention_fwd[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = bf16\[256,1,2048,640\]", text)
    text = lower("decode", 256, 1)
    assert re.search(r"%mla_decode_attention[.\d]* = ", text)
    assert not re.search(r"%copy[.\d]* = bf16\[256,1,2048,640\]", text)
