#!/usr/bin/env python3
"""What one call of the decode kernel costs, by the form of its k-walk, by
the tile a grid step carries and by how long the sequences are (PERF.md
section 6, PRs 28, 32 and 53).

    chiprun -- python3 tools/probe_decode_walk.py [--parent DIR] [--shape gpt2]
    python3 tools/probe_decode_walk.py --deviceless        # compiles only
    python3 tools/probe_decode_walk.py --trace-cost [--tree DIR]  # no chip

Three shapes, three serving cells' (``f32[64,12,1024,64]`` with one query
row, GPT-2's; ``bf16[64,8,1024,128]`` with a group of 16 query heads,
Command A+'s; MiMo-V2-Flash's full layers, ``bf16[128,4,4096,256]`` keys
beside values of 128 with a sink a query head), three sets of lengths
(every sequence 1 key: the price of a visit that fetches one block; the
cell's mix; every cache full: every block of the cache is live) and these
forms of the walk:

* ``live``: the library's. The grid has as many steps as k-blocks are
  fetched, listed in a scalar-prefetched table (``walk_steps``; the grid's
  bound is traced), the ``BlockSpec`` pipeline prefetching from one step
  to the next across sequences (the first form of ISSUE 53).
* ``loop``: a prototype kept here, never the library's. A grid of
  ``(sequence, group of heads)``, the caches left in HBM, an in-kernel
  ``fori_loop`` over the live blocks that double-buffers them with
  ``make_async_copy`` and starts the next visit's first block before it
  ends (the second form of ISSUE 53). No append, no sink.
* ``parent`` (``--parent DIR``): the kernel of an unpacked other commit
  (``git archive <commit> | tar -x -C DIR``); ``equal_to_parent`` says
  whether ``live``'s output has the parent's bits.
* ``tile HxR``: ``live`` at every other tile of ``(heads, rows)`` the
  shape allows (``--tiles``).
* ``append`` / ``parent append`` (GPT-2's shape, rows in lanes): the call
  that also writes the step's row, the caches carried and donated.

A call is timed as the wall time of a jitted scan of twice ``--calls``
calls less that of ``--calls``, each call fed the one before (what a
program costs around its calls cancels), each the best of ``--reps`` runs;
on the chip only (``--deviceless`` compiles every variant for a v5e it does
not have and times nothing). One JSON line per variant, and all of them in
``chiprun_out/probe_decode_walk.json``.

``--trace-cost`` needs no chip: it builds the chained decode programs of
GPT-2 and MiMo-V2-Flash (``--config``: others) as the benchmark's builders
build them, traces and lowers each for a described v5e and prints the
seconds, the decode kernel's Mosaic bodies in the module and a hash of the
lowered text with the Mosaic bodies' source locations taken out (two trees
whose hashes agree compile the same program); ``--tree DIR`` reads
``paddle_tpu`` and ``benchmark`` from another unpacked commit, so one host
says both sides.
"""
from __future__ import annotations

import argparse
import base64
import functools
import hashlib
import importlib
import importlib.util
import json
import os
import re
import sys
import time

# the tree whose ``paddle_tpu`` (and ``benchmark``) is imported: read before
# the imports it decides
_tree = argparse.ArgumentParser(add_help=False)
_tree.add_argument("--tree", default=os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir))
TREE = os.path.abspath(_tree.parse_known_args()[0].tree)
sys.path.insert(0, TREE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels import decode_attention as da

SHAPES = {
    # name: slots, key/value heads, rows, key dim, dtype, query heads a
    # group, value dim, a sink a query head, the mix's lengths (low, high;
    # None: the decode-saturated mix)
    "gpt2": (64, 12, 1024, 64, jnp.float32, 1, 64, False, None),
    "command-a-plus": (64, 8, 1024, 128, jnp.bfloat16, 16, 128, False, None),
    "mimo-v2-flash": (128, 4, 4096, 256, jnp.bfloat16, 16, 128, True,
                      (300, 3700)),
}
PAGE = 128
TRACE_COST = ("gpt2-base-serve", "mimo-v2-flash-ep16-serve")


def lengths_of(kind: str, slots: int, s_max: int, seed: int,
               mix=None) -> np.ndarray:
    if kind == "ones":
        return np.ones(slots, np.int32)
    if kind == "full":
        return np.full(slots, s_max, np.int32)
    rng = np.random.default_rng(seed)
    if mix is not None:     # a cell of long contexts: uniform between two
        return rng.integers(mix[0], mix[1] + 1, slots).astype(np.int32)
    # the decode-saturated mix: prompts 32-128, answers 64-192, a sequence
    # seen at a uniformly drawn point of its answer
    prompt = rng.integers(32, 129, slots)
    answer = rng.integers(64, 193, slots)
    return (prompt + 1 + rng.integers(0, answer)).astype(np.int32)


def tiles_of(heads: int, s_max: int):
    out = []
    for hb in sorted({1, heads // 2, heads} - {0}):
        for rows in (128, 256, 512, 1024):
            if heads % hb == 0 and s_max % rows == 0:
                out.append((hb, rows))
    return out


def parent_kernel(root: str):
    """``flash_attention_decode`` of the tree unpacked at ``root``, beside
    this tree's package (its relative imports resolve here)."""
    path = os.path.join(root, "paddle_tpu", "kernels", "decode_attention.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.kernels._probe_parent_decode_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.flash_attention_decode


# --------------------------------------------------------------------------
# the second form: a loop over the live blocks inside the kernel
# --------------------------------------------------------------------------

def _loop_kernel(scale, group, minor, num_k, len_ref, q_ref, k_hbm, v_hbm,
                 o_ref, m_scr, l_scr, acc, k_buf, v_buf, sems, base_ref):
    b, hg = pl.program_id(0), pl.program_id(1)
    groups = pl.num_programs(1)
    visit, visits = b * groups + hg, pl.num_programs(0) * groups
    heads = k_buf.shape[1]
    rows_at, d_at = (2, 1) if minor else (1, 2)
    block_k = k_buf.shape[1 + rows_at]
    length = len_ref[b]
    blocks = da.last_live_block(length, 1, block_k, num_k) + 1

    def fetch(b, hg, ik, slot):
        """The two copies that bring block ``ik`` of a visit into a slot
        (to start, or, made again alike, to wait for)."""
        rows = pl.ds(pl.multiple_of(ik * block_k, block_k), block_k)
        hs = pl.ds(hg * heads, heads)
        at = (b, hs, slice(None), rows) if minor else (b, hs, rows)
        return [pltpu.make_async_copy(c.at[at], buf.at[slot],
                                      sems.at[i, slot])
                for i, (c, buf) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf)))]

    @pl.when(visit == 0)
    def _first():
        base_ref[0] = 0
        for copy in fetch(b, hg, 0, 0):
            copy.start()

    base = base_ref[0]
    m_scr[:] = jnp.full_like(m_scr, da.NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc[:] = jnp.zeros_like(acc)

    def body(ik, _):
        slot = (base + ik) % 2

        @pl.when(ik + 1 < blocks)
        def _next_block():
            for copy in fetch(b, hg, ik + 1, 1 - slot):
                copy.start()

        # the next visit's first block, started before this one ends
        @pl.when((ik + 1 == blocks) & (visit + 1 < visits))
        def _next_visit():
            nxt = visit + 1
            for copy in fetch(nxt // groups, nxt % groups, 0, 1 - slot):
                copy.start()

        for copy in fetch(b, hg, ik, slot):
            copy.wait()
        q = q_ref[...]
        s = jax.lax.dot_general(q, k_buf[slot],
                                (((2,), (d_at,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        2)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
        s = jnp.where(k_pos < length + row, s, da.NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        m_safe = jnp.where(m_new > da.NEG_INF * 0.5, m_new, 0.0)
        corr = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_new = corr * l_scr[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_buf.dtype), v_buf[slot],
                                 (((2,), (rows_at,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return _

    jax.lax.fori_loop(0, blocks, body, 0)
    base_ref[0] = (base + blocks) % 2
    l = l_scr[:, :, :1]
    o_ref[...] = (acc[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def loop_call(q, k_cache, v_cache, lengths, tile, *, minor, scale, group,
              interpret=False):
    """The second form on ``q`` [B * H, R, D] and caches [B, H, S, D(v)]:
    what ``da._decode_call`` is to the first."""
    B, H = k_cache.shape[:2]
    _, R, D = q.shape
    Dv = v_cache.shape[3]
    hb, bk = tile
    nk = k_cache.shape[2] // bk
    if minor:
        k_cache, v_cache = k_cache.swapaxes(2, 3), v_cache.swapaxes(2, 3)
    rows_of = lambda b, hg, *_: (b * (H // hb) + hg, 0, 0)
    k_block = (hb, D, bk) if minor else (hb, bk, D)
    v_block = (hb, Dv, bk) if minor else (hb, bk, Dv)
    return pl.pallas_call(
        functools.partial(_loop_kernel, scale, int(group), minor, nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[pl.BlockSpec((hb, R, D), rows_of),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((hb, R, Dv), rows_of),
            scratch_shapes=[pltpu.VMEM((hb, R, 128), jnp.float32),
                            pltpu.VMEM((hb, R, 128), jnp.float32),
                            pltpu.VMEM((hb, R, Dv), jnp.float32),
                            pltpu.VMEM((2,) + k_block, k_cache.dtype),
                            pltpu.VMEM((2,) + v_block, v_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B * H, R, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="decode_attention_loop",
    )(lengths, q, k_cache, v_cache)


# --------------------------------------------------------------------------
# the variants of a shape
# --------------------------------------------------------------------------

def chain(call, calls: int):
    """``calls`` kernel calls in one program, each query fed the output of
    the call before, so none can be dropped or overlapped."""
    def run(q, k, v, n):
        def body(q, _):
            o = call(q, k, v, n)
            return q + (o[..., :1] * 0).astype(q.dtype), None
        return jax.lax.scan(body, q, None, length=calls)[0]
    return jax.jit(run)


def chain_append(call, calls: int, new):
    """The same around a call that appends: the caches are carried too,
    donated, and come back for the next run."""
    def run(q, k, v, n):
        def body(c, _):
            q, k, v = c
            o, k, v = call(q, k, v, n, append=(new, new, None))
            return (q + (o * 0).astype(q.dtype), k, v), None
        return jax.lax.scan(body, (q, k, v), None, length=calls)[0]
    return jax.jit(run, donate_argnums=(1, 2))


def variants(shape, parent, tiles: bool):
    """name -> call(q [B*H, G, D], k [B*H, S, D], v [B*H, S, Dv],
    lengths [B]); a name that ends in ``append`` takes ``append=`` too."""
    B, H, S, D, dt, G, Dv, with_sink, _ = shape
    sublanes = 8 * (4 // jnp.dtype(dt).itemsize)
    R = -(-G // sublanes) * sublanes
    minor = Dv == D and da.rows_minor(D, dt, PAGE)
    sink = jnp.linspace(-1.0, 1.0, H * G) if with_sink else None
    kw = dict(num_heads=H, page_size=PAGE, group=G)
    out = {}
    if parent is not None:
        out["parent"] = lambda q, k, v, n: parent(q, k, v, n, sink=sink, **kw)
    out["live"] = lambda q, k, v, n: da.flash_attention_decode(
        q, k, v, n, sink=sink, **kw)

    def padded(q):
        return jnp.concatenate([q, jnp.broadcast_to(
            q[:, -1:], (B * H, R - G, D))], axis=1) if R > G else q

    def tiled(tile, call):
        return lambda q, k, v, n: call(
            padded(q), k.reshape(B, H, S, D), v.reshape(B, H, S, Dv), n,
            tile, minor=minor, scale=D ** -0.5, group=G)[:, :G]

    chosen = da.kv_tile(H, S, D, dt, PAGE, v_dim=Dv)
    if not with_sink:
        out["loop"] = tiled(chosen, loop_call)
    if minor and G == 1:
        if parent is not None:
            out["parent append"] = lambda q, k, v, n, append: parent(
                q, k, v, n, append=append, **kw)
        out["append"] = lambda q, k, v, n, append: (
            da.flash_attention_decode(q, k, v, n, append=append, **kw))
    if tiles and not with_sink:
        live = functools.partial(da._decode_call, q_len=1, interpret=False)
        for tile in tiles_of(H, S):
            if tile != chosen:
                out["tile %dx%d" % tile] = tiled(tile, live)
    return out


# --------------------------------------------------------------------------
# --trace-cost: what a decode program pays to be traced and lowered
# --------------------------------------------------------------------------

def _lower_chained_decode(name: str, dev):
    """``run_chained(decode, steps=decode_chunk)``'s executable of a
    benchmark configuration, traced and lowered for ``dev`` from shapes
    alone (the path of ``benchmark/tools/deviceless_stored.py``, short of
    the compile). Returns (build seconds, trace + lower seconds, text)."""
    from jax.sharding import SingleDeviceSharding

    bench = os.path.join(TREE, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import harness

    import paddle_tpu as fluid
    from paddle_tpu.core.types import np_dtype

    class Place:
        def jax_device(self):
            return dev

    t0 = time.perf_counter()
    cfg = harness.load_json(os.path.join(bench, "configs", name + ".json"))
    if cfg["runner"] != "serve":        # a stored slice of a larger model
        reference = importlib.import_module(f"reference.{cfg['family']}")
        cfg["model"] = reference.model_config(cfg)
    net = importlib.import_module(f"families.{cfg['family']}").build(cfg)
    decode = net["decode"]
    stats = decode.get("expert_stats")
    fetches = [decode["next_token"].name] + (
        [stats.name] if stats is not None else [])
    steps = cfg["serving"]["generation"]["decode_chunk"]
    built = time.perf_counter() - t0

    t0 = time.perf_counter()
    program = decode["main"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.place = Place()
    step, _ = exe._lookup_chained(program, program, {}, fetches, steps,
                                  fluid.Scope(), None)
    on_dev = SingleDeviceSharding(dev)
    block = program.global_block

    def var(n):
        v = block.var(n)
        dt = jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype)))
        return jax.ShapeDtypeStruct(tuple(int(d) for d in v.shape), dt,
                                    sharding=on_dev)

    placed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                            sharding=on_dev)
    donated = [var(n) for n in step.donated_names]
    kept = [var(n) for n in step.kept_names]
    ro = [var(n) for n in step.ro_names]
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), steps))
    state = jax.eval_shape(step.base_step, [], donated + kept, ro,
                           jax.eval_shape(lambda: jax.random.key(0)))[1]
    at = {n: i for i, n in enumerate(step.io["state_out"])}
    wo = [placed(state[at[n]]) for n in step.wo_names]
    lowered = step.fn.lower([], donated, kept, ro, placed(keys), wo,
                            placed(jax.ShapeDtypeStruct((), jnp.float32)))
    return built, time.perf_counter() - t0, lowered.as_text()


_MOSAIC_BODY = re.compile(r'(body\\22: \\22)([A-Za-z0-9+/=]+)')


def _hash_without_locations(text: str) -> str:
    """A hash of a lowered module's text with every Mosaic body read back
    and printed without its operations' source locations (a body is
    serialized with them: the tree's path and the line of every frame), so
    two trees compile the same program where their hashes agree, wherever
    they lie and whatever moved in files the program does not run."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.JaxIrContext()
    ctx.append_dialect_registry(mlir.upstream_dialects)
    ctx.load_all_available_dialects()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # the serialized dialect's
    h, at = hashlib.sha256(), 0
    with ctx:
        for m in _MOSAIC_BODY.finditer(text):
            h.update(text[at:m.start(2)].encode())
            h.update(ir.Module.parse(base64.b64decode(
                m.group(2))).operation.get_asm(
                    enable_debug_info=False).encode())
            at = m.end(2)
    h.update(text[at:].encode())
    return h.hexdigest()[:16]


def trace_cost(reps: int, configs):
    from jax.experimental import topologies

    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    lines = []
    for name in configs:
        best = None
        for _ in range(reps):
            jax.clear_caches()
            built, s, text = _lower_chained_decode(name, dev)
            line = {"config": name, "program": "chained decode",
                    "build_s": built, "trace_lower_s": s,
                    "decode_attention_mosaic_bodies": sum(
                        1 for l in text.splitlines()
                        if "tpu_custom_call" in l
                        and '"decode_attention"' in l),
                    "lowered_sha256": _hash_without_locations(text)}
            if best is None or s < best["trace_lower_s"]:
                best = line
        lines.append(best)
        print(json.dumps(best), flush=True)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="an unpacked other commit to time too")
    ap.add_argument("--tree", help="--trace-cost: read paddle_tpu and "
                    "benchmark from this unpacked tree")
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every variant for a v5e, time nothing")
    ap.add_argument("--trace-cost", action="store_true",
                    help="seconds of trace + lower of the benchmark's "
                    "chained decode programs, with no chip")
    ap.add_argument("--config", action="append",
                    help="--trace-cost: these configurations (default: "
                    + ", ".join(TRACE_COST) + ")")
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES),
                    help="these shapes only (may repeat)")
    ap.add_argument("--tiles", action="store_true",
                    help="also every other tile the shape allows")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/probe_decode_walk.json")
    args = ap.parse_args(argv)
    if args.trace_cost:
        lines = trace_cost(args.reps, args.config or TRACE_COST)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"tree": TREE, "reps": args.reps, "results": lines},
                      f, indent=1)
        return 0
    parent = parent_kernel(args.parent) if args.parent else None

    if args.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        print("probe_decode_walk: no TPU here; a time comes from the chip "
              "(--deviceless compiles without one, --trace-cost times the "
              "host's trace and lowering)", file=sys.stderr)
        return 2

    results = []
    for name, shape in SHAPES.items():
        if args.shape and name not in args.shape:
            continue
        B, H, S, D, dt, G, Dv, _, mix = shape
        shapes = ((B * H, G, D), (B * H, S, D), (B * H, S, Dv))
        outputs = {}
        for label, call in variants(shape, parent, args.tiles).items():
            line = {"shape": name, "variant": label}
            if label.endswith("append"):
                new = jnp.ones((B, H, 1, D), dt)
                fn, twice = (chain_append(call, c, new)
                             for c in (args.calls, 2 * args.calls))
            else:
                fn, twice = chain(call, args.calls), chain(call,
                                                           2 * args.calls)
            try:
                if args.deviceless:
                    sds = lambda s, d: jax.ShapeDtypeStruct(s, d,
                                                            sharding=place)
                    fn.lower(*(sds(s, dt) for s in shapes),
                             sds((B,), jnp.int32)).compile()
                    line["compiles"] = True
                else:
                    key = jax.random.PRNGKey(args.seed)
                    q, k, v = (jax.random.normal(kk, s, jnp.float32).astype(
                        dt) for kk, s in zip(jax.random.split(key, 3),
                                             shapes))
                    for kind in ("ones", "mix", "full"):
                        n = jnp.asarray(lengths_of(kind, B, S, args.seed,
                                                   mix))
                        if not label.endswith("append"):
                            outputs[label, kind] = np.asarray(
                                jax.jit(call)(q, k, v, n), np.float32)
                        best = [float("inf")] * 2
                        for i, f in enumerate((fn, twice)):
                            for rep in range(args.reps + 1):
                                t0 = time.perf_counter()
                                out = jax.block_until_ready(f(q, k, v, n))
                                if rep:     # the first run compiles
                                    best[i] = min(best[i],
                                                  time.perf_counter() - t0)
                                if label.endswith("append"):
                                    _, k, v = out
                        line[kind + "_ms_a_call"] = (
                            1e3 * (best[1] - best[0]) / args.calls)
                        if label != "parent" and ("parent", kind) in outputs \
                                and (label, kind) in outputs:
                            line.setdefault("equal_to_parent", {})[kind] = (
                                bool(np.array_equal(
                                    outputs[label, kind],
                                    outputs["parent", kind])))
            except Exception as e:      # a tile the compiler refuses
                line["error"] = (type(e).__name__ + ": "
                                 + str(e).strip().splitlines()[0][:200])
            results.append(line)
            print(json.dumps(line), flush=True)
    if not args.deviceless:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "calls": args.calls, "reps": args.reps,
                       "seed": args.seed, "results": results}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
