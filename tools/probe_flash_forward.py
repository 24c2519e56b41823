#!/usr/bin/env python3
"""What one call of the flash forward costs: on the chip by the grid's form,
the q-block's height and the way the score tile lies, and here, with no chip,
what a program pays to trace and lower it (PERF.md, PR 50).

    chiprun -- python3 tools/probe_flash_forward.py [--parent DIR] [--shape mimo-full]
    python3 tools/probe_flash_forward.py --deviceless           # compiles only
    python3 tools/probe_flash_forward.py --trace-cost [--tree DIR]

The decoders' prefill shapes (one dispatch's sequences x heads, a bucket's
rows, the layer's mask), and for each the forward as the library picks it
(``chosen``) beside every forced combination of a form, a height and a
layout: ``dense`` (every pair fetched, scored and masked: the grid until
PR 50), ``guarded`` (the k axis ends at the q-block's reach; a step past its
last visible block repeats that block and scores nothing) and ``flat`` (the
visible pairs alone, from a scalar-prefetched table), at q-blocks of 128 to
512 rows, with the score tile laid [queries, keys] (``rows``) or [keys,
queries] (``lanes``). ``--parent DIR`` also times the kernel of an unpacked
other commit (``git archive <commit> | tar -x -C DIR``). ``--check``
compares, on the chip, the chosen forward's ``o`` and ``lse`` with the dense
128-row grid's in both layouts (and the parent's) bit for bit.

A call is timed as the wall time of a jitted scan of twice ``--calls`` calls
less that of ``--calls``, each call fed the one before (what a program costs
around its calls cancels), each the best of ``--reps`` runs; on the chip only
(``--deviceless`` compiles every variant for a v5e it does not have and
times nothing). One JSON line a variant: ms a call, the steps a head's grid
takes and how many of them hold no visible pair (``dead``), us a step, and
the share of 197 TFLOP/s that the visible (query, key) pairs' products make
of the call's time. All of them in ``chiprun_out/probe_flash_forward.json``.

``--trace-cost`` needs no chip: it builds the benchmark's prefill programs
as their builders build them (GPT-2's two buckets and its chunk program,
GLM-4.7-Flash's longest bucket, MiMo-V2-Flash's 3,584 bucket, BERT's train
step), lowers each for the described v5e (Pallas' lowering to Mosaic runs
for real) and prints the seconds of trace + lower (wall, and the process's
own CPU seconds, which a busy shared host moves less; the best of ``--reps``
fresh builds, each traced from nothing: JAX's caches cleared), the number
of times ``_fwd_kernel``'s Python body ran and the Mosaic bodies named
``flash_attention_fwd`` in the module, beside its call sites; before them,
the same for stacks of identical flash calls and nothing else (CPU seconds:
the kernel's part of a program's, without the program's noise). ``--tree
DIR`` reads ``paddle_tpu`` and ``benchmark`` from another tree (the parent
unpacked), so one host says both sides. Seconds of a CPU host: compare them
with each other, never with the chip's.
"""
from __future__ import annotations

import argparse
import dataclasses
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

# the package exports a function of the module's name
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

PEAK_FLOPS = 197e12         # one TPU v5e, bf16 (benchmark/peaks.json)

# name: sequences a dispatch, query heads, key/value heads, key width, value
# width, dtype, window, causal_block, sink, key bias, buckets. The decoders'
# prefills carry their prompts' key-padding bias (``models/decoder.py``);
# GLM-4.7-Flash's latent prefill carries none.
SHAPES = {
    "mimo-full": (1, 64, 4, 192, 128, jnp.bfloat16, 0, 0, False, True,
                  (256, 512, 1024, 2048, 3584)),
    "mimo-window": (1, 64, 8, 192, 128, jnp.bfloat16, 128, 0, True, True,
                    (256, 512, 1024, 2048, 3584)),
    "qwen3-next": (1, 16, 2, 256, 256, jnp.bfloat16, 0, 0, False, True,
                   (1024, 2048, 3072)),
    "glm-4.7-flash": (1, 20, 20, 256, 256, jnp.bfloat16, 0, 0, False, False,
                      (512, 768, 1024)),
    "sdar": (1, 32, 4, 128, 128, jnp.bfloat16, 0, 4, False, True,
             (256, 512, 768, 1024)),
    "granite": (2, 32, 8, 128, 128, jnp.bfloat16, 0, 0, False, True,
                (256, 512, 768)),
    "command-a-plus": (16, 128, 8, 128, 128, jnp.bfloat16, 0, 0, False,
                       True, (128,)),
    "gpt2": (8, 12, 12, 64, 64, jnp.float32, 0, 0, False, True,
             (128, 512)),
}
FORMS = ("dense", "guarded", "flat")
HEIGHTS = (128, 256, 384, 512)
LAYOUTS = ("rows", "lanes")

# --trace-cost, the flash calls alone: name -> ([B*H, S, D], dtype, layers,
# key bias, the call's options)
STACKS = {
    "gpt2 f32 [96,512,64] biased": ((96, 512, 64), jnp.float32, 12, True,
                                    dict(causal=True, num_heads=12)),
    "gpt2 f32 [96,128,64] biased (1 x 1 grid)": (
        (96, 128, 64), jnp.float32, 12, True,
        dict(causal=True, num_heads=12)),
    "glm bf16 [20,1024,256]": ((20, 1024, 256), jnp.bfloat16, 8, False,
                               dict(causal=True, num_heads=20)),
    "bf16 [64,3584,128]": ((64, 3584, 128), jnp.bfloat16, 7, False,
                           dict(causal=True, num_heads=64)),
    "bert bf16 [384,512,64] biased, dropout": (
        (384, 512, 64), jnp.bfloat16, 12, True,
        dict(dropout_rate=0.1, seed=3, num_heads=12)),
}

# configuration -> the programs of it that --trace-cost lowers (None: all)
TRACE_COST = (("gpt2-base-serve", None),
              ("glm-4.7-flash-ep8-serve", ("prefill:1024",)),
              ("mimo-v2-flash-ep16-serve", ("prefill:3584",)),
              ("bert-base-pretrain", None))


def parent_module(root: str):
    """``kernels/flash_attention.py`` of the tree unpacked at ``root``."""
    path = os.path.join(root, "paddle_tpu", "kernels", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.kernels._probe_parent_flash_attention", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def forward_as(form, block_q, lanes, q, k, v, bias, sink, *, heads, window,
               causal_block):
    """This tree's forward alone at a forced form, height and layout."""
    cfg, bias, scalars = fa._prepare(q, k, bias, True, None, 0.0, 7, 0, 0,
                                     heads, block_q, 128, False, window,
                                     causal_block)
    if sink is not None:
        cfg = dataclasses.replace(cfg, has_sink=True)
    return fa._fwd(cfg, q, k, v, bias, scalars, sink, form=form, lanes=lanes)


def library_call(mod):
    def call(q, k, v, bias, sink, *, heads, window, causal_block):
        return mod.flash_attention_with_lse(
            q, k, v, bias=bias, causal=True, num_heads=heads, window=window,
            causal_block=causal_block, sink=sink)
    return call


def variants(parent, bucket: int, dv: int):
    """name -> call(q, k, v, bias, sink, heads=, window=, causal_block=)."""
    out = {}
    if parent is not None:
        out["parent"] = library_call(parent)
    out["chosen"] = library_call(fa)
    for form in FORMS:
        for h in HEIGHTS:
            if h > bucket or bucket % h:
                continue
            for layout in LAYOUTS:
                if layout == "lanes" and dv % 128:     # no whole lane tile
                    continue
                out[f"{form} q{h} {layout}"] = (
                    lambda form, h, lanes: lambda *a, **kw: forward_as(
                        form, h, lanes, *a, **kw))(form, h, layout == "lanes")
    return out


def steps_of(label: str, bucket: int, window: int, causal_block: int,
             d: int, dv: int, itemsize: int):
    """(steps a head's grid takes, those that score nothing, the label of
    the chosen grid or None)."""
    if label == "parent":       # the dense 128-row grid, but for a window
        if window:
            return None, None, None
        label = "dense q128 rows"
    form = h = lanes = None
    if label != "chosen":
        form, h, layout = label.split(" ")
        h, lanes = int(h[1:]), layout == "lanes"
    cfg = fa._shape_cfg(bucket, bucket, True, window, causal_block, h, 128)
    cfg, grid = fa._forward_grid(cfg, bucket, bucket, d, dv, itemsize, form,
                                 lanes)
    nq, nk = bucket // cfg.block_q, bucket // cfg.block_k
    live = len(fa._visible_pairs(cfg, nq, nk))
    steps = grid.steps if grid.form == "flat" else nq * grid.steps
    chosen = None if form else (
        f"q{cfg.block_q}xk{cfg.block_k}/{grid.form}"
        f"{' lanes' if grid.lanes else ''}")
    return steps, steps - live, chosen


def visible_products(bucket: int, window: int, causal_block: int) -> int:
    """(query, key) pairs one head's mask lets through."""
    q = np.arange(bucket)
    if causal_block:
        return int(((q // causal_block + 1) * causal_block).sum())
    return int(np.minimum(q + 1, window or bucket).sum())


def chain(call, calls: int, **kw):
    """``calls`` kernel calls in one program, each query fed the output of
    the call before, so none can be dropped or overlapped."""
    def run(q, k, v, bias, sink):
        def body(q, _):
            o, lse = call(q, k, v, bias, sink, **kw)
            return q + (o[..., :1] * 0 + lse[..., None] * 0).astype(
                q.dtype), None
        return jax.lax.scan(body, q, None, length=calls)[0]
    return jax.jit(run)


def operands(shape, bucket, seed):
    B, H, Hkv, D, Dv, dt, _, _, with_sink, with_bias, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    mk = lambda key, *s: jax.random.normal(key, s, jnp.float32).astype(dt)
    return (mk(keys[0], B * H, bucket, D), mk(keys[1], B * Hkv, bucket, D),
            mk(keys[2], B * Hkv, bucket, Dv),
            -1e4 * (jax.random.uniform(keys[4], (B, bucket)) < 0.1)
            if with_bias else None,
            jax.random.normal(keys[3], (H,), jnp.float32)
            if with_sink else None)


def check(name, shape, bucket, parent, seed):
    """Bits of the chosen forward against the dense 128-row grid's."""
    B, H, Hkv, D, Dv, dt, window, L, _, _, _ = shape
    ops = operands(shape, bucket, seed)
    kw = dict(heads=H, window=window if window < bucket else 0,
              causal_block=L)
    got = jax.jit(lambda *a: library_call(fa)(*a, **kw))(*ops)
    line = {"shape": name, "bucket": bucket, "check": True}
    others = {"dense q128 rows": lambda *a: forward_as(
        "dense", 128, False, *a, **kw)}
    if "lanes" in (steps_of("chosen", bucket, kw["window"], L, D, Dv,
                            jnp.dtype(dt).itemsize)[2] or ""):
        # the same tile the other way up: one more rounding apart from the
        # rows layout's, so the grid is judged against its own layout too
        others["dense q128 lanes"] = lambda *a: forward_as(
            "dense", 128, True, *a, **kw)
    if parent is not None:
        others["parent"] = lambda *a: library_call(parent)(*a, **kw)
    for label, fn in others.items():
        want = jax.jit(fn)(*ops)
        line[f"bits_equal_{label}"] = all(
            bool(jnp.array_equal(a, b, equal_nan=True))
            for a, b in zip(got, want))
        line[f"max_abs_diff_{label}"] = float(jnp.max(jnp.abs(
            got[0].astype(jnp.float32) - want[0].astype(jnp.float32))))
        line[f"lse_max_abs_diff_{label}"] = float(jnp.max(jnp.abs(
            got[1] - want[1])))
    return line


# --------------------------------------------------------------------------
# --trace-cost: what a program pays to trace and lower its flash calls
# --------------------------------------------------------------------------

def _programs(name: str):
    """label -> (program, fetch names, batch) of a benchmark configuration,
    as its family's builder builds them."""
    bench = os.path.join(TREE, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import harness

    cfg = harness.load_json(os.path.join(bench, "configs", name + ".json"))
    if cfg["runner"] == "train":
        m = importlib.import_module(f"families.{cfg['family']}").build(cfg)
        return {"train step": (m["main"], [m["loss"].name], 32)}
    if cfg["runner"] != "serve":        # a stored slice of a larger model
        reference = importlib.import_module(f"reference.{cfg['family']}")
        cfg["model"] = reference.model_config(cfg)
    net = importlib.import_module(f"families.{cfg['family']}").build(cfg)

    def fetches(p):
        stats = p.get("expert_stats")
        return [p["first_token"].name] + (
            [stats.name] if stats is not None else [])

    out = {f"prefill:{b}": (p["main"], fetches(p), None)
           for b, p in net["prefill"].items()}
    if net.get("chunk"):
        out[f"chunk:{net['prefill_chunk']}"] = (
            net["chunk"]["main"], fetches(net["chunk"]), None)
    return out


def _lower(program, fetch_names, dev, batch):
    """``Executor.run(program, fetch_list=fetch_names)``'s step function
    traced and lowered for ``dev`` from shapes alone (the path of
    ``benchmark/tools/deviceless.py``, short of the compile)."""
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu.core.types import np_dtype

    class Place:
        def jax_device(self):
            return dev

    exe = fluid.Executor(fluid.CPUPlace())
    exe.place = Place()
    block = program.global_block
    feeds = {n for n, v in block.vars.items() if getattr(v, "is_data", False)}
    step = exe._compile(program, feeds, list(fetch_names), fluid.Scope())
    sharding = SingleDeviceSharding(dev)

    def shaped(name):
        v = block.var(name)
        shape = tuple(batch if d in (-1, None) else int(d) for d in v.shape)
        dt = jax.dtypes.canonicalize_dtype(np.dtype(np_dtype(v.dtype)))
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sharding)
    return step.fn.lower([shaped(n) for n in step.feed_names],
                         [shaped(n) for n in step.donated_names],
                         [shaped(n) for n in step.ro_names], key)


_MOSAIC_BODY = re.compile(r"stablehlo\.custom_call @tpu_custom_call")


def count_flash(text: str):
    """(Mosaic call bodies of the flash forward in a lowered module's text,
    the sites that reach one)."""
    bodies = sum(1 for line in text.splitlines()
                 if "tpu_custom_call" in line
                 and "flash_attention_fwd" in line)
    shared = re.findall(r"func\.func private @(\w*_fwd\w*)\(", text)
    sites = bodies if not shared else sum(
        len(re.findall(r"call @" + re.escape(f) + r"\(", text))
        for f in set(shared))
    return bodies, sites


def stack_cost(dev, reps: int):
    """The trace + lower of a stack of identical flash calls and nothing
    else: what the programs' seconds hold of the kernel, without the rest
    of a program's noise."""
    from jax.sharding import SingleDeviceSharding

    place = SingleDeviceSharding(dev)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=place)
    lines = []
    for name, (shape, dt, layers, bias, kw) in STACKS.items():
        def stack(q, k, v, b):
            for _ in range(layers):
                q = fa.flash_attention(q, k, v, bias=b if bias else None,
                                       **kw)
            return q

        best = float("inf")
        for _ in range(reps):
            jax.clear_caches()
            c0 = time.process_time()
            jax.jit(stack).lower(
                sds(shape, dt), sds(shape, dt), sds(shape, dt),
                sds((shape[0] // 12 or 1, shape[1]), jnp.float32))
            best = min(best, time.process_time() - c0)
        lines.append({"stack": name, "layers": layers,
                      "trace_lower_cpu_s": best})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def trace_cost(reps: int, only):
    from jax.experimental import topologies

    dev = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    stacks = [] if only else stack_cost(dev, max(reps, 3))
    runs = [0]
    real = fa._fwd_kernel

    def counted(*a, **kw):
        runs[0] += 1
        return real(*a, **kw)

    fa._fwd_kernel = counted
    lines = []
    for name, wanted in TRACE_COST:
        if only and name not in only:
            continue
        best = {}
        for _ in range(reps):
            for label, (prog, fetch, batch) in _programs(name).items():
                if wanted and label not in wanted:
                    continue
                jax.clear_caches()
                runs[0] = 0
                t0, c0 = time.perf_counter(), time.process_time()
                lowered = _lower(prog, fetch, dev, batch)
                s, cpu = time.perf_counter() - t0, time.process_time() - c0
                bodies, sites = count_flash(lowered.as_text())
                line = {"config": name, "program": label,
                        "trace_lower_s": s, "trace_lower_cpu_s": cpu,
                        "fwd_kernel_body_runs": runs[0],
                        "flash_fwd_mosaic_bodies": bodies,
                        "flash_fwd_call_sites": sites}
                if label not in best or s < best[label]["trace_lower_s"]:
                    best[label] = line
        for line in best.values():
            lines.append(line)
            print(json.dumps(line), flush=True)
    fa._fwd_kernel = real
    return stacks + lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="an unpacked other commit to time too")
    ap.add_argument("--tree", help="--trace-cost: read paddle_tpu and "
                    "benchmark from this unpacked tree")
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every variant for a v5e, time nothing")
    ap.add_argument("--trace-cost", action="store_true",
                    help="seconds of trace + lower of the benchmark's "
                    "prefill programs, with no chip")
    ap.add_argument("--config", action="append",
                    help="--trace-cost: these configurations only")
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES),
                    help="these shapes only (may repeat)")
    ap.add_argument("--bucket", type=int, action="append",
                    help="these buckets only (may repeat)")
    ap.add_argument("--only", action="append",
                    help="these variants only, e.g. 'flat q512 lanes' (may "
                    "repeat)")
    ap.add_argument("--check", action="store_true",
                    help="compare bits on the chip, time nothing")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/probe_flash_forward.json")
    args = ap.parse_args(argv)
    if args.trace_cost:
        lines = trace_cost(args.reps, args.config)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"tree": TREE, "reps": args.reps, "results": lines},
                      f, indent=1)
        return 0
    parent = parent_module(args.parent) if args.parent else None

    if args.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        print("probe_flash_forward: no TPU here; a time comes from the chip "
              "(--deviceless compiles without one, --trace-cost times the "
              "host's trace and lowering)", file=sys.stderr)
        return 2

    results = []
    for name, shape in SHAPES.items():
        if args.shape and name not in args.shape:
            continue
        (B, H, Hkv, D, Dv, dt, window, L, with_sink, with_bias,
         buckets) = shape
        itemsize = jnp.dtype(dt).itemsize
        for bucket in buckets:
            if args.bucket and bucket not in args.bucket:
                continue
            if args.check:
                results.append(check(name, shape, bucket, parent, args.seed))
                print(json.dumps(results[-1]), flush=True)
                continue
            win = window if window < bucket else 0
            products = visible_products(bucket, win, L)
            for label, call in variants(parent, bucket, Dv).items():
                if args.only and label not in args.only:
                    continue
                steps, dead, chosen = steps_of(label, bucket, win, L, D, Dv,
                                               itemsize)
                line = {"shape": name, "bucket": bucket, "variant": label,
                        "steps": steps, "dead": dead}
                if chosen:
                    line["grid"] = chosen
                kw = dict(heads=H, window=win, causal_block=L)
                fn = chain(call, args.calls, **kw)
                twice = chain(call, 2 * args.calls, **kw)
                try:
                    if args.deviceless:
                        sds = lambda s, d: jax.ShapeDtypeStruct(
                            s, d, sharding=place)
                        fn.lower(sds((B * H, bucket, D), dt),
                                 sds((B * Hkv, bucket, D), dt),
                                 sds((B * Hkv, bucket, Dv), dt),
                                 sds((B, bucket), jnp.float32)
                                 if with_bias else None,
                                 sds((H,), jnp.float32) if with_sink
                                 else None).compile()
                        line["compiles"] = True
                    else:
                        ops = operands(shape, bucket, args.seed)
                        best = [float("inf")] * 2
                        for i, f in enumerate((fn, twice)):
                            f(*ops).block_until_ready()
                            for _ in range(args.reps):
                                t0 = time.perf_counter()
                                f(*ops).block_until_ready()
                                best[i] = min(best[i],
                                              time.perf_counter() - t0)
                        s = (best[1] - best[0]) / args.calls
                        line["ms_a_call"] = 1e3 * s
                        if steps:
                            line["us_a_step"] = 1e6 * s / (B * H * steps)
                        line["mxu_pct"] = 100.0 * (
                            2.0 * B * H * products * (D + Dv)
                            / s / PEAK_FLOPS)
                except Exception as e:      # a block the compiler refuses
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
