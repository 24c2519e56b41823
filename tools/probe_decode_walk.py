#!/usr/bin/env python3
"""What one call of the decode kernel costs, by the tile a grid step carries
and by how long the sequences are (PERF.md, PR 28).

    chiprun -- python3 tools/probe_decode_walk.py [--parent DIR] [--shape gpt2]
    python3 tools/probe_decode_walk.py --deviceless        # compiles only

Two shapes, the two serving cells' (``f32[64,12,1024,64]`` with one query
row; ``bf16[64,8,1024,128]`` with a group of 16 query heads), three sets
of lengths (every sequence 1 key: the price of a step that does nothing;
the saturated mix's 33-320; every cache full) and every tile of
``(heads, rows)`` the shape allows. ``--parent DIR`` also times the kernel
of an unpacked other commit (``git archive <commit> | tar -x -C DIR``).
A call is timed as the wall time of a jitted scan of twice ``--calls``
calls less that of ``--calls``, each call fed the one before (what a
program costs around its calls cancels), each the best of ``--reps`` runs;
on the chip only
(``--deviceless`` compiles every variant for a v5e it does not have and
times nothing). One JSON line per variant, and all of them in
``chiprun_out/probe_decode_walk.json``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import decode_attention as da

SHAPES = {
    # name: slots, key/value heads, rows, head dim, dtype, query heads a group
    "gpt2": (64, 12, 1024, 64, jnp.float32, 1),
    "command-a-plus": (64, 8, 1024, 128, jnp.bfloat16, 16),
}
PAGE = 128


def lengths_of(kind: str, slots: int, s_max: int, seed: int) -> np.ndarray:
    if kind == "ones":
        return np.ones(slots, np.int32)
    if kind == "full":
        return np.full(slots, s_max, np.int32)
    # the decode-saturated mix: prompts 32-128, answers 64-192, a sequence
    # seen at a uniformly drawn point of its answer
    rng = np.random.default_rng(seed)
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


def chain(call, calls: int):
    """``calls`` kernel calls in one program, each query fed the output of
    the call before, so none can be dropped or overlapped."""
    def run(q, k, v, n):
        def body(q, _):
            o = call(q, k, v, n)
            return q + (o * 0).astype(q.dtype), None
        return jax.lax.scan(body, q, None, length=calls)[0]
    return jax.jit(run)


def variants(shape, parent):
    """name -> call(q [B*H, G, D], k, v [B*H, S, D], lengths [B])."""
    B, H, S, D, dt, G = shape
    sublanes = 8 * (4 // jnp.dtype(dt).itemsize)
    R = -(-G // sublanes) * sublanes
    out = {}
    if parent is not None:
        out["parent"] = lambda q, k, v, n: parent(
            q, k, v, n, num_heads=H, page_size=PAGE, group=G)

    def tiled(tile):
        def call(q, k, v, n):
            q8 = jnp.concatenate([q, jnp.broadcast_to(
                q[:, -1:], (B * H, R - G, D))], axis=1) if R > G else q
            o = da._decode_call(
                q8, k.reshape(B, H, S, D), v.reshape(B, H, S, D), n, tile,
                minor=da.rows_minor(D, dt, PAGE), scale=D ** -0.5, group=G,
                q_len=1, interpret=False)
            return o[:, :G]
        return call

    for tile in tiles_of(H, S):
        out["tile %dx%d" % tile] = tiled(tile)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="an unpacked other commit to time too")
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every variant for a v5e, time nothing")
    ap.add_argument("--shape", choices=sorted(SHAPES),
                    help="one of the two shapes only")
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/probe_decode_walk.json")
    args = ap.parse_args(argv)
    parent = parent_kernel(args.parent) if args.parent else None

    if args.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.devices()[0].platform != "tpu":
        print("probe_decode_walk: no TPU here; a time comes from the chip "
              "(--deviceless compiles without one)", file=sys.stderr)
        return 2

    results = []
    for name, shape in SHAPES.items():
        if args.shape not in (None, name):
            continue
        B, H, S, D, dt, G = shape
        chosen = "tile %dx%d" % da.kv_tile(H, S, D, dt, PAGE)
        for label, call in variants(shape, parent).items():
            line = {"shape": name, "variant": label,
                    "chosen": label == chosen}
            fn, twice = chain(call, args.calls), chain(call, 2 * args.calls)
            try:
                if args.deviceless:
                    sds = lambda s, d: jax.ShapeDtypeStruct(s, d,
                                                            sharding=place)
                    fn.lower(sds((B * H, G, D), dt), sds((B * H, S, D), dt),
                             sds((B * H, S, D), dt),
                             sds((B,), jnp.int32)).compile()
                    line["compiles"] = True
                else:
                    key = jax.random.PRNGKey(args.seed)
                    q, k, v = (jax.random.normal(kk, s, jnp.float32).astype(
                        dt) for kk, s in zip(jax.random.split(key, 3), (
                            (B * H, G, D), (B * H, S, D), (B * H, S, D))))
                    for kind in ("ones", "mix", "full"):
                        n = jnp.asarray(lengths_of(kind, B, S, args.seed))
                        best = [float("inf")] * 2
                        for i, f in enumerate((fn, twice)):
                            f(q, k, v, n).block_until_ready()
                            for _ in range(args.reps):
                                t0 = time.perf_counter()
                                f(q, k, v, n).block_until_ready()
                                best[i] = min(best[i],
                                              time.perf_counter() - t0)
                        line[kind + "_ms_a_call"] = (
                            1e3 * (best[1] - best[0]) / args.calls)
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
