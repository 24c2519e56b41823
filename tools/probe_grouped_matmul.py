#!/usr/bin/env python3
"""What one call of the grouped expert matmul costs at a stored cell's
shapes, by what the router deals the held experts (PERF.md section 6, PR 44).

    chiprun -- python3 tools/probe_grouped_matmul.py [--shape sdar] \\
        [--counts even skew] [--parent DIR]
    python3 tools/probe_grouped_matmul.py --deviceless       # compiles only

A call is what ``ops/moe.py`` ``_grouped_held`` makes in one layer of one
decode forward: the gated gate-and-up product, then down, over a row buffer
laid out as the op lays it (each expert's rows padded to whole tiles of
``tm``, the buffer sized for every assignment local, the dead tiles
behind). ``--counts``: ``even`` deals ``--total`` assignments evenly,
``skew`` draws them from a log-normal whose busiest expert is ``--skew``
times the mean (the SDAR cell reads 5.2, ledger, PR 43), or a list
``12,0,85,...`` of one count a held expert. ``--blocks-up`` /
``--blocks-down`` ``tk,tn`` replace the kernel's own blocks (this tree's
kernel only). ``--parent DIR`` times the kernel of another commit unpacked
at DIR beside this one, in the same process on the same operands: the
parent's schedule is reached by checking the parent out, not by a switch.

A call is timed as the wall time of a jitted scan of twice ``--calls``
calls less that of ``--calls`` (what the program costs around its calls
cancels), each the best of ``--reps`` runs; the bytes are what the
benchmark's roofline counts (each hit expert's three matrices once,
``benchmark/kernel_costs.py`` ``expert_matmul_cost``) and the peak 819 GB/s.
On the chip only (``--deviceless`` compiles each call for a v5e it does not
have and times nothing). One JSON line per kernel and counts, and all of
them in ``chiprun_out/probe_grouped_matmul.json``.
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

from paddle_tpu.kernels import moe as this_moe
from paddle_tpu.ops.moe import expert_tile_rows

HBM_BYTES_PER_S = 819e9

# cell -> experts held, hidden, expert width, experts routed over, top k,
# rows of a decode forward, local assignments a call (ledger, PR 43)
SHAPES = {
    "sdar": (128, 2048, 768, 128, 8, 256, 2030),
    "granite": (36, 4096, 768, 72, 10, 64, 291),
    "glm": (8, 2048, 1536, 64, 4, 128, 64),
    "qwen3-next": (256, 2048, 512, 512, 10, 64, 320),
    "command-a-plus": (16, 4096, 4096, 128, 8, 64, 53),
}


def parent_module(root: str):
    """``kernels/moe.py`` of the tree unpacked at ``root``, beside this
    tree's package (its relative imports resolve here)."""
    path = os.path.join(root, "paddle_tpu", "kernels", "moe.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.kernels._probe_parent_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deal(kind: str, held: int, total: int, skew: float, seed: int):
    """One count a held expert."""
    if kind == "even":
        return np.full(held, total // held) + (np.arange(held)
                                               < total % held)
    if kind != "skew":
        counts = np.asarray([int(c) for c in kind.split(",")])
        if len(counts) != held:
            raise SystemExit(f"--counts: {len(counts)} counts for {held} "
                             f"held experts")
        return counts
    # the sigma whose busiest expert is ``skew`` times the mean, by halving
    draw = np.random.default_rng(seed).normal(size=held)
    lo, hi = 0.0, 4.0
    for _ in range(40):
        sigma = (lo + hi) / 2
        p = np.exp(sigma * draw)
        counts = np.floor(total * p / p.sum()).astype(int)
        lo, hi = ((sigma, hi) if counts.max() < skew * counts.mean()
                  else (lo, sigma))
    counts[np.argsort(-p)[:total - counts.sum()]] += 1
    return counts


def layout(counts, tm: int, assignments: int):
    """``tile_expert`` and ``n_valid`` of ``counts``, and the rows of the
    buffer, as ``_grouped_held`` has them."""
    held = len(counts)
    per = -(-counts // tm)
    tile_expert = np.repeat(np.arange(held), per)
    rows = -(-assignments // tm) * tm + held * tm
    dead = rows // tm - len(tile_expert)
    if dead < 0:
        raise SystemExit("more tiles than the op's row buffer holds")
    tile_expert = np.concatenate([tile_expert, np.full(dead, held - 1)])
    return tile_expert.astype(np.int32), int(per.sum()), rows


def chain(mod, tm: int, calls: int, which: str, blocks):
    """``calls`` calls in one program; each hands the next its ``n_valid``
    through a value the compiler cannot fold, so none is dropped."""
    def ffn(x, h0, wg, wu, wd, te, nv):
        kw = lambda b: {"blocks": b} if b else {}
        y = h = None
        if which != "down":
            h = mod.grouped_matmul(x, wg, te, nv, tm=tm, rhs2=wu,
                                   out_dtype=x.dtype, **kw(blocks[0]))
        if which != "up":
            y = mod.grouped_matmul(h0 if h is None else h, wd, te, nv,
                                   tm=tm, **kw(blocks[1]))
        return h if y is None else y

    def run(x, h0, wg, wu, wd, te, nv):
        def body(nv, _):
            y = ffn(x, h0, wg, wu, wd, te, nv)
            return nv - (y[0, 0] > 1e30).astype(jnp.int32), None
        return jax.lax.scan(body, nv, None, length=calls)[0]
    return jax.jit(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every call for a v5e, time nothing")
    ap.add_argument("--shape", choices=sorted(SHAPES), action="append")
    ap.add_argument("--counts", nargs="+", default=["even", "skew"])
    ap.add_argument("--total", type=int,
                    help="assignments dealt (default: the cell's measured "
                         "local assignments a call)")
    ap.add_argument("--skew", type=float, default=5.2)
    ap.add_argument("--parent", help="an unpacked other commit to time too")
    pair = lambda s: tuple(int(v) for v in s.split(","))
    ap.add_argument("--blocks-up", type=pair)
    ap.add_argument("--blocks-down", type=pair)
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/probe_grouped_matmul.json")
    args = ap.parse_args(argv)

    if args.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        place = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                     sharding=place)
    elif jax.devices()[0].platform != "tpu":
        print("probe_grouped_matmul: no TPU here; a time comes from the "
              "chip (--deviceless compiles without one)", file=sys.stderr)
        return 2

    kernels = {"this": this_moe}
    if args.parent:
        kernels["parent"] = parent_module(args.parent)
    bf = jnp.bfloat16
    results = []
    for shape in args.shape or ["sdar"]:
        held, H, F, routed, top_k, rows, measured = SHAPES[shape]
        assignments = rows * top_k
        tm = expert_tile_rows(rows, top_k, routed)
        for kind in args.counts:
            counts = deal(kind, held, args.total or measured, args.skew,
                          args.seed)
            tile_expert, live, M = layout(counts, tm, assignments)
            hit = int((counts > 0).sum())
            moved = hit * 3.0 * H * F * 2
            for label, mod in kernels.items():
                own = label == "this"
                blocks = ((args.blocks_up, args.blocks_down) if own
                          else (None, None))
                line = {
                    "shape": shape, "kernel": label,
                    "counts": kind if len(kind) < 12 else "list",
                    "assignments": int(counts.sum()), "tm": tm,
                    "live_tiles": live, "experts_hit": hit,
                    "tiles_per_hit_expert": live / max(hit, 1),
                    "max_over_mean": float(counts.max()
                                           / max(counts.mean(), 1e-9)),
                    "blocks_up": blocks[0] or mod.gmm_blocks(H, F),
                    "blocks_down": blocks[1] or mod.gmm_blocks(F, H),
                    "weight_bytes": moved}
                if args.deviceless:
                    chain(mod, tm, 2, "ffn", blocks).lower(
                        sds((M, H), bf), sds((M, F), bf),
                        sds((held, H, F), bf), sds((held, H, F), bf),
                        sds((held, F, H), bf), sds((M // tm,), jnp.int32),
                        sds((), jnp.int32)).compile()
                    line["compiled"] = True
                else:
                    line.update(time_calls(mod, tm, blocks, args, held, H,
                                           F, M, tile_expert, live))
                    line["weights_gb_per_s"] = moved / line["ffn_us"] / 1e3
                    line["hbm_share_pct"] = (100 * moved / HBM_BYTES_PER_S
                                             / (line["ffn_us"] * 1e-6))
                    line["device"] = jax.devices()[0].device_kind
                print(json.dumps(line), flush=True)
                results.append(line)
    if not args.deviceless:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for line in results:
                f.write(json.dumps(line) + "\n")
    return 0


def time_calls(mod, tm, blocks, args, held, H, F, M, tile_expert, live):
    """us a call of the pair and of each product alone."""
    bf = jnp.bfloat16
    key = lambda i: jax.random.fold_in(jax.random.key(args.seed), i)
    x = jax.random.normal(key(0), (M, H), bf)
    h0 = jax.random.normal(key(1), (M, F), bf)
    wg, wu = (jax.random.normal(key(i), (held, H, F), bf) * 0.02
              for i in (2, 3))
    wd = jax.random.normal(key(4), (held, F, H), bf) * 0.02
    te, nv = jnp.asarray(tile_expert), jnp.int32(live)
    out = {}
    for which in ("ffn", "up", "down"):
        walls = {}
        for calls in (args.calls, 2 * args.calls):
            fn = chain(mod, tm, calls, which, blocks)
            best = None
            for _ in range(args.reps + 1):          # the first one compiles
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x, h0, wg, wu, wd, te, nv))
                took = time.perf_counter() - t0
                best = took if best is None else min(best, took)
            walls[calls] = best
        out[f"{which}_us"] = 1e6 * (walls[2 * args.calls]
                                    - walls[args.calls]) / args.calls
    return out


if __name__ == "__main__":
    sys.exit(main())
