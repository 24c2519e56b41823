#!/usr/bin/env python3
"""Does ``fused_multihead_attention_grad`` regenerate the forward's dropout
mask when it rides the saved residuals (PERF.md, PR 30)?

    chiprun -- python3 tools/probe_attention_dropout.py

The in-kernel dropout draws from the TPU's own generator, which has no
interpret-mode lowering: only the chip can show this. On the BERT-base
shapes (``f32[32,12,512,64]``, a padding bias, rate 0.1 as published) two
programs are built alike but for the op's ``SoftmaxLse`` output: with it
the gradient op calls the two backward kernels on the forward's ``Out``
and log-sum-exp; without it (a program from before PR 30) it
differentiates the forward rule, which runs the forward kernel again.
Each runs once through a fresh ``Executor`` with the same
``random_seed``, so both see the same key and op uid. The gradients must
be equal bit for bit; the same holds at rate 0, and the two rates must
differ (a mask was drawn at all). Then each program's step is timed
(``--steps`` dispatches after two warm ones, fetch of one scalar).
One JSON line, also in ``chiprun_out/probe_attention_dropout.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

import paddle_tpu as fluid
import paddle_tpu.unique_name as un

B, H, S, D = 32, 12, 512, 64
ATTN_GRAD = "fused_multihead_attention_grad"


def build(rate: float, keep_lse: bool):
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q, k, v = (fluid.layers.data(n, shape=[H, S, D], dtype="float32")
                       for n in "qkv")
            for t in (q, k, v):
                t.stop_gradient = False
            m = fluid.layers.data("m", shape=[S], dtype="float32")
            out = fluid.layers.fused_multihead_attention(
                q, k, v, bias_qk=m, attn_dropout=rate)
            if not keep_lse:
                del main.global_block.ops[-1].outputs["SoftmaxLse"]
            loss = fluid.layers.mean(fluid.layers.tanh(out))
            grads = fluid.backward.calc_gradient([loss], [q, k, v])
            total = fluid.layers.sums(
                [fluid.layers.reduce_sum(g) for g in grads])
    main.random_seed = 7
    return main, [out.name] + [g.name for g in grads], total.name


def routes_of(program) -> dict:
    from paddle_tpu import monitor

    fam = monitor.get_registry().get("kernel_route_total")
    return {f"{lab['op']}:{lab['route']}": int(c.value)
            for lab, c in (fam.children() if fam else ())
            if lab["program"] == str(program._serial)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    a = ap.parse_args()
    rng = np.random.default_rng(11)
    feed = {n: rng.standard_normal((B, H, S, D), np.float32) for n in "qkv"}
    feed["m"] = np.where(rng.random((B, S)) > 0.2, 0.0,
                         -10000.0).astype(np.float32)
    result = {"shape": [B, H, S, D], "device": None}
    kept = {}
    for rate in (0.1, 0.0):
        for keep_lse in (True, False):
            prog, fetches, total = build(rate, keep_lse)
            exe = fluid.Executor()          # fresh: the same key for both
            result["device"] = str(exe.place.jax_device())
            with fluid.scope_guard(fluid.Scope()):
                vals = [np.asarray(r) for r in
                        exe.run(prog, feed=feed, fetch_list=fetches)]
                walls = []
                for _ in range(a.steps + 2):
                    t0 = time.perf_counter()
                    exe.run(prog, feed=feed, fetch_list=[total])
                    walls.append(time.perf_counter() - t0)
            tag = f"rate{rate}:{'saved' if keep_lse else 'generic'}"
            kept[tag] = vals
            result[tag] = {
                "routes": routes_of(prog),
                "step_ms_median": 1e3 * float(np.median(walls[2:])),
                "finite": bool(all(np.isfinite(v).all() for v in vals))}
    for rate in (0.1, 0.0):
        a_, b_ = kept[f"rate{rate}:saved"], kept[f"rate{rate}:generic"]
        result[f"rate{rate}:bit_equal"] = bool(all(
            np.array_equal(x, y) for x, y in zip(a_, b_)))
        result[f"rate{rate}:max_abs_diff"] = float(max(
            np.abs(x - y).max() for x, y in zip(a_, b_)))
    result["mask_drawn"] = bool(not np.array_equal(
        kept["rate0.1:saved"][0], kept["rate0.0:saved"][0]))
    result["ok"] = bool(
        result["rate0.1:bit_equal"] and result["rate0.0:bit_equal"]
        and result["mask_drawn"]
        and all(result[t]["finite"] for t in kept)
        # a program is lowered twice here (two fetch lists): routes, not
        # counts
        and all({r for r in result[t]["routes"] if r.startswith(ATTN_GRAD)}
                == {f"{ATTN_GRAD}:" + ("pallas" if t.endswith("saved")
                                       else "primitive")} for t in kept))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_attention_dropout.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
