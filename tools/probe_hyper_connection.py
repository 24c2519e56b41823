#!/usr/bin/env python3
"""What a hyper-connection costs a sublayer, as one Pallas kernel a side
or as the equations in ``jax.numpy`` (PERF.md section 6, PR 52).

    chiprun -- python3 tools/probe_hyper_connection.py [--rows 256,1024]
    chiprun -- python3 tools/probe_hyper_connection.py --cell-generic -- <run.py arguments>

The read and the write of a four-stream residual path of 3,584
(``kernels/hyper_connection.py``; Xing4.0-29B-A4B's widths) over ``--rows``
token rows (256: a decode step's slots; 768-1,536: a prefill's bucket), as
``kernel`` (``hc_read`` / ``hc_write``) and as ``primitive`` (the op's
route off the TPU and the kernels' oracle, jitted alone: what XLA makes of
the equations). A call is timed as the wall time of a jitted chain of
twice ``--steps`` read-and-write pairs less that of ``--steps`` (the write
feeds the next read, as in the model; what the program costs around its
steps cancels), the best of ``--reps`` runs, beside the least time for the
pair's bytes at 819 GB/s (the streams read once for the read, read and
written once for the write beside the sublayer's output, the projection
once: ``benchmark/kernel_costs_mhc.py``'s count) and the largest
difference between the two forms' results. One JSON line per form and row
count, all of them in ``chiprun_out/probe_hyper_connection.json``.

``--cell-generic`` runs ``benchmark/run.py`` with what follows ``--`` and
the ops held to their primitive route: the cell as it would run without
the kernels.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N, C = 4, 3584
HBM_BYTES_PER_S = 819e9


def _chain(read, write, steps):
    import jax

    def run(x, y, proj, alpha, bias):
        def body(x, _):
            u, coef, _ = read(x, proj, alpha, bias)
            m = N * (N + 2)
            # the sublayer: something of the read's output, as wide
            return write(x, y + u, coef[:, N:2 * N], coef[:, 2 * N:m]), None
        return jax.lax.scan(body, x, None, length=steps)[0]
    return jax.jit(run)


def _best(fn, args, reps):
    fn(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return min(times)


def probe(rows_list, steps, reps):
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import hyper_connection as hc

    out = []
    forms = {
        "kernel": (functools.partial(hc.hc_read, n=N),
                   functools.partial(hc.hc_write, n=N)),
        "primitive": (functools.partial(hc.hc_read_reference, n=N),
                      functools.partial(hc.hc_write_reference, n=N))}
    for rows in rows_list:
        rng = np.random.default_rng(rows)
        m = N * (N + 2)
        bias = rng.uniform(-1, 1, m)
        bias[2 * N:] += 4 * np.eye(N).ravel()
        args = [jnp.asarray(a, jnp.float32) for a in (
            rng.normal(size=(rows, N * C)), rng.normal(size=(rows, C)),
            rng.normal(size=(m, N * C)) * 0.02, rng.uniform(0.5, 1.5, 3),
            bias)]
        results = {}
        for name, (read, write) in forms.items():
            one = _chain(read, write, 1)(*args)
            t1 = _best(_chain(read, write, steps), args, reps)
            t2 = _best(_chain(read, write, 2 * steps), args, reps)
            results[name] = np.asarray(one)
            moved = rows * 4.0 * (3 * N * C + C) + 4.0 * m * N * C
            line = {"form": name, "rows": rows,
                    "pair_us": 1e6 * (t2 - t1) / steps,
                    "least_us": 1e6 * moved / HBM_BYTES_PER_S,
                    "device": jax.devices()[0].device_kind}
            line["share_of_819GBps_pct"] = 100 * line["least_us"] \
                / line["pair_us"]
            out.append(line)
        diff = float(np.abs(results["kernel"] - results["primitive"]).max())
        for line in out[-2:]:
            line["max_abs_diff_between_forms"] = diff
            print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "probe_hyper_connection.json"), "w") as f:
        json.dump(out, f, indent=1)


def cell_generic(argv):
    from paddle_tpu.kernels import hyper_connection as kernels

    kernels.supports = lambda rows, n, C: False
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import run

    return run.main(argv)


def main():
    argv = sys.argv[1:]
    if "--cell-generic" in argv:
        return cell_generic(argv[argv.index("--") + 1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="256,1024")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args(argv)
    probe([int(r) for r in a.rows.split(",")], a.steps, a.reps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
