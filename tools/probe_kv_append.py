#!/usr/bin/env python3
"""What the decode step's K/V append costs at GPT-2's geometry, by the form
that writes it (PERF.md section 6, PRs 32, 33, 34 and 45).

    chiprun -- python3 tools/probe_kv_append.py [--layers 12] [--attend]
    python3 tools/probe_kv_append.py --deviceless        # compiles only

The serving cell's caches (``f32[64,12,1024,64]``, K and V of ``--layers``
layers, stored rows in lanes: ``kernels.rows_minor``) are carried through a
scan of decode steps as ``run_chained`` carries them, donated, every step
appending one row a sequence to each. The forms:

* ``loop``: one ``fori_loop`` iteration a sequence, the old column read,
  selected by the slot mask and written by ``dynamic_update_slice`` in the
  rows-minor view (the library's form until PR 34; kept here, where the
  library has dropped it, so the comparison can be repeated);
* ``kernel``: ``kernels.kv_append``, one Pallas call a cache that aliases
  it, a grid step a sequence (the library's form for a chunk of rows);
* ``fused`` (with ``--attend``): no append call at all, the decode kernel
  merges the column into the last live block it fetches and copies that
  block back itself (``flash_attention_decode(append=...)``: the library's
  form for a step of one row since PR 45).

``--attend`` runs the decode kernel on the appended caches too, a whole
attention layer of a decode step. A step is timed as the wall time of a
jitted scan of twice ``--steps`` steps less that of ``--steps`` (what the
program costs around its steps cancels), each the best of ``--reps`` runs;
on the chip only (``--deviceless`` compiles every form for a v5e it does
not have, prints the compiler's ``temp`` bytes, and times nothing). One
JSON line per form, and all of them in ``chiprun_out/probe_kv_append.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import flash_attention_decode, kv_append

B, H, S, D = 64, 12, 1024, 64       # gpt2-base-serve: slots, heads, rows, D
PAGE = 128


def column_loop(cache, new, positions, mask):
    """PR 32's append on ``cache`` [B, H, D, S]: ``new`` [B, H, 1, D]."""
    cols = new.swapaxes(2, 3)
    keep = mask.reshape(B) > 0

    def one(b, c):
        start = [b, jnp.int32(0), jnp.int32(0), positions[b]]
        n = jnp.where(keep[b], jax.lax.dynamic_index_in_dim(cols, b, 0),
                      jax.lax.dynamic_slice(c, start, (1, H, D, 1)))
        return jax.lax.dynamic_update_slice(c, n, start)

    return jax.lax.fori_loop(0, B, one, cache)


FORMS = {
    "loop": column_loop,
    "kernel": kv_append,
    "fused": None,          # the decode kernel appends: --attend only
}


def chunk_of(form: str, steps: int, attend: bool):
    """``steps`` decode steps over every layer's K and V cache (logical
    shape, as the program declares them), positions advancing."""
    append = FORMS[form]
    fused = append is None

    def layer(ck, cv, q, new, pos, mask):
        at = jnp.minimum(pos, S - 1)
        if not fused:
            ck, cv = (append(c.swapaxes(2, 3), new, at, mask).swapaxes(2, 3)
                      for c in (ck, cv))
        if attend:
            o = flash_attention_decode(
                q.reshape(B * H, 1, D), ck.reshape(B * H, S, D),
                cv.reshape(B * H, S, D), jnp.minimum(pos + 1, S),
                num_heads=H, page_size=PAGE,
                append=(new, new, mask) if fused else None)
            if fused:
                o, ck, cv = o[0], o[1].reshape(ck.shape), o[2].reshape(
                    cv.shape)
            q = q + (o * 0).reshape(q.shape)
        return ck, cv, q

    def chunk(caches, q, new, pos, mask):
        def body(carry, _):
            caches, pos, q = carry
            out = []
            for ck, cv in zip(caches[::2], caches[1::2]):
                ck, cv, q = layer(ck, cv, q, new, pos, mask)
                out += [ck, cv]
            return (out, pos + 1, q), None
        return jax.lax.scan(body, (caches, pos, q), None, length=steps)[0]

    return jax.jit(chunk, donate_argnums=(0,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every form for a v5e, time nothing")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--attend", action="store_true",
                    help="the decode kernel after each layer's appends")
    ap.add_argument("--form", choices=sorted(FORMS), action="append")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/probe_kv_append.json")
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
        print("probe_kv_append: no TPU here; a time comes from the chip "
              "(--deviceless compiles without one)", file=sys.stderr)
        return 2

    rng = np.random.default_rng(args.seed)
    # the decode-saturated mix: prompts 32-128, answers 64-192, a sequence
    # seen at a uniformly drawn point of its answer; two slots masked out
    prompt, answer = rng.integers(32, 129, B), rng.integers(64, 193, B)
    pos0 = (prompt + rng.integers(0, answer)).astype(np.int32)
    mask0 = np.ones((B, 1), np.float32)
    mask0[[5, 40]] = 0
    n = 2 * args.layers
    results = []
    for form in args.form or sorted(FORMS):
        if FORMS[form] is None and not args.attend:
            continue        # nothing appends where nothing attends
        line = {"form": form, "layers": args.layers, "attend": args.attend,
                "appends_a_step": n * B}
        if args.deviceless:
            row = sds((B, H, 1, D), jnp.float32)
            c = chunk_of(form, args.steps, args.attend).lower(
                [sds((B, H, S, D), jnp.float32)] * n, row, row,
                sds((B,), jnp.int32), sds((B, 1), jnp.float32)).compile()
            line["temp_bytes"] = c.memory_analysis().temp_size_in_bytes
            line["custom_calls"] = c.as_text().count("tpu_custom_call")
        else:
            walls = {}
            for steps in (args.steps, 2 * args.steps):
                fn = chunk_of(form, steps, args.attend)
                caches = [jax.random.normal(jax.random.key(i), (B, H, S, D),
                                            jnp.float32) for i in range(n)]
                q = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
                new = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
                pos, mask = jnp.asarray(pos0), jnp.asarray(mask0)
                best = None
                for _ in range(args.reps + 1):      # the first one compiles
                    t0 = time.perf_counter()
                    caches, _, _ = fn(caches, q, new, pos, mask)
                    jax.block_until_ready(caches)
                    took = time.perf_counter() - t0
                    best = took if best is None else min(best, took)
                walls[steps] = best
                del caches
            step = (walls[2 * args.steps] - walls[args.steps]) / args.steps
            line.update(step_ms=1e3 * step,
                        append_us=1e6 * step / (n * B) if not args.attend
                        else None,
                        device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)
        results.append(line)
    if not args.deviceless:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for line in results:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
