#!/usr/bin/env python3
"""What the decode step's K/V append costs, by the form that writes it and
the way the cache lies (PERF.md section 6, PRs 32, 33, 34, 45 and 48).

    chiprun -- python3 tools/probe_kv_append.py [--shape NAME] [--attend]
    python3 tools/probe_kv_append.py --shape NAME --deviceless   # compiles

A cell's caches are carried through a scan of decode steps as
``run_chained`` carries them, donated, every step appending a sequence's
new rows to each. ``--shape`` names the cell whose caches they are:

* ``gpt2`` (``f32[64,12,1024,64]``, K and V of ``--layers`` layers): heads
  of 64 lie rows in lanes (``kernels.rows_minor``), a row is a column. The
  forms: ``loop``, one ``fori_loop`` iteration a sequence, the old column
  read, selected by the slot mask and written by ``dynamic_update_slice``
  in the rows-minor view (the library's form until PR 34); ``kernel``,
  ``kernels.kv_append``, one Pallas call a cache that aliases it (the
  library's form for a chunk of rows); ``fused`` (with ``--attend``): no
  append call, the decode kernel merges the column into the last live
  block it fetches (``flash_attention_decode(append=...)``, the library's
  form for a step of one row since PR 45).
* ``mimo-v2-flash``, ``command-a-plus``, ``sdar`` (bf16, heads of 128 and
  256 lanes, shapes from ``benchmark/configs/``): the caches lie as
  declared, a row is whole lane tiles. The forms: ``loop``, one
  ``dynamic_slice``, ``select`` and ``dynamic_update_slice`` a sequence
  and row (``kernels.paged_kv_append`` a row: the library's form for a
  step of up to 8 rows until PR 48); ``slice`` (steps of several rows),
  the same loop with the chunk's rows as one slice a sequence (SDAR's
  block of 4 until PR 48, and the prefill's bulk write still); ``rows``,
  ONE scatter a cache with one index a (slot, head, row) and a row its
  window: ``kernels.paged_kv_append_rows``, the library's form for EVERY
  decode-step append on such a cache since PR 48 (the step of one row, the
  verify chunk, the chunked-prefill slice, SDAR's block), 6 times faster
  than ``loop`` at one row a step and 2.8 times faster than ``slice`` at
  SDAR's 4 (PERF.md section 6, PR 48); ``block``, one scatter with one
  index a (slot, head) and the chunk's ``C`` rows its window, which the
  TPU compiler expands into a ``while`` over its indices, 5 to 50 times
  slower than ``rows`` (kept here, the library never had it). With
  ``--deviceless`` also the two forms that make the TPU compiler re-lay
  the whole cache (its heads move next to its lanes: a copy of the cache
  into the chunk and one out, ``temp`` one whole cache): ``heads_window``
  (one index a slot, the heads inside the update window) and ``vmapped``
  (``jax.vmap`` over the slots of a scatter on ``[H, S, D]``, the
  library's form past 8 rows until PR 48).

``--attend`` (``gpt2`` only) runs the decode kernel on the appended caches
too. A step is timed as the wall time of a jitted scan of twice ``--steps``
steps less that of ``--steps`` (what the program costs around its steps
cancels), each the best of ``--reps`` runs; on the chip only
(``--deviceless`` compiles every form for a v5e it does not have, prints
the compiler's ``temp`` bytes, its ``scatter``s and whether the optimized
HLO holds a ``copy`` of a cache, and times nothing). One JSON line per
form, and all of them in ``chiprun_out/probe_kv_append.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import (flash_attention_decode, kv_append,
                                paged_kv_append, paged_kv_append_rows)

PAGE = 128

# name: dtype, rows a step, positions drawn below, and the caches a step
# appends to as (shape [B, H, S, D], how many, ring)
SHAPES = {
    # gpt2-base-serve: 12 layers' K and V (``--layers``), rows in lanes
    "gpt2": (jnp.float32, 1, 320, [((64, 12, 1024, 64), None, False)]),
    # mimo-v2-flash-ep16-serve: two full layers' keys (192 in 256 lanes)
    # and values, five window layers' rings
    "mimo-v2-flash": (jnp.bfloat16, 1, 4096, [
        ((128, 4, 4096, 256), 2, False), ((128, 4, 4096, 128), 2, False),
        ((128, 8, 128, 256), 5, True), ((128, 8, 128, 128), 5, True)]),
    # command-a-plus-ep8-serve: a full layer and three sliding ones whose
    # window (4,096) is past the cache: rings of all 1,024 rows
    "command-a-plus": (jnp.bfloat16, 1, 1024, [
        ((64, 8, 1024, 128), 2, False), ((64, 8, 1024, 128), 6, True)]),
    # sdar-30b-a3b-serve: six layers, a block of 4 rows a step
    "sdar": (jnp.bfloat16, 4, 2048, [((64, 4, 2048, 128), 12, False)]),
}


def column_loop(cache, new, positions, mask):
    """PR 32's append on ``cache`` [B, H, D, S]: ``new`` [B, H, 1, D]."""
    B, H, D, _ = cache.shape
    cols = new.swapaxes(2, 3)
    keep = mask.reshape(B) > 0

    def one(b, c):
        start = [b, jnp.int32(0), jnp.int32(0), positions[b]]
        n = jnp.where(keep[b], jax.lax.dynamic_index_in_dim(cols, b, 0),
                      jax.lax.dynamic_slice(c, start, (1, H, D, 1)))
        return jax.lax.dynamic_update_slice(c, n, start)

    return jax.lax.fori_loop(0, B, one, cache)


def _row_index(cache, new, positions, mask, ring):
    """[B, C]: where row ``i`` of a sequence lands, ``S`` (out of range,
    dropped) for a sequence whose mask is 0."""
    S, C = cache.shape[2], new.shape[2]
    at = positions[:, None] + jnp.arange(C, dtype=jnp.int32)
    at = at % S if ring else jnp.minimum(at, S - 1)
    return jnp.where(mask.reshape(-1, 1) > 0, at, S)


def row_loop(cache, new, positions, mask, ring):
    """The library's form until PR 48: ``paged_kv_append`` a row."""
    S = cache.shape[2]
    for i in range(new.shape[2]):
        at = positions + i
        cache = paged_kv_append(
            cache, new[:, :, i:i + 1],
            at % S if ring else jnp.minimum(at, S - 1), mask)
    return cache


def heads_window(cache, new, positions, mask, ring):
    """One index a slot, its heads inside the update window (re-lays)."""
    B = cache.shape[0]
    at = _row_index(cache, new, positions, mask, ring)
    for i in range(new.shape[2]):
        cache = cache.at[jnp.arange(B), :, at[:, i]].set(new[:, :, i],
                                                         mode="drop")
    return cache


def vmapped(cache, new, positions, mask, ring):
    """The library's form past 8 rows until PR 48 (re-lays)."""
    at = _row_index(cache, new, positions, mask, ring)
    return jax.vmap(lambda c, n, r: c.at[..., r, :].set(n, mode="drop"))(
        cache, new, at)


def block(cache, new, positions, mask, ring):
    """One index a (slot, head), the chunk's ``C x D`` rows its window, on
    ``[B x H, S, D]``; a block lies inside the cache or is dropped whole
    (one row wraps). The TPU compiler expands it into a ``while`` over its
    indices."""
    B, H, S, D = cache.shape
    at = _row_index(cache, new, positions, mask, ring)[:, :1]
    idx = jnp.concatenate([jnp.arange(B * H, dtype=jnp.int32)[:, None],
                           jnp.repeat(at, H, axis=0)], axis=1)
    return jax.lax.scatter(
        cache.reshape(B * H, S, D), idx, new.reshape(B * H, -1, D),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0, 1)),
        unique_indices=True, mode=jax.lax.GatherScatterMode.FILL_OR_DROP
    ).reshape(cache.shape)


ROW_FORMS = {
    "loop": row_loop,
    # a chunk as one slice a sequence (SDAR's block until PR 48; the
    # prefill's bulk write): its start clamps as a whole, it does not wrap
    "slice": lambda c, n, p, m, ring: paged_kv_append(c, n, p, m),
    "rows": lambda c, n, p, m, ring: paged_kv_append_rows(c, n, p, m,
                                                          ring=ring),
    "block": block,
    "heads_window": heads_window,
    "vmapped": vmapped,
}
RE_LAYS = ("heads_window", "vmapped")
COLUMN_FORMS = {"loop": column_loop, "kernel": kv_append, "fused": None}


def column_chunk(form: str, steps: int, attend: bool, shape):
    """``steps`` decode steps over every layer's K and V cache of GPT-2's
    geometry (logical shape, as the program declares them), positions
    advancing."""
    B, H, S, D = shape
    append = COLUMN_FORMS[form]
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

    def chunk(caches, q, news, pos, mask):
        def body(carry, _):
            caches, pos, q = carry
            out = []
            for ck, cv in zip(caches[::2], caches[1::2]):
                ck, cv, q = layer(ck, cv, q, news[0], pos, mask)
                out += [ck, cv]
            return (out, pos + 1, q), None
        return jax.lax.scan(body, (caches, pos, q), None, length=steps)[0]

    return jax.jit(chunk, donate_argnums=(0,))


def row_chunk(form: str, steps: int, rings, rows: int):
    """``steps`` decode steps of ``rows`` rows over caches that lie as
    declared (``rings[i]``: cache ``i`` is a ring), positions advancing."""
    append = ROW_FORMS[form]

    def chunk(caches, q, news, pos, mask):
        def body(carry, _):
            caches, pos, q = carry
            out = [append(c, n, pos, mask, ring)
                   for c, n, ring in zip(caches, news, rings)]
            return (out, pos + rows, q), None
        return jax.lax.scan(body, (caches, pos, q), None, length=steps)[0]

    return jax.jit(chunk, donate_argnums=(0,))


def _cache_copies(text: str, shapes) -> int:
    """``copy`` instructions that produce a whole cache, however its
    dimensions are ordered in the result."""
    n = 0
    for B, H, S, D in set(shapes):
        dims = "|".join({f"{B},{H},{S},{D}", f"{B},{S},{H},{D}",
                         f"{B * H},{S},{D}", f"{B},{H},{D},{S}"})
        n += len(re.findall(r"copy[.\w]* = \w+\[(?:" + dims + r")\]", text))
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--deviceless", action="store_true",
                    help="compile every form for a v5e, time nothing")
    ap.add_argument("--shape", choices=sorted(SHAPES), default="gpt2")
    ap.add_argument("--layers", type=int, default=12,
                    help="gpt2: layers whose K and V caches a step appends")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--attend", action="store_true",
                    help="gpt2: the decode kernel after a layer's appends")
    ap.add_argument("--form", action="append")
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

    dt, rows, reach, groups = SHAPES[args.shape]
    columns = args.shape == "gpt2"
    if args.attend and not columns:
        ap.error("--attend: the gpt2 shape only")
    forms = COLUMN_FORMS if columns else ROW_FORMS
    shapes, rings = [], []
    for shape, count, ring in groups:
        count = 2 * args.layers if count is None else count
        shapes += [shape] * count
        rings += [ring] * count
    B = shapes[0][0]
    # one row a cache shape: caches of one shape are written the same rows
    new_shapes = [(s[0], s[1], rows, s[3]) for s in shapes]

    rng = np.random.default_rng(args.seed)
    # a sequence seen at a uniformly drawn point of its life (GPT-2: the
    # decode-saturated mix, prompts 32-128 and answers 64-192), with room
    # for the probe's steps; two slots masked out
    if columns:
        prompt, answer = rng.integers(32, 129, B), rng.integers(64, 193, B)
        pos0 = (prompt + rng.integers(0, answer)).astype(np.int32)
    else:
        pos0 = (rows * rng.integers(
            0, (reach - 2 * rows * args.steps) // rows, B)).astype(np.int32)
    mask0 = np.ones((B, 1), np.float32)
    mask0[[5, 40]] = 0
    qshape = (B, shapes[0][1], 1, shapes[0][3])

    def chunk_of(form, steps):
        if columns:
            return column_chunk(form, steps, args.attend, shapes[0])
        return row_chunk(form, steps, rings, rows)

    results = []
    for form in args.form or sorted(forms):
        if form not in forms:
            ap.error(f"--form {form}: the {args.shape} shape has "
                     f"{sorted(forms)}")
        if columns and forms[form] is None and not args.attend:
            continue        # nothing appends where nothing attends
        if form in RE_LAYS and not args.deviceless:
            continue        # a whole cache of scratch a cache: compile only
        if form in ("block", "slice") and rows > 1 and any(rings):
            continue        # a block of several rows does not wrap
        if form == "slice" and rows == 1:
            continue        # the loop itself
        line = {"shape": args.shape, "form": form, "caches": len(shapes),
                "rows_a_step": rows, "attend": args.attend,
                "appends_a_step": len(shapes) * B}
        if args.deviceless:
            c = chunk_of(form, args.steps).lower(
                [sds(s, dt) for s in shapes], sds(qshape, dt),
                [sds(s, dt) for s in new_shapes],
                sds((B,), jnp.int32), sds((B, 1), jnp.float32)).compile()
            text = c.as_text()
            line["temp_bytes"] = c.memory_analysis().temp_size_in_bytes
            line["custom_calls"] = text.count("tpu_custom_call")
            line["scatters"] = len(re.findall(r" scatter\(", text))
            line["cache_copies"] = _cache_copies(text, shapes)
        else:
            walls = {}
            for steps in (args.steps, 2 * args.steps):
                fn = chunk_of(form, steps)
                caches = [jax.random.normal(jax.random.key(i), s, dt)
                          for i, s in enumerate(shapes)]
                q = jnp.asarray(rng.normal(size=qshape), dt)
                news = [jnp.asarray(rng.normal(size=s), dt)
                        for s in new_shapes]
                pos, mask = jnp.asarray(pos0), jnp.asarray(mask0)
                best = None
                for _ in range(args.reps + 1):      # the first one compiles
                    t0 = time.perf_counter()
                    caches, _, _ = fn(caches, q, news, pos, mask)
                    jax.block_until_ready(caches)
                    took = time.perf_counter() - t0
                    best = took if best is None else min(best, took)
                walls[steps] = best
                del caches
            step = (walls[2 * args.steps] - walls[args.steps]) / args.steps
            line.update(step_ms=1e3 * step,
                        append_us=1e6 * step / (len(shapes) * B)
                        if not args.attend else None,
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
