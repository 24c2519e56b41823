#!/usr/bin/env python
"""Executor runtime-metrics report + CI recompile gate (the reporting face
of ``paddle_tpu.monitor``, sibling of tools/mem_report.py).

Runs a built-in model suite through the executor (run / run_chained /
inference-clone paths), collects the monitor's counters per scenario, and
dumps the full metrics snapshot (registry + compile/recompile events) as a
JSON artifact for CI.

Usage:
  python tools/metrics_report.py
      Run the suite, print the per-scenario metric summary.
  python tools/metrics_report.py --json report.json
      Also write the machine-readable artifact (the CI companion of
      ci_mem_report.json).
  python tools/metrics_report.py --check
      CI gate: exit 1 if any scenario misses its expected compile/cache
      behaviour or if recompiles exceed --recompile-threshold (default 0 —
      the suite is steady-state by construction, ANY recompile is a
      regression in the cache keying or the lowering).
  python tools/metrics_report.py --check --force-recompile 3
      Negative control: appends a scenario that alternates feed shapes to
      force 3 recompiles; the gate must then FAIL (non-zero exit). CI runs
      this once to prove the tripwire trips.

Metric semantics: docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu import monitor  # noqa: E402

# the (metric, labels) scalars each scenario reports as deltas
_TRACKED = {
    "run_hits": ("executor_cache_lookups_total",
                 {"path": "run", "result": "hit"}),
    "run_misses": ("executor_cache_lookups_total",
                   {"path": "run", "result": "miss"}),
    "run_compiles": ("executor_compiles_total", {"path": "run"}),
    "chained_hits": ("executor_cache_lookups_total",
                     {"path": "chained", "result": "hit"}),
    "chained_misses": ("executor_cache_lookups_total",
                       {"path": "chained", "result": "miss"}),
    "chained_compiles": ("executor_compiles_total", {"path": "chained"}),
    "chained_iterations": ("executor_chained_iterations_total", {}),
    "donated_buffers": ("executor_donated_buffers_total", {}),
    "kept_buffers": ("executor_kept_buffers_total", {}),
    "feed_bytes": ("executor_feed_bytes_total", {}),
    "fetch_bytes": ("executor_fetch_bytes_total", {}),
}


def _counters_now() -> dict:
    vals = {}
    for key, (name, labels) in _TRACKED.items():
        v = monitor.metric_value(name, default=0.0, **labels)
        vals[key] = float(v)
    vals["recompiles"] = float(monitor.recompile_count())
    return vals


def _delta(before: dict, after: dict) -> dict:
    return {k: int(after[k] - before[k]) for k in after}


def _build_regression():
    x = fluid.layers.data("x", shape=[13], dtype="float32")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, 1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.02).minimize(loss)
    return loss


def _feed(batch=8, dtype=np.float32):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 13).astype(dtype),
            "y": rng.rand(batch, 1).astype(dtype)}


def scenario_run_repeat():
    """Two exe.run of the same program/feed: exactly 1 compile + 1 cache
    hit (the acceptance bar for the compile cache)."""
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_regression()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        feed = _feed()
        with fluid.scope_guard(scope):
            exe.run(startup)                     # outside the window
            before = _counters_now()
            exe.run(main, feed=feed, fetch_list=[loss])
            exe.run(main, feed=feed, fetch_list=[loss])
    got = _delta(before, _counters_now())
    expect = {"run_compiles": 1, "run_hits": 1, "run_misses": 1,
              "recompiles": 0}
    return {"name": "run_repeat", "metrics": got, "expect": expect}


def scenario_chained_kept_state():
    """run_chained twice with a fetched param: 1 chained compile + 1 hit,
    donated AND kept buffers both reported (the PR 2 kept-state split)."""
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_regression()
            param = next(v.name for v in main.global_block.vars.values()
                         if type(v).__name__ == "Parameter"
                         and v.name.endswith(".w_0"))
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        feed = _feed()
        with fluid.scope_guard(scope):
            exe.run(startup)
            before = _counters_now()
            exe.run_chained(main, feed=feed, fetch_list=[loss, param],
                            steps=3)
            exe.run_chained(main, feed=feed, fetch_list=[loss, param],
                            steps=3)
    got = _delta(before, _counters_now())
    expect = {"chained_compiles": 1, "chained_hits": 1,
              "chained_misses": 1, "chained_iterations": 6,
              "recompiles": 0}
    ok_extra = got["donated_buffers"] > 0 and got["kept_buffers"] > 0
    return {"name": "chained_kept_state", "metrics": got, "expect": expect,
            "extra_ok": ok_extra,
            "extra_why": "donated>0 and kept>0 (fetched param is "
                         "donation-unsafe but threads the carry)"}


def scenario_infer_clone():
    """Inference clone run twice: its own single compile, then cache."""
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[13], dtype="float32")
            pred = fluid.layers.fc(x, 4, act="softmax")
        infer = main.clone(for_test=True)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        feed = {"x": np.random.RandomState(1).rand(8, 13)
                .astype(np.float32)}
        with fluid.scope_guard(scope):
            exe.run(startup)
            before = _counters_now()
            exe.run(infer, feed=feed, fetch_list=[pred.name])
            exe.run(infer, feed=feed, fetch_list=[pred.name])
    got = _delta(before, _counters_now())
    expect = {"run_compiles": 1, "run_hits": 1, "run_misses": 1,
              "recompiles": 0}
    return {"name": "infer_clone_repeat", "metrics": got, "expect": expect}


def _family_sum(name: str) -> float:
    """A labelled counter family summed over its children."""
    fam = monitor.get_registry().get(name)
    return float(sum(child.snapshot() for _, child in fam.children())
                 ) if fam else 0.0


def scenario_routed_experts():
    """A sparse-expert decoder (SDAR, CI-sized: top 2 of 8 experts, all
    held) served three requests: what the expert op counted reaches the
    ``moe_expert_*`` families, and a live tile of the grouped matmul is
    counted for every hit expert at the least (``expert_tiles`` over
    ``experts_hit`` = the tiles that rode one fetch of an expert's
    weights; anything dropped is a bug)."""
    import paddle_tpu.unique_name as un
    from paddle_tpu import serving
    from paddle_tpu.models.sdar_moe import (SdarMoeConfig,
                                            build_sdar_moe_generative)

    tracked = {"expert_tiles": "moe_expert_tiles_total",
               "experts_hit": "moe_experts_hit_total",
               "expert_tokens": "moe_expert_tokens_total",
               "dropped_assignments": "moe_dropped_assignments_total"}
    with un.guard():
        net = build_sdar_moe_generative(
            SdarMoeConfig.tiny(dtype="float32"), batch_slots=2, max_seq=32,
            page_size=8, prompt_buckets=(16,), prefill_rows=1)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=2, queue_depth=8,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=2))
    eng.warm_up()
    now = lambda: dict({k: _family_sum(f) for k, f in tracked.items()},
                       recompiles=float(monitor.recompile_count()))
    before = now()
    rng = np.random.RandomState(0)
    with eng:
        for fut in [eng.submit(rng.randint(1, 100, n), max_new_tokens=4)
                    for n in (5, 9, 12)]:
            fut.result()
    got = _delta(before, now())
    return {"name": "routed_experts", "metrics": got,
            "expect": {"dropped_assignments": 0, "recompiles": 0},
            "extra_ok": got["expert_tokens"] >= got["expert_tiles"]
            >= got["experts_hit"] > 0}


def scenario_forced_recompile(n: int):
    """Negative control: grow the feed batch size every run so each run
    after the first misses the cache with a fresh signature — n recompiles,
    each diagnosed with changed=('feed_signature',). The --check gate must
    fail on this. (Alternating two sizes would NOT recompile: both steps
    stay cached — exactly the bucketed-shape advice in
    docs/OBSERVABILITY.md.)"""
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            loss = _build_regression()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            before = _counters_now()
            for i in range(n + 1):
                exe.run(main, feed=_feed(batch=8 * (i + 1)),
                        fetch_list=[loss])
    got = _delta(before, _counters_now())
    evs = monitor.recompile_events()
    return {"name": f"forced_recompile_x{n}", "metrics": got,
            "expect": {"recompiles": n}, "forced": True,
            "diagnostic": (evs[-1].to_dict() if evs else None)}


SCENARIOS = [scenario_run_repeat, scenario_chained_kept_state,
             scenario_infer_clone, scenario_routed_experts]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on missed expectations or recompiles "
                         "above --recompile-threshold (the CI gate)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the metrics snapshot artifact as JSON")
    ap.add_argument("--recompile-threshold", type=int, default=0,
                    help="max tolerated recompiles across the suite "
                         "(default 0)")
    ap.add_argument("--force-recompile", type=int, default=0, metavar="N",
                    help="append a scenario that forces N recompiles "
                         "(negative control: --check must then fail)")
    args = ap.parse_args(argv)

    monitor.reset()
    results = [fn() for fn in SCENARIOS]
    if args.force_recompile > 0:
        results.append(scenario_forced_recompile(args.force_recompile))

    suite_ok = True
    for r in results:
        missed = {k: (v, r["metrics"].get(k))
                  for k, v in r["expect"].items()
                  if r["metrics"].get(k) != v}
        r["ok"] = not missed and r.get("extra_ok", True)
        r["missed"] = {k: {"want": w, "got": g}
                       for k, (w, g) in missed.items()}
        if not r.get("forced"):
            suite_ok = suite_ok and r["ok"]
        status = "ok" if r["ok"] else "MISS"
        print(f"[{status}] {r['name']}: " + ", ".join(
            f"{k}={v}" for k, v in sorted(r["metrics"].items()) if v))
        for k, wg in r["missed"].items():
            print(f"       expected {k}={wg['want']}, got {wg['got']}")

    # histogram SLO summary: the registry snapshots now carry estimated
    # p50/p99 (serving latency reads the same fields in load_check)
    for fam in monitor.get_registry().families():
        if fam.kind != "histogram":
            continue
        # only *_seconds histograms are durations; ratio histograms
        # (e.g. serving_batch_occupancy) print their raw values
        in_ms = fam.name.endswith("_seconds")

        def _fmt(v):
            return f"{v * 1e3:.2f}ms" if in_ms else f"{v:.4g}"

        for labels, child in fam.children():
            snap = child.snapshot()
            if not snap["count"]:
                continue
            lbl = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            print(f"  {fam.name}{{{lbl}}}: n={snap['count']} "
                  f"p50={_fmt(snap['p50'])} p99={_fmt(snap['p99'])} "
                  f"max={_fmt(snap['max'])}")

    recompiles = monitor.recompile_count()
    gate_ok = suite_ok and recompiles <= args.recompile_threshold
    check = {"recompile_threshold": args.recompile_threshold,
             "recompiles": recompiles, "suite_ok": suite_ok,
             "status": "ok" if gate_ok else "fail"}
    print(f"recompiles across suite: {recompiles} "
          f"(threshold {args.recompile_threshold}) -> "
          f"{'ok' if gate_ok else 'FAIL'}")
    for ev in monitor.recompile_events():
        print(f"  recompile[{ev.path}] program {ev.program_serial} "
              f"built at {ev.build_site}: changed {list(ev.changed)} — "
              f"{ev.detail}")

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump({"scenarios": results,
                       "snapshot": monitor.snapshot(),
                       "check": check}, f, indent=2, default=str)
        print(f"metrics artifact written to {args.json}")
    return 0 if (not args.check or gate_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
