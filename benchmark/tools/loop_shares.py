"""Where the generative dispatch thread's time goes under a traffic mix:
one window of a serving configuration on a mix that need not be a cell
yet, and per phase of ``serving_loop_seconds`` (and the executor's
dispatches) its share of the thread's time; with ``--trace 1`` also the
device's idle share and the idle seconds by innermost program span.

    python3 benchmark/tools/loop_shares.py --workload gpt2-base.decode-saturated \
        --traffic chat-mixed --rate 0.5 --seconds 40 --trace 1

Through the chip tool; ``--rehearse`` runs the control flow on the CPU at
the files' tiny sizes and its shares say nothing about a chip.
"""
import argparse
import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402

PHASES = ("idle_wait", "schedule", "admit", "feed", "settle", "publish")


def span_stats(spans) -> dict:
    """Per span name of the window: count, mean, median and longest, in
    ms. ``serving.settle`` is split by the dispatch it follows (the root
    span that ended last before it on its thread): a decode chunk's settle
    wakes every stream, a prefill's only the newcomers."""
    roots = sorted((s["t1"], s["name"]) for s in spans if s["name"] in (
        "serving.prefill", "serving.prefill_chunk", "serving.decode",
        "serving.spec_verify"))
    ends = [t1 for t1, _ in roots]
    by = {}
    for s in spans:
        name = s["name"]
        if name == "serving.settle":
            i = bisect.bisect_right(ends, s["t0"] + 1e-4)
            name += " after " + (roots[i - 1][1].split(".")[1] if i
                                 else "?")
        by.setdefault(name, []).append(1e3 * (s["t1"] - s["t0"]))
    return {name: {"n": len(v), "mean": sum(v) / len(v),
                   "p50": harness.median(v), "max": max(v)}
            for name, v in sorted(by.items())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a serving cell: its configuration is used")
    ap.add_argument("--traffic", required=True,
                    help="a file of benchmark/traffic/, by name")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=77026)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import paddle_tpu as fluid
    from paddle_tpu import trace as program_trace
    from runners import serve as runner

    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell = harness.Cell(bench, a.workload, rehearse=a.rehearse)
    mix = harness.load_json(harness.HERE, "traffic", a.traffic + ".json")
    if a.rehearse:
        harness._merge(mix, mix.get("rehearsal", {}))
    if a.rate is not None:
        mix["rate_per_s"] = a.rate
    harness.use_compile_cache()
    chips = harness.find_chips(cell)
    trace = harness.TraceWindow(bool(a.trace), a.seconds,
                                f"{cell.name}.{a.traffic}")
    if trace.on:
        fluid.set_flags({"FLAGS_trace_buffer_size": 2_000_000})
    trace.enable_spans()
    s = runner.Session(cell, chips)
    s.load(a.seed, mix)
    program_trace.clear()
    w = s.window(mix, a.seconds, trace)
    delta = w["counters"]
    # the thread's time between the two counter snapshots (the window and
    # the drain of what it sent) is its loop phases plus its dispatches
    took = {p: (harness.sum_matching(delta, "serving_loop_seconds_sum",
                                     phase=p),
                harness.sum_matching(delta, "serving_loop_seconds_count",
                                     phase=p)) for p in PHASES}
    for path in ("run", "chained"):
        took["executor." + path] = (
            harness.sum_matching(delta, "executor_step_seconds_sum",
                                 path=path),
            harness.sum_matching(delta, "executor_step_seconds_count",
                                 path=path))
    total = sum(t for t, _ in took.values())
    out = {"traffic": a.traffic, "rate_per_s": mix.get("rate_per_s"),
           "judged": len(w["judged"]), "failed": len(w["failed"]),
           **w["e2e"], "dispatch_thread_s": total,
           "share_pct": {k: 100.0 * t / total for k, (t, _) in took.items()},
           "mean_ms": {k: 1e3 * t / n if n else None
                       for k, (t, n) in took.items()},
           "count": {k: int(n) for k, (_, n) in took.items()}}
    if trace.on:
        spans = harness.program_spans()
        out["spans_ms"] = span_stats(spans)
        t = harness.reduce_trace(trace, spans)
        if t is not None:
            idle = t["window_s"] - t["busy_s"]
            out["device_idle_pct"] = 100.0 * idle / t["window_s"]
            out["idle_gaps_s"] = t["idle_gaps"]
            out["idle_share_by_span_pct"] = [
                [name, 100.0 * sec / idle] for name, sec in t["idle_gaps"]]
            out["device_ops_s"] = t["device_ops"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
