"""What a serving cell's scheduler turns were made of, run by run: the cell
several times in one call, each run a new process that goes through
``runners.<runner>.run`` as ``run.py`` does, untraced, and prints beside
its result line what the program's always-on families counted from the end
of set-up on: decode and prefill dispatches and their walls, sequences
seated, the executor's fetch waits, the dispatch thread's phases. The
parent never touches JAX; it prints one row a run and the spread between
quartiles of the rate.

    python3 benchmark/tools/turns.py --workload <cell> --seeds 1,2,3 \
        [--seconds 40] [--set serving.generation.decode_chunk=16]

``--set`` puts a value into the configuration as loaded (a dotted path and
JSON), so that a setting can be read on the chip before its file states it.
A seed may be given more than once.
"""
import argparse
import importlib
import json
import os
import re
import statistics
import subprocess
import sys
import time

T_PROCESS = time.perf_counter()

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, REPO)

from tools.order_spread import spread, trimmed_spread       # noqa: E402

PHASES = ("idle_wait", "schedule", "admit", "feed", "publish", "settle")


def put(config: dict, assignment: str) -> None:
    path, _, value = assignment.partition("=")
    *parents, leaf = path.split(".")
    for key in parents:
        config = config[key]
    if leaf not in config:
        raise KeyError(f"--set {path}: the configuration has no such key")
    config[leaf] = json.loads(value)


def arithmetic(delta: dict, window_s: float) -> dict:
    """The turn's parts from the counters' movement over the window (the
    reference that follows it makes no dispatch)."""
    import harness

    def fam(name, **labels):
        return harness.sum_matching(delta, name, **labels)

    n_dec = fam("serving_decode_chunk_seconds_count")
    n_pre = fam("serving_prefill_seconds_count")
    dec_s = fam("serving_decode_chunk_seconds_sum")
    pre_s = fam("serving_prefill_seconds_sum")
    seated = fam("serving_first_token_seconds_count")
    return {
        "decode_dispatches": n_dec, "prefill_dispatches": n_pre,
        "prefills_per_decode": n_pre / n_dec if n_dec else None,
        "sequences_per_prefill": seated / n_pre if n_pre else None,
        "decode_ms": 1e3 * dec_s / n_dec if n_dec else None,
        "prefill_ms": 1e3 * pre_s / n_pre if n_pre else None,
        "walls_s": dec_s + pre_s,
        "wait_ms_per_turn": (1e3 * (window_s - dec_s - pre_s) / n_dec
                             if n_dec else None),
        "chained_step_s": fam("executor_step_seconds_sum", path="chained"),
        "chained_steps": fam("executor_step_seconds_count", path="chained"),
        "fetch_wait_s": {p: fam("executor_fetch_wait_seconds_sum", path=p)
                         for p in ("run", "chained")},
        "loop_s": {p: fam("serving_loop_seconds_sum", phase=p)
                   for p in PHASES},
        # the work a seed's deal and weights give the kernels, where the
        # program counts it: rows the latent walk fetched, rows and experts
        # the expert op served
        "latent_rows": {p: fam("latent_attention_rows_total", phase=p)
                        for p in ("decode", "prefill")},
        "expert_tokens": {p: fam("moe_expert_tokens_total", phase=p)
                          for p in ("decode", "prefill")},
        "experts_hit": {p: fam("moe_experts_hit_total", phase=p)
                        for p in ("decode", "prefill")},
    }


def child(a) -> int:
    import harness

    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell = harness.Cell(bench, a.workload, rehearse=a.rehearse)
    for assignment in a.set:
        put(cell.config, assignment)
    harness.use_compile_cache()
    chips = harness.find_chips(cell)
    runner = importlib.import_module(f"runners.{cell.config['runner']}")
    at_ready = {}
    a.seed, a.trace = a.child, 0
    result = runner.run(cell, chips, a, T_PROCESS,
                        broken=lambda s: at_ready.update(harness.counters()))
    delta = harness.counter_delta(at_ready, harness.counters())
    print("TURNS " + json.dumps(delta_families(delta)), flush=True)
    return harness.print_result(cell, chips, result, False)


def delta_families(delta: dict) -> dict:
    keep = ("serving_decode_chunk_seconds", "serving_prefill_seconds",
            "serving_first_token_seconds", "executor_step_seconds",
            "executor_fetch_wait_seconds", "serving_loop_seconds",
            "latent_attention_rows_total", "moe_expert_tokens_total",
            "moe_experts_hit_total")
    return {k: v for k, v in delta.items() if k.startswith(keep)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", type=int, help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.child is not None:
        return child(a)
    if not a.seeds:
        ap.error("--seeds is required")
    seeds = [int(s) for s in a.seeds.split(",")]
    rates, read = [], 0
    for k, seed in enumerate(seeds):
        cmd = [sys.executable, os.path.abspath(__file__), "--workload",
               a.workload, "--seconds", str(a.seconds), "--child", str(seed)]
        for assignment in a.set:
            cmd += ["--set", assignment]
        if a.rehearse:
            cmd.append("--rehearse")
        p = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
        lines = p.stdout.splitlines()
        row = {"run": k, "seed": seed, "rc": p.returncode}
        closed = re.search(r"window closed ([\d.]+) s", p.stdout)
        turns = next((json.loads(l[6:]) for l in lines
                      if l.startswith("TURNS ")), None)
        last = json.loads(lines[-1]) if lines and lines[-1].startswith(
            "{") else {}
        if not (closed and turns and last):
            print(f"== run {k} seed {seed} rc {p.returncode}: no result\n"
                  + p.stdout[-1500:] + p.stderr[-1500:], flush=True)
            continue
        row["window_s"] = float(closed.group(1))
        row.update({n: m["value"] for n, m in last.get("metrics",
                                                       {}).items()})
        row.update(correct=last["correct"], failed=last["failed"],
                   attempted=last["attempted"],
                   memory_peak_bytes=last.get("device", {}).get(
                       "memory_peak_bytes"))
        row.update(arithmetic(turns, row["window_s"]))
        row["compared"] = {n: c["value"]
                           for n, c in last.get("compared", {}).items()}
        read += 1
        if "decode_tokens_per_s" in row:
            rates.append(row["decode_tokens_per_s"])
        print(json.dumps(row), flush=True)
    if len(rates) >= 2:
        print(json.dumps({
            "set": a.set, "runs": len(rates), "rates": rates,
            "median": statistics.median(rates), "spread": spread(rates),
            "spread_farthest_out": trimmed_spread(rates)}), flush=True)
    return 0 if read == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
