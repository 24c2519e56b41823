#!/usr/bin/env python
"""XLA compile-option sweep over chained-scan probes (the perf-round lever
VERDICT r5 names next to remat).

``FLAGS_xla_options`` reaches ``jax.jit(compiler_options=...)`` on every
executor path and is part of the compile-cache key, so flipping options
recompiles rather than silently reusing an executable. This tool turns that
knob into a search, TVM-style (PAPERS.md "Learning to Optimize Tensor
Programs": treat the compiler configuration as a tunable, measure, rank):
each candidate option set is timed with the repo's honest chained-scan
protocol (``Executor.run_chained`` differencing — docs/PERF_NOTES.md) on
short ResNet / BERT probes, and the ranked results land in a JSON artifact
whose best entry can be fed straight back via
``FLAGS_xla_options='<json>'``.

Usage:
  python tools/xla_sweep.py [--model resnet --model bert] [--json out.json]
  python tools/xla_sweep.py --ci --json ci_xla_sweep.json
      CI mode: tiny probes (MLP + BERT-tiny), short chains, backend-
      appropriate option sets; exits non-zero if the sweep could not rank
      (baseline failed or every option set errored out).
  python tools/xla_sweep.py --options-file my_sets.json
      Sweep user option sets (a JSON list of objects) instead of the
      built-ins.

Option sets that XLA rejects (unknown flag for the backend) are recorded as
failed trials, not fatal: the artifact shows exactly which sets are legal
on this backend. Methodology notes: docs/PERF_NOTES.md "XLA option
sweeps".

This tool is now the CLI of the PERSISTENT tuning loop
(``paddle_tpu.tuning`` — docs/PERF_NOTES.md "Persistent autotuner"): with
``FLAGS_autotune=measure`` every successful trial is also recorded into
the durable cost database (keyed by program content fingerprint, shape
bucket, backend), so the next process with ``FLAGS_autotune=use`` compiles
straight to the best-known options with zero re-trials. Without the flag
the behaviour is the original one-shot sweep."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# candidate sets live in paddle_tpu.tuning now (the persistent loop and
# this CLI sweep the same space); re-exported here for script compat
from paddle_tpu.tuning import CPU_OPTION_SETS, TPU_OPTION_SETS  # noqa: E402


def _probe_mlp(width=256, depth=4, batch=64):
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[width], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="float32")
            h = x
            for _ in range(depth):
                h = fluid.layers.fc(h, width, act="relu")
            pred = fluid.layers.fc(h, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(batch, width).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}
    return main, startup, loss.name, feed


def _probe_resnet(ci: bool):
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.resnet import build_resnet

    depth, batch, hw = (18, 8, 64) if ci else (50, 128, 224)
    with un.guard():
        model = build_resnet(depth=depth, class_num=100 if ci else 1000,
                             image_shape=(3, hw, hw), amp=not ci)
    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(batch, 3, hw, hw).astype(np.float32),
            "label": rng.randint(0, 100 if ci else 1000,
                                 (batch, 1)).astype(np.int64)}
    return model["main"], model["startup"], model["loss"].name, feed


def _probe_bert(ci: bool):
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.bert import BertConfig, build_bert_pretrain

    cfg = BertConfig.tiny() if ci else BertConfig.base()
    seq, batch = (32, 4) if ci else (512, 32)
    with un.guard():
        model = build_bert_pretrain(cfg, seq_len=seq, amp=not ci)
    rng = np.random.RandomState(0)
    feed = {
        "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq)),
        "pos_ids": np.tile(np.arange(seq), (batch, 1)),
        "sent_ids": np.zeros((batch, seq)),
        "input_mask": np.ones((batch, seq), np.float32),
        "mask_label": rng.randint(0, cfg.vocab_size, (batch, seq)),
        "next_sent_label": rng.randint(0, 2, (batch, 1)),
    }
    for k in ("src_ids", "pos_ids", "sent_ids", "mask_label",
              "next_sent_label"):
        feed[k] = feed[k].astype(np.int64)
    return model["main"], model["startup"], model["loss"].name, feed


PROBES = {"mlp": lambda ci: _probe_mlp(),
          "resnet": _probe_resnet,
          "bert": _probe_bert}


def time_one(main, startup, loss_name, feed, k_short, k_long, repeats):
    """Per-step seconds in a fresh executor/scope, timed through the one
    shared chained-differencing implementation (tuning.chained_step_seconds)."""
    import paddle_tpu as fluid
    from paddle_tpu import tuning

    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        return tuning.chained_step_seconds(
            exe, main, feed, [loss_name], scope,
            k_short=k_short, k_long=k_long, repeats=repeats)


def sweep(models, option_sets, ci: bool, k_short, k_long, repeats) -> dict:
    import jax

    import paddle_tpu as fluid

    from paddle_tpu import tuning

    persist = tuning.autotune_mode() == "measure"
    report = {"backend": jax.default_backend(),
              "protocol": "run_chained differencing: "
                          f"(T({k_long})-T({k_short}))/{k_long - k_short}, "
                          f"min over {repeats} repeats",
              "autotune_db": tuning.default_db_path() if persist else None,
              "models": {}}
    prev = fluid.get_flags(["FLAGS_xla_options"])
    # one shared DB handle, one durable write per model (record_trial
    # save=False memoizes in the handle; per-trial saves would pay a
    # flock + merge + fsync + atomic-rewrite cycle for every candidate)
    database = tuning.get_database() if persist else None
    try:
        for mname in models:
            main, startup, loss_name, feed = PROBES[mname](ci)
            trials = []
            for opts in option_sets:
                fluid.set_flags({"FLAGS_xla_options": json.dumps(opts)})
                label = json.dumps(opts, sort_keys=True)
                t0 = time.time()
                try:
                    # trial_guard: the executor must compile exactly these
                    # options — in measure mode it would otherwise fill
                    # unset knobs (gemm blocks, and the {} baseline's
                    # options) from the DB's best-known entry
                    with tuning.trial_guard():
                        per_step = time_one(main, startup, loss_name, feed,
                                            k_short, k_long, repeats)
                    trials.append({"options": opts, "status": "ok",
                                   "per_step_s": per_step,
                                   "sweep_s": round(time.time() - t0, 2)})
                    if persist:
                        # the durable loop: this measurement feeds the next
                        # process's compile path (FLAGS_autotune=use). A
                        # failed DB write degrades to a warning — the
                        # timing above succeeded, so the artifact keeps
                        # exactly one 'ok' row for this candidate
                        batch = max([1] + [np.asarray(v).shape[0]
                                           for v in feed.values()])
                        try:
                            tuning.record_trial(
                                main, batch, tuning.TunedConfig.make(opts),
                                per_step, db=database, save=False)
                        except Exception as e:
                            print(f"[{mname}] {label}: DB record failed "
                                  f"({type(e).__name__}: {e})", flush=True)
                    print(f"[{mname}] {label}: "
                          f"{per_step * 1e3:.3f} ms/step", flush=True)
                except Exception as e:
                    trials.append({"options": opts, "status": "error",
                                   "error": f"{type(e).__name__}: {e}"[:300]})
                    print(f"[{mname}] {label}: FAILED "
                          f"({type(e).__name__})", flush=True)
            ok = sorted((t for t in trials if t["status"] == "ok"),
                        key=lambda t: t["per_step_s"])
            base = next((t["per_step_s"] for t in trials
                         if t["status"] == "ok" and not t["options"]), None)
            for rank, t in enumerate(ok):
                t["rank"] = rank
                if base:
                    t["speedup_vs_default"] = round(
                        base / t["per_step_s"], 4)
            report["models"][mname] = {
                "trials": trials,
                "best_options": ok[0]["options"] if ok else None,
                "best_per_step_s": ok[0]["per_step_s"] if ok else None,
            }
            if database is not None:
                # one durable write per model: a crash mid-sweep keeps
                # every completed model's trials
                try:
                    database.save()
                except Exception as e:
                    print(f"[{mname}] DB save failed "
                          f"({type(e).__name__}: {e})", flush=True)
    finally:
        fluid.set_flags(prev)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", action="append", choices=sorted(PROBES),
                    help="probe model(s); default: resnet + bert "
                         "(mlp + bert under --ci)")
    ap.add_argument("--ci", action="store_true",
                    help="tiny probes + short chains (the CI artifact run)")
    ap.add_argument("--options-file", metavar="PATH",
                    help="JSON list of option objects to sweep instead of "
                         "the built-ins")
    ap.add_argument("--json", metavar="PATH",
                    help="write the ranked report (the CI artifact)")
    ap.add_argument("--k-short", type=int, default=None)
    ap.add_argument("--k-long", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=None)
    args = ap.parse_args(argv)

    import jax

    models = args.model or (["mlp", "bert"] if args.ci
                            else ["resnet", "bert"])
    if args.options_file:
        with open(args.options_file, "r", encoding="utf-8") as f:
            option_sets = json.load(f)
        if not isinstance(option_sets, list):
            print("--options-file must hold a JSON list of objects",
                  file=sys.stderr)
            return 2
    else:
        option_sets = (TPU_OPTION_SETS if jax.default_backend() == "tpu"
                       else CPU_OPTION_SETS)
    k_short = args.k_short or (2 if args.ci else 4)
    k_long = args.k_long or (6 if args.ci else 16)
    repeats = args.repeats or (1 if args.ci else 3)

    report = sweep(models, option_sets, args.ci, k_short, k_long, repeats)
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2)
        print(f"report written to {args.json}")

    ranked = all(m["best_options"] is not None
                 for m in report["models"].values())
    for mname, m in report["models"].items():
        if m["best_per_step_s"]:
            print(f"{mname}: best {m['best_per_step_s'] * 1e3:.3f} ms/step "
                  f"with {json.dumps(m['best_options'])}")
    if not ranked:
        print("sweep failed to rank (no option set succeeded)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
