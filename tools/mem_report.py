#!/usr/bin/env python
"""Peak-memory planner CLI (the reporting face of analysis/liveness.py +
analysis/sharding_check.py).

Usage:
  python tools/mem_report.py
      Plan the test-book programs (mnist-mlp and seq2seq train, plus the
      lint_program.py --builtin suite): per program, print the estimated
      peak live bytes and the top-10 live-range hot spots with build sites.
  python tools/mem_report.py prog.json [prog2.json ...]
      Plan serialized programs (Program.to_json output).
  python tools/mem_report.py --check [--json report.json]
      CI gate: also run the liveness verifier pass (PT5xx) over every
      program and exit 1 on any *error*-severity PT5xx finding; --json
      writes the full machine-readable report (the CI artifact).
  python tools/mem_report.py --mesh dp=8 --specs zero1
      PER-CHIP mode: plan every program under the mesh + layout
      (analysis.sharding_check spec propagation; layouts from
      parallel.sharding.extract_param_specs — "zero1" applies the
      BuildStrategy.ReduceStrategy.Reduce optimizer-state sharding,
      "allreduce" replicates state, or pass a JSON file of
      name -> [axis|null, ...] specs). Each JSON entry gains a
      "per_chip" section: the per-chip plan, the collective wire volumes
      and the predicted comms-vs-compute ratio.
  ... --mesh dp=8 --check --hbm-budget-mb 15872
      Per-chip budget gate: FAIL any program whose per-chip peak exceeds
      the budget (default: off).
  ... --mesh dp=8 --specs zero1 --check --validate-live
      Multichip dryrun gate: train one dp-sharded zoo model (mnist-mlp +
      Adam under ZeRO-1) LIVE on the current device set, measure the
      state bytes actually resident per chip from the jax shardings, and
      FAIL unless the static per-chip estimate matches within
      --tolerance (default 0.1). Requires >= mesh devices
      (CI runs it under XLA_FLAGS=--xla_force_host_platform_device_count=8).

Options: --batch N (resolve -1 dims, default 64), --top K (hot spots).
Methodology note: docs/PERF_NOTES.md "Peak-memory planning" and
"Per-chip memory under a sharding assignment".
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.analysis import Severity, verify_program  # noqa: E402

# the chip the per-chip plan is predicted FOR (CI passes its HBM as
# --hbm-budget-mb 15872): its entry in analysis.cost_model.DEVICE_PEAKS
# prices the comms-vs-compute ratio. A static prediction, not a measurement.
TARGET_DEVICE_KIND = "TPU v5 lite"


def _book_programs():
    """(name, program, feed_names, fetch_names) for the book models the
    test suite trains (tests/test_mnist_mlp.py, tests/test_seq2seq.py)."""
    import paddle_tpu.unique_name as un
    from paddle_tpu.models.mlp import build_mnist_mlp
    from paddle_tpu.models.seq2seq import build_seq2seq_train

    out = []
    with un.guard():
        m = build_mnist_mlp()
        out.append(("mnist_mlp/main", m["main"], list(m["feeds"]),
                    [m["loss"].name, m["acc"].name]))
        out.append(("mnist_mlp/startup", m["startup"], [], []))
    with un.guard():
        s = build_seq2seq_train(src_vocab=50, tgt_vocab=50)
        out.append(("seq2seq/main", s["main"], list(s["feeds"]),
                    [s["loss"].name]))
        out.append(("seq2seq/startup", s["startup"], [], []))

    import tools.lint_program as lint

    for name, prog, fetches in lint._builtin_programs():
        feeds = [v.name for v in prog.global_block.vars.values()
                 if v.is_data]
        out.append((name, prog, feeds, fetches))
    return out


def _parse_mesh(s):
    """'dp=8,tp=2' -> {'dp': 8, 'tp': 2}"""
    mesh = {}
    for part in s.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        mesh[k.strip()] = int(v)
    if not mesh:
        raise ValueError(f"empty mesh spec {s!r}")
    return mesh


def _specs_for(program, mesh, specs_mode):
    """Resolve --specs for one program: a layout name or a JSON file.
    Anything else is an ERROR — a typo'd spec file silently degrading to
    the replicated layout would make the gate validate the wrong thing."""
    from paddle_tpu.parallel.sharding import extract_param_specs

    mode = (specs_mode or "allreduce").lower()
    if mode not in ("zero1", "allreduce"):
        if not os.path.exists(specs_mode):
            raise SystemExit(
                f"--specs {specs_mode!r} is neither 'zero1', 'allreduce' "
                f"nor an existing JSON spec file")
        with open(specs_mode, "r", encoding="utf-8") as f:
            raw = json.load(f)
        return {k: tuple(v) for k, v in raw.items()}
    specs, _feed = extract_param_specs(program, mesh, zero=mode == "zero1")
    return specs


def _per_chip_entry(program, feeds, fetches, batch, mesh, specs_mode):
    """The per-chip section of one program's JSON entry."""
    from paddle_tpu.analysis.cost_model import (DEVICE_PEAKS,
                                                comms_compute_ratio,
                                                estimate_comms,
                                                estimate_cost)

    specs = _specs_for(program, mesh, specs_mode)
    plan = program.memory_plan(feed_names=feeds, fetch_names=fetches,
                               batch_size=batch, mesh=mesh, specs=specs)
    analysis = plan.sharding
    comms = estimate_comms(analysis)
    cost = estimate_cost(program, batch_size=batch)
    section = {
        "mesh": dict(analysis.mesh),
        "specs_mode": specs_mode or "allreduce",
        "plan": plan.to_dict(),
        "sharding": analysis.to_dict(),
        "comms": comms.to_dict(),
        "comms_compute_ratio": round(
            comms_compute_ratio(
                comms, cost, DEVICE_PEAKS[TARGET_DEVICE_KIND].bf16_tflops),
            4),
    }
    return plan, section


def _static_state_bytes_per_chip(program, analysis, batch):
    """Static per-chip bytes of the persistable state under the analysis'
    propagated specs — the quantity the live validation measures."""
    from paddle_tpu.analysis.liveness import _var_bytes
    from paddle_tpu.analysis.sharding_check import spec_divisor

    total = 0
    seen = set()
    for blk in program.blocks:
        for v in blk.vars.values():
            if not v.persistable or v.is_data or v.name in seen:
                continue
            seen.add(v.name)
            nbytes = _var_bytes(v, batch)[0]
            spec = analysis.var_specs.get(v.name, ())
            total += nbytes // spec_divisor(spec, analysis.mesh, v.shape,
                                            batch)
    return total


def validate_live(mesh, specs_mode, batch, tolerance):
    """Train one dp-sharded zoo model live under ZeRO-1 and compare the
    measured per-chip resident state bytes against the static estimate.
    Returns the JSON section; raises RuntimeError on mismatch."""
    import jax
    import numpy as np

    import paddle_tpu.unique_name as un
    from paddle_tpu.models.mlp import build_mnist_mlp

    n_mesh = 1
    for v in mesh.values():
        n_mesh *= v
    if jax.device_count() < n_mesh:
        raise RuntimeError(
            f"--validate-live needs {n_mesh} devices, have "
            f"{jax.device_count()} (run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_mesh})")

    with un.guard():
        m = build_mnist_mlp(optimizer="adam")
    prog, startup = m["main"], m["startup"]
    feeds = list(m["feeds"])
    fetches = [m["loss"].name]

    specs = _specs_for(prog, mesh, specs_mode)
    plan = prog.memory_plan(feed_names=feeds, fetch_names=fetches,
                            batch_size=batch, mesh=mesh, specs=specs)
    static_bytes = _static_state_bytes_per_chip(prog, plan.sharding, batch)

    bs = fluid.BuildStrategy()
    if (specs_mode or "").lower() == "zero1":
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    compiled = fluid.CompiledProgram(prog).with_data_parallel(
        loss_name=m["loss"].name, build_strategy=bs)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(7)
    with fluid.scope_guard(scope):
        exe.run(startup)
        xb = rng.rand(batch, 784).astype(np.float32)
        yb = rng.randint(0, 10, (batch, 1)).astype(np.int64)
        exe.run(compiled, feed={"img": xb, "label": yb},
                fetch_list=fetches)
        # measured: bytes of each persistable's shards RESIDENT on chip 0
        dev0 = jax.devices()[0]
        measured = 0
        per_var = {}
        persistable = {v.name for blk in prog.blocks
                       for v in blk.vars.values()
                       if v.persistable and not v.is_data}
        for name in sorted(persistable):
            v = scope.find_var(name)
            if v is None:
                continue
            if isinstance(v, jax.Array):
                nbytes = sum(int(s.data.nbytes)
                             for s in v.addressable_shards
                             if s.device == dev0)
            else:
                nbytes = int(np.asarray(v).nbytes)
            measured += nbytes
            per_var[name] = nbytes
    rel = abs(measured - static_bytes) / max(measured, 1)
    section = {
        "model": "mnist_mlp/adam",
        "mesh": dict(mesh),
        "specs_mode": specs_mode or "allreduce",
        "batch": batch,
        "static_state_bytes_per_chip": static_bytes,
        "measured_state_bytes_per_chip": measured,
        # per-var measured bytes so a tolerance failure names the var
        # whose layout drifted without re-instrumenting
        "measured_per_var": per_var,
        "relative_error": round(rel, 5),
        "tolerance": tolerance,
        "ok": rel <= tolerance,
    }
    status = "ok" if section["ok"] else "FAIL"
    print(f"[{status}] live validation ({section['model']}, mesh "
          f"{mesh}, {specs_mode or 'allreduce'}): static "
          f"{static_bytes} B/chip vs measured {measured} B/chip "
          f"(rel err {rel:.2%}, tolerance {tolerance:.0%})")
    return section


def _report_one(name, program, feed_names, fetch_names, batch, top,
                check: bool, mesh=None, specs_mode=None,
                hbm_budget_mb: float = 0.0):
    plan = program.memory_plan(feed_names=feed_names,
                               fetch_names=fetch_names, batch_size=batch)
    entry = {"name": name, "feeds": list(feed_names),
             "fetches": list(fetch_names), "plan": plan.to_dict()}
    gate_errors = []
    budget_fail = None
    chip_plan = None
    if mesh:
        chip_plan, section = _per_chip_entry(
            program, feed_names, fetch_names, batch, mesh, specs_mode)
        entry["per_chip"] = section
        if check and hbm_budget_mb > 0 \
                and chip_plan.peak_bytes > hbm_budget_mb * 2**20:
            budget_fail = (f"per-chip peak "
                           f"{chip_plan.peak_bytes / 2**20:.1f} MiB "
                           f"exceeds --hbm-budget-mb {hbm_budget_mb:g}")
            entry["budget_fail"] = budget_fail
    if check:
        diags = verify_program(program, fetch_names=fetch_names,
                               passes=("liveness",))
        entry["diagnostics"] = [
            {"code": d.code, "severity": d.severity, "message": d.message,
             "block": d.block_idx, "op": d.op_idx, "op_type": d.op_type}
            for d in diags]
        gate_errors = [d for d in diags
                       if d.code.startswith("PT5")
                       and d.severity == Severity.ERROR]
    status = "FAIL" if (gate_errors or budget_fail) else "ok"
    print(f"[{status}] {name}")
    print("  " + plan.format(top).replace("\n", "\n  "))
    if chip_plan is not None:
        print("  " + chip_plan.format(top).replace("\n", "\n  "))
        comms = entry["per_chip"]["comms"]
        print(f"  collectives: {comms['gbytes_per_step'] * 1000:.3f} "
              f"MB/chip/step on the wire, predicted comms/compute "
              f"{entry['per_chip']['comms_compute_ratio']:.3f}")
    if budget_fail:
        print(f"    {budget_fail}")
    if check:
        n = len(entry["diagnostics"])
        print(f"  liveness findings: {n} "
              f"({len(gate_errors)} error-severity PT5xx)")
        for d in gate_errors:
            print(f"    {d}")
    return entry, not (gate_errors or budget_fail)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("programs", nargs="*",
                    help="serialized Program JSON files (default: the "
                         "test-book programs)")
    ap.add_argument("--check", action="store_true",
                    help="run the PT5xx liveness pass; exit 1 on "
                         "error-severity findings (the CI gate)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the full report as JSON (CI artifact)")
    ap.add_argument("--batch", type=int, default=64,
                    help="batch size substituted for -1 dims (default 64)")
    ap.add_argument("--top", type=int, default=10,
                    help="hot spots to print per program (default 10)")
    ap.add_argument("--mesh", default=None,
                    help="per-chip mode: mesh shape like dp=8 or dp=4,tp=2")
    ap.add_argument("--specs", default=None,
                    help="layout under --mesh: zero1 | allreduce "
                         "(default) | path to a JSON spec file")
    ap.add_argument("--hbm-budget-mb", type=float, default=0.0,
                    help="with --check and --mesh: FAIL programs whose "
                         "per-chip peak exceeds this many MiB")
    ap.add_argument("--validate-live", action="store_true",
                    help="with --mesh: train a dp-sharded zoo model live "
                         "and FAIL unless measured per-chip state bytes "
                         "match the static estimate within --tolerance")
    ap.add_argument("--tolerance", type=float, default=0.1,
                    help="relative tolerance for --validate-live "
                         "(default 0.1)")
    args = ap.parse_args(argv)

    mesh = _parse_mesh(args.mesh) if args.mesh else None

    targets = []
    if args.programs:
        for path in args.programs:
            with open(path, "r", encoding="utf-8") as f:
                prog = fluid.Program.from_json(f.read())
            feeds = [v.name for v in prog.global_block.vars.values()
                     if v.is_data]
            targets.append((path, prog, feeds, []))
    else:
        targets = _book_programs()

    ok = True
    report = {"batch_size": args.batch, "programs": []}
    if mesh:
        report["mesh"] = dict(mesh)
        report["specs_mode"] = args.specs or "allreduce"
    for name, prog, feeds, fetches in targets:
        entry, good = _report_one(name, prog, feeds, fetches, args.batch,
                                  args.top, args.check, mesh=mesh,
                                  specs_mode=args.specs,
                                  hbm_budget_mb=args.hbm_budget_mb)
        report["programs"].append(entry)
        ok = ok and good
    if args.validate_live:
        if not mesh:
            print("--validate-live requires --mesh", file=sys.stderr)
            return 2
        section = validate_live(mesh, args.specs, args.batch,
                                args.tolerance)
        report["live_validation"] = section
        ok = ok and section["ok"]
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=2)
        print(f"report written to {args.json}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
