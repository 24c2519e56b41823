"""Which Fluid op emits a device operation: build a cell's executables as
its runner does, read each compiled executable's HLO text, and print, for
every instruction name that a ``breakdown`` lists (``copy``,
``broadcast_select_fusion``, ``fusion``, ...), the ``op_name`` scopes the
text gives it. The program's lowering puts every Fluid op's type into the
scopes (``jit(step_fn)/layer_norm/...``, ``paddle_tpu/lowering.py``), and a
fusion's own line carries only its root's, so the instructions of the
computation a fusion calls are read too.

    python3 benchmark/tools/op_origin.py --workload <cell> [--names copy,fusion]

Run it through the chip tool (the executables are the chip's); with
``--rehearse`` it reads the CPU's at tiny sizes, which shows the scopes and
not the chip's fusions. Without ``--names`` it takes the names of the
cell's newest ``breakdown`` in ``PERF_LEDGER.jsonl``. The whole listing
goes to ``chiprun_out/op_origin.<cell>.txt``; nothing here is a
measurement.
"""
import argparse
import collections
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(([\w\-]+?)(?:\.\d+)*) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_RESULT = re.compile(r" = \(?(\w+\[[\d,]*\])")
_REF = re.compile(r"%([\w.\-]+)")


def origins(hlo_text: str, names=None) -> dict:
    """``{stem: {"count": n, "shapes": Counter, "scopes": Counter, "users":
    Counter}}`` for the instructions of ``hlo_text`` whose name, numbers
    dropped, is in ``names`` (all of them where ``names`` is None).
    ``scopes`` counts the ``op_name`` of the instruction and of every
    instruction in a computation it ``calls``; instructions of called
    computations are themselves listed only through their caller.
    ``users`` counts the ``op_name`` (or, lacking one, the name) of the
    instructions that read it: a copy the compiler put in has no scope of
    its own, and is explained by who asked for that layout."""
    comps, current = {}, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None and _INSTRUCTION.match(line):
            current.append(line)
    called = {m.group(1) for body in comps.values() for line in body
              for m in [_CALLS.search(line)] if m}
    out = {}
    for comp, body in comps.items():
        if comp in called:
            continue
        users = collections.defaultdict(list)
        for line in body:
            lhs, _, rhs = line.partition(" = ")
            scope = _OP_NAME.search(line)
            who = scope.group(1) if scope else _INSTRUCTION.match(
                line).group(2)
            for ref in set(_REF.findall(rhs.split(", metadata=")[0])):
                users[ref].append(who)
        for line in body:
            name, stem = _INSTRUCTION.match(line).groups()
            if names is not None and stem not in names:
                continue
            rec = out.setdefault(stem, {
                "count": 0, "shapes": collections.Counter(),
                "scopes": collections.Counter(),
                "users": collections.Counter()})
            rec["count"] += 1
            rec["users"].update(users.get(name, ()))
            shape = _RESULT.search(line)
            if shape:
                rec["shapes"][shape.group(1)] += 1
            inner = _CALLS.search(line)
            for src in [line] + comps.get(inner.group(1) if inner else "",
                                          []):
                scope = _OP_NAME.search(src)
                if scope:
                    rec["scopes"][scope.group(1)] += 1
    return out


def ledger_names(cell: str):
    """Instruction names of the cell's newest breakdown in the ledger
    (a ledger key is ``<name>_<kind>``)."""
    path = os.path.join(harness.REPO, "PERF_LEDGER.jsonl")
    ops = None
    if os.path.exists(path):
        for line in open(path):
            rec = json.loads(line)
            if rec.get("workload") == cell and rec.get("breakdown"):
                ops = rec["breakdown"].get("device_ops") or ops
    if not ops:
        return None
    return {k.split("_custom-call:")[0] if "_custom-call:" in k
            else k.rsplit("_", 1)[0] for k, _ in ops}


def executables(cell, chips, seed: int) -> dict:
    """``{label: HLO text}`` of every executable the cell's set-up builds,
    through the runner's own ``Session``."""
    import importlib

    runner = importlib.import_module(f"runners.{cell.config['runner']}")
    s = runner.Session(cell, chips)
    if cell.config["runner"] == "serve":
        s.load(seed, cell.traffic)
        s.eng.stop(drain=True, timeout=60.0)
    else:
        s.load(seed)
        s.step(0)
    texts = {}
    for key, step in s.exe._cache.items():
        if getattr(step, "_aot", None):
            kind = "chained" if key[0] == "chained" else "run"
            serial = key[1][0] if kind == "chained" else key[0][0]
            texts[f"{kind} program {serial}"] = step._aot.as_text()
    return texts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--names", default="")
    ap.add_argument("--seed", type=int, default=77025)
    ap.add_argument("--top", type=int, default=6)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell = harness.Cell(bench, a.workload, rehearse=a.rehearse)
    # no compilation cache: its key leaves the scopes out, so a cached
    # executable carries the scopes of whichever checkout compiled it
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    chips = harness.find_chips(cell)
    names = set(filter(None, a.names.split(","))) or ledger_names(cell.name)
    lines = []
    for label, text in executables(cell, chips, a.seed).items():
        found = origins(text, names)
        lines.append(f"== {label}: {len(text.splitlines())} lines of HLO, "
                     f"{len(found)} of the names")
        for stem, rec in sorted(found.items(),
                                key=lambda kv: -kv[1]["count"]):
            shapes = ", ".join(f"{s} x{n}" for s, n in
                               rec["shapes"].most_common(a.top))
            lines.append(f"  {stem}: {rec['count']} instructions; {shapes}")
            for scope, n in rec["scopes"].most_common(a.top):
                lines.append(f"      x{n:<4} {scope}")
            for user, n in rec["users"].most_common(a.top):
                lines.append(f"      read by x{n:<4} {user}")
    out_dir = os.path.join(harness.REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"op_origin.{cell.name}.txt"),
              "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
