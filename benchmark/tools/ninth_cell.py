"""A made-up next cell, appended as a later PR has to append one: a new
configuration, a new cell and its per-layer entries at the END of every
list of ``BENCHMARK.json``, new data files beside the old ones, and no
file or entry that is there edited. The benchmark's own tests have to hold
on such a tree (PR 51: until then five of them pinned a cell's metric
count, one its place in a list), so the next configuration's PR is an
addition.

The made-up cell is a copy of an existing one under other names: its
configuration's file as ``configs/<NAME>.json``, its traffic mix, and each
of its per-layer metrics' files under the suffix ``.<SUFFIX>``.

    python3 benchmark/tools/ninth_cell.py --tree DIR [--like <cell>]

writes them into the unpacked tree DIR (a scratch copy, never the
repository: ``git archive HEAD | tar -x -C DIR``), where ``python3 -m pytest
benchmark/tests -q`` then runs with nine cells.
``benchmark/tests/test_layer_metric_files.py`` appends the same in memory.
"""
import argparse
import copy
import json
import os

NAME, SUFFIX = "made-up-ninth-serve", "ninth"
LIKE = "sdar-30b-a3b.decode-blocks"       # 16 entries of its own


def appended(bench: dict, like: str = LIKE, name: str = NAME,
             suffix: str = SUFFIX):
    """``(the appended copy of bench, {path under benchmark/: the file it
    copies})`` for a further cell made like ``like``: configuration
    ``name``, its metrics under ``.suffix``."""
    out = copy.deepcopy(bench)
    cell = next(w for w in bench["workloads"] if w["name"] == like)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    ninth = f"{name}.{cell['traffic']}"
    files = {f"configs/{name}.json":
             os.path.relpath(config["file"], "benchmark")}
    out["configs"].append(dict(config, name=name,
                               file=f"benchmark/configs/{name}.json"))
    out["workloads"].append(dict(cell, name=ninth, config=name))
    for m in out["end_to_end"]:
        if like in m.get("workloads", []):
            m["workloads"].append(ninth)
    for m in bench["per_layer"]:
        if m.get("workloads") == [like]:
            stem = m["name"].rsplit(".", 1)[0]
            out["per_layer"].append(dict(m, name=f"{stem}.{suffix}",
                                         workloads=[ninth]))
            files[f"layer_metrics/{stem}.{suffix}.json"] = \
                f"layer_metrics/{m['name']}.json"
    return out, files


def write(tree: str, like: str = LIKE) -> str:
    """Appends the ninth cell to the unpacked tree at ``tree``; returns the
    cell's name."""
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    out, files = appended(bench, like)
    for new, old in files.items():
        with open(os.path.join(tree, "benchmark", old)) as f:
            spec = json.load(f)
        spec["name"] = os.path.basename(new)[:-len(".json")]
        with open(os.path.join(tree, "benchmark", new), "x") as f:
            json.dump(spec, f, indent=2)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out["workloads"][-1]["name"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", required=True,
                    help="an unpacked scratch copy of the repository")
    ap.add_argument("--like", default=LIKE)
    args = ap.parse_args()
    if os.path.exists(os.path.join(args.tree, ".git")):
        raise SystemExit("--tree is a repository: give a scratch copy")
    print(write(args.tree, args.like))
