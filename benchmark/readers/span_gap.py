"""Mean time from the start of each ``parent`` span to the start of its
first child span named ``child``, in the program's spans of the window:
how long a request waited between submission and its first dispatch."""


def read(ctx, parent, child, scale=1.0):
    starts = {s["span_id"]: s["t0"] for s in ctx["spans"]
              if s["name"] == parent}
    first = {}
    for s in ctx["spans"]:
        if s["name"] == child and s["parent_id"] in starts:
            pid = s["parent_id"]
            first[pid] = min(first.get(pid, s["t0"]), s["t0"])
    if not first:
        return None
    return scale * sum(first[p] - starts[p] for p in first) / len(first)
