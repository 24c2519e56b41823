"""Share of the summed duration of the spans named in ``of`` that the spans
named in ``part`` take: of the dispatch thread's time in the executor, the
part spent in prefill programs."""


def read(ctx, part, of):
    total = {n: 0.0 for n in of}
    for s in ctx["spans"]:
        if s["name"] in total:
            total[s["name"]] += s["t1"] - s["t0"]
    whole = sum(total.values())
    if whole <= 0:
        return None
    return 100.0 * sum(total[n] for n in part) / whole
