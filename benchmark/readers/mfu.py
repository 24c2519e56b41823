"""Model FLOP/s utilization: the end-to-end tokens per second times the
operations one token needs (``costs.py``, from the configuration's sizes,
forward and backward, nothing recomputed counted), over chips times the
published peak of the type the matmuls run in."""
import costs


def read(ctx, rate, cost, peak):
    tps = ctx["end_to_end"].get(rate)
    if not tps or not ctx.get("peaks"):
        return None
    flops = getattr(costs, cost)(ctx["config"])
    return 100.0 * tps * flops / (ctx["chips"] * ctx["peaks"][peak])
