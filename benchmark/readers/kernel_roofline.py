"""A kernel's share of its roofline: the least time the chip could take
for one of the kernel's calls over the time the device trace gives one.
The least time is ``kernel_costs.<cost>`` (operations and HBM bytes from
the calls' shapes, through the program's counters of the whole window)
over the calls those counters saw. The trace's time is the summed duration
of the device operations whose name matches ``pattern`` over their number,
read from the profile the run has just written (the reduced trace keeps
sums by name, not counts). Both are means over prefill and decode calls,
which a few seconds of a saturated closed loop hold in the window's own
proportion."""
import glob
import os
import re

import harness
import kernel_costs
from readers import xplane


def _newest_profile():
    found = glob.glob(os.path.join(harness.OUT_DIR, "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def read(ctx, pattern, cost, exclude="^$"):
    if not ctx.get("trace") or not ctx.get("peaks"):
        return None
    least = getattr(kernel_costs, cost)(ctx["config"], ctx["counters"],
                                        ctx["peaks"])
    path = _newest_profile()
    if not least or not least[1] or not path:
        return None
    pat, exc = re.compile(pattern), re.compile(exclude)
    took = [(e - s) / 1e9
            for dev in xplane.load(path)["devices"].values()
            for name, s, e in dev["ops"]
            if pat.search(name) and not exc.search(name)]
    if not took:
        return None
    seconds, calls = least
    return 100.0 * (seconds / calls) / (sum(took) / len(took))
