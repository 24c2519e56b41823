"""``tools/deviceless_stored.py`` for a configuration whose engine fetches
its recurrent layers' statistics beside the experts' (``rule_stats``: the
scan's rows, ``layers.mamba2_scan``; the delta rule's too): the same
compiles for a described v5e, with every fetch the engine asks.

    python3 benchmark/tools/deviceless_ssm.py [--config granite-4.0-h-small-ep2-serve] [--record] [--hlo DIR]

Run with JAX_PLATFORMS=cpu. Nothing runs on a device; no number printed
here is a measurement.
"""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from tools import deviceless_stored as stored                # noqa: E402


def _names(net: dict, first: str):
    return [net[first].name] + [net[k].name for k in
                                ("expert_stats", "rule_stats")
                                if net.get(k) is not None]


if __name__ == "__main__":
    if "--config" not in sys.argv:
        sys.argv += ["--config", "granite-4.0-h-small-ep2-serve"]
    stored._names = _names
    stored.main()
    if "--record" in sys.argv:      # the record names the tool that made it
        import json
        path = os.path.join(HERE, "configs", sys.argv[
            sys.argv.index("--config") + 1] + ".json")
        text = open(path).read().replace("tools/deviceless_stored.py",
                                         "tools/deviceless_ssm.py")
        with open(path, "w") as f:
            f.write(json.dumps(json.loads(text), indent=2) + "\n")
