"""``benchmark/tests/test_decode_walk_metric.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_decode_walk_metric.py"))
