"""``benchmark/tests/test_costs.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_costs.py"))
