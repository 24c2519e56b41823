"""``benchmark/tests/test_order_spread.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_order_spread.py"))
