"""``benchmark/tests/test_deviceless_stored.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_deviceless_stored.py"))
