"""``benchmark/tests/test_cache_rewrite_metric.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_cache_rewrite_metric.py"))
