"""``benchmark/tests/test_ssm_metrics.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_ssm_metrics.py"))
