"""``benchmark/tests/test_layer_metric_files.py`` under the tier-1 gate (see ``_own.py``)."""
from _own import load

globals().update(load("test_layer_metric_files.py"))
