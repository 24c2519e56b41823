"""Child process of test_data_parallel.py: the ``train`` runner with
``data_parallel: 4`` on four virtual CPU devices, through the harness's own
``Cell`` and result line. XLA_FLAGS is set by the parent."""
import argparse
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness                                              # noqa: E402
from runners import train                                   # noqa: E402

bench = {
    "configs": [{"name": "bert-tiny-dp4",
                 "file": "benchmark/tests/data/bert-tiny-dp4.json"}],
    "workloads": [{"name": "bert-tiny.dp4", "config": "bert-tiny-dp4",
                   "traffic": "pretrain-s512-b32", "chips": 4}],
    "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
cell = harness.Cell(bench, "bert-tiny.dp4", rehearse=True)
chips = harness.find_chips(cell)
assert len(chips["devices"]) == 4, chips
args = argparse.Namespace(seed=9, seconds=1.0, trace=0)
result = train.run(cell, chips, args, T0)
sys.exit(harness.print_result(cell, chips, result, False))
