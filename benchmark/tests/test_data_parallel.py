"""The ``train`` runner's ``data_parallel: n`` path, which no cell uses
yet: ``CompiledProgram.with_data_parallel`` over four virtual CPU devices,
held to the same reference as the single-device step. So the four-chip
cell under Open questions in PERF.md is a pair of data files for whoever
adds it."""
import json
import os
import subprocess
import sys

import harness


def test_train_runner_with_data_parallel_4_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "tests", "data",
                                      "dp4_run.py")],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=harness.REPO)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0
