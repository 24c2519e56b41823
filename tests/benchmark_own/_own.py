"""The benchmark's own tests (``benchmark/tests/``) where the tier-1 gate
counts them: each ``test_own_*.py`` beside this file loads one file of
``benchmark/tests/`` by path and re-exports its tests and fixtures, so a
broken reader behind the ledger fails ``pytest tests/``. No file under
``benchmark/`` is edited; the loaded file keeps its ``__file__``, by which
the benchmark's tests find ``benchmark/tests/data``.

``benchmark/`` has to lead ``sys.path`` (as ``benchmark/tests/conftest.py``
puts it), and its ``tools`` is a regular package that shadows the
repository's own ``tools/`` directory. So the benchmark's top-level modules
are in ``sys.modules`` only while one of its files is loaded or one of its
tests runs; the rest of the suite, in the same worker, never sees them.
"""
import contextlib
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
# every top-level name benchmark/ can be imported under
_NAMES = {os.path.splitext(n)[0] for n in os.listdir(BENCH)}
_inside: dict = {}    # the benchmark's modules, while the suite's are in
_outside: dict = {}   # the suite's modules of those names, while ours are


def _swap(take_out: dict, put_in: dict) -> None:
    for name in [n for n in sys.modules if n.split(".")[0] in _NAMES]:
        take_out[name] = sys.modules.pop(name)
    sys.modules.update(put_in)
    put_in.clear()


@contextlib.contextmanager
def benchmark_imports():
    path = sys.path[:]
    _swap(_outside, _inside)
    sys.path[0:0] = [BENCH, ROOT]
    try:
        yield
    finally:
        sys.path[:] = path
        _swap(_inside, _outside)


@pytest.fixture(scope="module", autouse=True)
def _benchmark_imports():
    """Module-scoped, so it is set up before the loaded file's own
    module-scoped fixtures and stays for imports made inside a test."""
    with benchmark_imports():
        yield


def load(filename: str) -> dict:
    """The public names of ``benchmark/tests/<filename>`` for a thin
    module's ``globals()``, and the fixture above."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests." + filename[:-3],
        os.path.join(BENCH, "tests", filename))
    module = importlib.util.module_from_spec(spec)
    with benchmark_imports():
        spec.loader.exec_module(module)
    names = {k: v for k, v in vars(module).items()
             if not k.startswith("__")}
    names["_benchmark_imports"] = _benchmark_imports
    return names
