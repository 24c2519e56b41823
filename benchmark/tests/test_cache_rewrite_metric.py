"""``cache_rewrite_time_pct.saturated`` is gone (PR 36): no program has
produced a whole ``f32[64,12,1024,64]`` cache since PR 32, and what took its
place, ``kv_append_time_pct.saturated``, has been an entry since PR 34.

This file stays under its name because ``tests/benchmark_own/
test_own_cache_rewrite_metric.py`` loads it by path for the tier-1 gate and
a ``benchmark`` PR may touch no file outside ``benchmark/``. Until a later
PR deletes that loader and adds one each for ``test_order_spread.py`` and
``test_turns.py``, this file carries those files' tests into tier-1 beside
its own (run from ``benchmark/`` they then run twice, some 15 s)."""
import importlib.util
import os

import harness


def _carry(filename: str) -> dict:
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests." + filename[:-3],
        os.path.join(os.path.dirname(os.path.abspath(__file__)), filename))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {k: v for k, v in vars(module).items() if k.startswith("test_")}


globals().update(_carry("test_order_spread.py"))
globals().update(_carry("test_turns.py"))


def test_the_metric_is_gone_and_what_took_its_place_is_there():
    bench = harness.load_json(harness.REPO, "BENCHMARK.json")
    names = {m["name"] for m in bench["per_layer"]}
    assert "cache_rewrite_time_pct.saturated" not in names
    assert "kv_append_time_pct.saturated" in names
    assert not os.path.exists(os.path.join(
        harness.HERE, "layer_metrics",
        "cache_rewrite_time_pct.saturated.json"))
