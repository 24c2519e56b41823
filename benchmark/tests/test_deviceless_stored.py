"""The ``command-a-plus-ep8-serve`` executables, as the engine builds
them, compile for a v5e that is described and not attached at the
configuration's real size and fit its memory: prefill:128 through
``Executor.run`` and the chained decode scan. Every new kernel is in them
under its stable name, the caches are updated in place inside the scan,
and the bytes the configuration's file records are the compiler's.

Compiling says nothing about results or speed. The topology is described
inside a fixture: one process loads the TPU's library.
"""
import re

import pytest

import harness
from tools import deviceless, deviceless_stored

HBM_BYTES = 16_909_336_064      # bytes_limit the v5e's allocator reports
CONFIG = "command-a-plus-ep8-serve"


@pytest.fixture(scope="module")
def compiled():
    try:
        dev = deviceless.describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cfg = harness.load_json(harness.HERE, "configs", CONFIG + ".json")
    return deviceless_stored.compile_all(cfg, dev)


def test_programs_fit_and_match_the_recorded_bytes(compiled):
    recorded = harness.load_json(harness.HERE, "configs", CONFIG + ".json")[
        "deviceless_memory_analysis"]
    assert set(compiled) == {"prefill:128", "chained decode"}
    for name, exe in compiled.items():
        m = deviceless.memory_of(exe)
        need = (m["argument_size_in_bytes"] + m["temp_size_in_bytes"]
                + m["generated_code_size_in_bytes"])
        assert need < HBM_BYTES, (name, m)
        # 9.47 GB of bf16 weights and 1.07 GB of bf16 cache
        assert 10.4e9 < m["argument_size_in_bytes"] < 10.7e9, (name, m)
        assert recorded[name]["arguments"] == m["argument_size_in_bytes"]
        assert recorded[name]["temp"] == pytest.approx(
            m["temp_size_in_bytes"], rel=0.05)


def test_every_kernel_is_there_under_its_name(compiled):
    calls = lambda text: dict(
        (n, len(re.findall(r"%%%s[.\d]* = [^\n]*tpu_custom_call" % n, text)))
        for n in ("moe_router", "moe_expert_matmul", "decode_attention",
                  "flash_attention_fwd"))
    assert calls(compiled["chained decode"].as_text()) == {
        "moe_router": 4, "moe_expert_matmul": 8, "decode_attention": 4,
        "flash_attention_fwd": 0}
    assert calls(compiled["prefill:128"].as_text()) == {
        "moe_router": 4, "moe_expert_matmul": 8, "decode_attention": 0,
        "flash_attention_fwd": 4}


def test_the_scan_copies_no_cache_and_no_expert_weights(compiled):
    """In the decode program nothing but an in-place update produces a
    whole cache (since PR 48 one ``scatter`` a cache and step, in a fusion
    of its own), and no instruction produces a stack of expert weights."""
    text = compiled["chained decode"].as_text()
    cache = re.findall(r"= bf16\[64,8,1024,128\]\S* ([a-z][\w\-]*)\(", text)
    assert set(cache) <= {"parameter", "fusion", "get-tuple-element",
                          "bitcast", "while"}
    # the scatter works on [slots x heads, rows, D], a bitcast of the cache:
    # four layers, a key and a value cache each
    assert len(re.findall(r"= bf16\[512,1024,128\]\S* scatter\(", text)) == 8
    assert not re.search(r"= bf16\[(?:64,8|512),1024,128\]\S* copy\(", text)
    assert not re.search(r"= bf16\[16,4096,4096\]\S* (copy|fusion|convert)\(",
                         text)
