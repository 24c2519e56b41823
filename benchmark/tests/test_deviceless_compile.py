"""The cells' executables compile for a v5e that is described and not
attached, at the configurations' real sizes, and fit its memory: the BERT
train step and the GPT programs that go through ``Executor.run`` (two
prefill buckets and the chunk program) at the committed slot count. The
chained decode scan is built inside the executor and is probed on the chip
instead (``tools/probe_slots.py``, numbers in the configuration file).

Compiling says nothing about results or speed. All in this one file, the
topology described inside a fixture: one process loads the TPU's library.
"""
import pytest

import harness
from tools import deviceless

HBM_BYTES = 16_909_336_064      # bytes_limit the v5e's allocator reports


@pytest.fixture(scope="module")
def v5e():
    try:
        return deviceless.describe_v5e()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _fits(compiled):
    m = deviceless.memory_of(compiled)
    need = (m["argument_size_in_bytes"] + m["temp_size_in_bytes"]
            + m["generated_code_size_in_bytes"])
    assert need < HBM_BYTES, m
    return m


def test_bert_base_train_step_compiles_and_fits(v5e):
    cfg = harness.load_json(harness.HERE, "configs",
                            "bert-base-pretrain.json")
    (prog, fetch), = deviceless.train_programs(cfg).values()
    compiled = deviceless.compile_run_program(prog, fetch, v5e, batch=32)
    m = _fits(compiled)
    assert compiled.as_text().count("tpu_custom_call") >= 36
    assert m["temp_size_in_bytes"] > 4e9      # a full step, not a toy


def test_gpt2_base_run_programs_compile_and_fit_at_the_committed_slots(v5e):
    cfg = harness.load_json(harness.HERE, "configs", "gpt2-base-serve.json")
    programs = deviceless.serve_programs(cfg)
    assert set(programs) == {"prefill:128", "prefill:512", "chunk:128"}
    for name, (prog, fetch) in programs.items():
        m = _fits(deviceless.compile_run_program(prog, fetch, v5e))
        assert m["argument_size_in_bytes"] > 4.8e9, (name, m)  # the cache
