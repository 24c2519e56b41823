"""``tools/op_origin.py``: from compiled HLO text to the scopes behind an
instruction name, on a hand-written module (a fusion whose computation
holds the Fluid op's scope, a compiler-made copy explained by its reader)
and on the CPU's executable of a small program."""
import numpy as np

from tools import op_origin

HLO = '''HloModule jit_multi_fn

%fused_computation.1 (p0: f32[64,12,1024,64], p1: pred[64]) -> f32[64,12,1024,64] {
  %p0 = f32[64,12,1024,64]{3,2,1,0} parameter(0)
  %p1 = pred[64]{0} parameter(1)
  %b.1 = pred[64,12,1024,64]{3,2,1,0} broadcast(%p1), dimensions={0}, metadata={op_name="jit(multi_fn)/while/body/kv_cache_append/jit(_where)/broadcast_in_dim"}
  ROOT %s.1 = f32[64,12,1024,64]{3,2,1,0} select(%b.1, %p0, %p0), metadata={op_name="jit(multi_fn)/while/body/kv_cache_append/jit(_where)/select_n"}
}

ENTRY %main.9 (a: f32[64,12,1024,64], m: pred[64]) -> f32[64,12,1024,64] {
  %a = f32[64,12,1024,64]{3,2,1,0} parameter(0)
  %m = pred[64]{0} parameter(1)
  %broadcast_select_fusion.2 = f32[64,12,1024,64]{3,2,1,0} fusion(%a, %m), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(multi_fn)/while/body/kv_cache_append/jit(_where)/select_n"}
  %copy.5 = f32[64,12,1024,64]{3,2,1,0:T(8,128)} copy(%broadcast_select_fusion.2)
  ROOT %decode_attention.1 = f32[64,12,1024,64]{3,2,1,0} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(multi_fn)/while/body/fused_decode_attention/pallas/decode_attention/pallas_call"}
}
'''


def test_scopes_of_a_fusion_and_readers_of_a_copy():
    got = op_origin.origins(HLO, {"broadcast_select_fusion", "copy"})
    assert set(got) == {"broadcast_select_fusion", "copy"}
    fusion = got["broadcast_select_fusion"]
    assert fusion["count"] == 1
    assert fusion["shapes"] == {"f32[64,12,1024,64]": 1}
    assert all("/kv_cache_append/" in s for s in fusion["scopes"])
    assert sum(fusion["scopes"].values()) == 3      # own line + two inside
    copy = got["copy"]
    assert not copy["scopes"]                       # the compiler's own
    assert list(copy["users"]) == [
        "jit(multi_fn)/while/body/fused_decode_attention/pallas/"
        "decode_attention/pallas_call"]
    # instructions of a called computation are listed through their caller
    assert "s" not in op_origin.origins(HLO)


def test_ledger_names_split_name_from_kind(tmp_path, monkeypatch):
    import json

    (tmp_path / "PERF_LEDGER.jsonl").write_text(json.dumps({
        "workload": "c", "breakdown": {"device_ops": [
            ["broadcast_select_fusion_fusion", 1.2], ["copy_copy", 1.1],
            ["closed_call_custom-call:tpu_custom_call", 0.9],
            ["dynamic-update-slice_dynamic-update-slice", 0.1]]}}) + "\n")
    monkeypatch.setattr(op_origin.harness, "REPO", str(tmp_path))
    assert op_origin.ledger_names("c") == {
        "broadcast_select_fusion", "copy", "closed_call",
        "dynamic-update-slice"}
    assert op_origin.ledger_names("another") is None


def test_scopes_of_a_compiled_program_hold_the_fluid_op_types():
    import jax

    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", shape=[8], dtype="float32")
        out = fluid.layers.fc(x, 4, act="relu")
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe.run(main, feed={"x": np.ones((2, 8), np.float32)},
                fetch_list=[out], scope=scope)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    (step,) = [s for k, s in exe._cache.items()
               if k[0][0] == main._serial]
    scopes = set()
    for rec in op_origin.origins(step._aot.as_text()).values():
        scopes |= set(rec["scopes"])
    assert any("/mul/" in s for s in scopes), scopes
    assert any("/relu/" in s for s in scopes), scopes
