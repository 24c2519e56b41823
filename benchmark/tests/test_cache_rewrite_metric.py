"""``cache_rewrite_time_pct.saturated`` counts the device events that
PRODUCE a whole KV cache of the serving configuration (a ``copy``, or a
fusion named for its select) and not the in-place updates, whose result
has the cache's shape too. The lines are instructions as the v5e's
compiler printed them for the chained decode and the prefill programs
(PR 26)."""
import pytest

import harness
from readers import trace_op_share

SPEC = harness.load_json(harness.HERE, "layer_metrics",
                         "cache_rewrite_time_pct.saturated.json")
CACHE = "f32[64,12,1024,64]{3,2,1,0:T(8,128)}"
LINES = {
    "copy in the loop": f"%copy.730 = {CACHE} copy(%get-tuple-element.6175)",
    "copy at the entry": "%copy.12 = f32[64,12,1024,64]{2,3,1,0:T(8,128)} "
                         "copy(%donated_vals_2_.1)",
    "rematerialised copy": f"%copy.347.remat_uncompressed = {CACHE} "
                           "copy(%copy.347.remat_compressed)",
    "slot-mask select": f"%broadcast_select_fusion.5 = ({CACHE}, {CACHE}) "
                        "fusion(%p.1, %p.2), kind=kLoop",
    "row select": "%broadcast_select_fusion.96 = f32[1,12,1,64]"
                  "{3,2,1,0:T(1,128)S(1)} fusion(%p.1), kind=kLoop",
    "row update": f"%dynamic_update_slice.9 = {CACHE} "
                  "dynamic-update-slice(%p.1, %p.2, %c.1)",
    "bulk update": "%select_dynamic-update-slice_fusion.3 = "
                   "f32[64,12,1024,64]{2,3,1,0:T(8,128)} fusion(%p.1), "
                   "kind=kLoop",
    "small copy": "%copy.4 = f32[768,1,64]{2,1,0:T(8,128)} copy(%p.1)",
    "async copy": f"%copy-start.2 = ({CACHE}, {CACHE}, u32[]) "
                  "copy-start(%p.1)",
    "kernel": "%decode_attention.1 = f32[768,8,64]{2,1,0} custom-call(%a), "
              'custom_call_target="tpu_custom_call"',
    "loop": f"%while.3 = (s32[], {CACHE}) while(%t.1), body=%b.1",
}
HITS = {"copy in the loop", "copy at the entry", "rematerialised copy",
        "slot-mask select"}


@pytest.mark.parametrize("what", sorted(LINES))
def test_pattern_takes_whole_cache_producers_only(what):
    one = {"trace": {"busy_s": 2.0, "op_seconds": {LINES[what]: 1.0}}}
    got = trace_op_share.read(one, **SPEC["args"])
    assert (got == pytest.approx(50.0)) if what in HITS else got is None


def test_shape_is_the_configurations():
    """The pattern names the cache's shape; it has to be the one the
    cell's configuration gives (slots, heads, positions, head size)."""
    cfg = harness.load_json(harness.HERE, "configs", "gpt2-base-serve.json")
    m, s = cfg["model"], cfg["serving"]
    shape = "f32\\[%d,%d,%d,%d\\]" % (
        s["slots"], m["num_heads"], s["max_seq"],
        m["hidden_size"] // m["num_heads"])
    assert shape in SPEC["args"]["pattern"]


def test_deviceless_decode_tells_the_loop_from_the_entry():
    """``tools/deviceless_decode.py`` reads the compiled HLO's text: a
    whole-cache result counts by where it is made; plumbing and the
    insides of fused computations do not count."""
    from tools.deviceless_decode import whole_cache_instructions

    c = "f32[4,2,32,8]{3,2,1,0:T(8,128)}"
    text = "\n".join([
        "%fused_computation.1 (p: f32[4,2,32,8]) -> f32[4,2,32,8] {",
        f"  ROOT %select.1 = {c} select(%a, %b, %c)",
        "}",
        "%wide.region_0.sunk (t: (s32[], f32[4,2,32,8])) -> (s32[]) {",
        f"  %get-tuple-element.1 = {c} get-tuple-element(%t), index=1",
        f"  %copy.7 = {c} copy(%get-tuple-element.1)",
        f"  %broadcast_select_fusion.2 = ({c}, {c}) fusion(%copy.7), "
        "kind=kLoop, calls=%fused_computation.1",
        f"  %dynamic_update_slice.3 = {c} dynamic-update-slice(%copy.7, %r)",
        "  %bitcast.4 = f32[8,32,8]{2,1,0:T(8,128)} bitcast(%copy.7)",
        "}",
        "ENTRY %main.1 (p0: f32[4,2,32,8]) -> f32[4,2,32,8] {",
        "  %copy.1 = f32[4,2,32,8]{2,3,1,0:T(8,128)} copy(%p0)",
        "  %small.1 = f32[4,2,1,8]{3,2,1,0} copy(%p1)",
        "}"])
    got = whole_cache_instructions(text, (4, 2, 32, 8))
    assert dict(got["loop"]) == {"copy": 1, "broadcast_select_fusion": 1,
                                 "dynamic_update_slice": 1}
    assert dict(got["entry"]) == {"copy": 1}
