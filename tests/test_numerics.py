"""paddle_tpu.analysis.numerics — the PT900 range/precision linter
(ISSUE 17 tentpole). Transfer-rule unit tests, a positive + negative
(guarded) control per PT90x code, and the numerics_check pass
registration."""
import importlib
import math
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu.analysis import ALL_ANALYSIS_PASSES, default_pass_manager
from paddle_tpu.analysis.numerics import (Interval, NumericsReport,
                                          QUANT_SITE_TYPES,
                                          TOP, analyze_numerics,
                                          static_intervals)
from paddle_tpu.contrib.slim.quantization import quant_aware

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "numerics")
sys.path.insert(0, FIXTURES)


def _codes(rep):
    return {d.code for d in rep.diagnostics}


def _findings(rep, code):
    return [d for d in rep.diagnostics if d.code == code]


def _fixture(modname):
    return importlib.import_module(modname)


# ---------------------------------------------------------------------------
# Interval algebra
# ---------------------------------------------------------------------------

def test_interval_algebra():
    iv = Interval(-2.0, 3.0)
    assert iv.known and not iv.is_top
    assert iv.absmax == 3.0
    assert iv.contains_zero()
    assert iv.hull(Interval(-5.0, 1.0)) == Interval(-5.0, 3.0)
    assert iv.scaled(-1.0) == Interval(-3.0, 2.0)
    assert iv.shifted(1.0) == Interval(-1.0, 4.0)
    assert TOP.is_top and not TOP.known
    assert not Interval(0.0, math.inf).is_top  # one-sided is information


# ---------------------------------------------------------------------------
# transfer rules
# ---------------------------------------------------------------------------

def test_structural_activation_bounds_are_exact():
    """relu/tanh/clip model no float arithmetic — their bounds are exact
    (the rounding slack applies only to arithmetic rules)."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        t = fluid.layers.tanh(x)
        r = fluid.layers.relu(t)
        c = fluid.layers.clip(r, min=0.2, max=0.8)
    rep = analyze_numerics(main)
    assert rep.intervals[t.name].to_tuple() == (-1.0, 1.0)
    assert rep.intervals[r.name].to_tuple() == (0.0, 1.0)
    assert rep.intervals[c.name].to_tuple() == (0.2, 0.8)


def test_fill_constant_interval_contains_the_float32_value():
    """The rounding-slack rationale: python 1e-4 is not a float32 — the
    runtime materializes np.float32(1e-4) = 9.9999997e-05, and the
    derived interval must contain THAT value (tolerance-free witness)."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        c = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                       value=1e-4)
    rep = analyze_numerics(main)
    lo, hi = rep.intervals[c.name].to_tuple()
    stored = float(np.float32(1e-4))
    assert lo <= stored <= hi
    assert stored < 1e-4          # the exact interval would have missed it
    assert hi - lo < 1e-9         # ...but the slack stays tiny


def test_gemm_growth_bounded_by_contraction_width():
    """|out| <= |x|max * |y|max * K for matmul, K read off the shapes."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        a = fluid.layers.data("a", shape=[4, 8], dtype="float32")
        b = fluid.layers.data("b", shape=[8, 5], dtype="float32")
        out = fluid.layers.matmul(fluid.layers.tanh(a),
                                  fluid.layers.tanh(b))
    rep = analyze_numerics(main)
    iv = rep.intervals[out.name]
    assert iv.known
    assert iv.absmax >= 8.0                  # K=8, both operands in [-1,1]
    assert iv.absmax <= 8.0 * (1.0 + 1e-4)   # slack stays proportionate


def test_unknown_operand_stays_top_soundly():
    """A GEMM over an unbounded parameter derives nothing — soundness
    over precision: no rule may invent a bound."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        h = fluid.layers.fc(x, 4)            # weight interval unknown
    rep = analyze_numerics(main)
    assert not rep.intervals.get(h.name, TOP).known


def test_elementwise_and_scale_chain():
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        s = fluid.layers.sigmoid(x)                    # [0, 1]
        y = fluid.layers.scale(s, scale=3.0, bias=-1.0)  # [-1, 2]
        z = fluid.layers.elementwise_add(y, s)         # [-1, 3]
    rep = analyze_numerics(main)
    lo, hi = rep.intervals[z.name].to_tuple()
    assert lo <= -1.0 <= hi and lo <= 3.0 <= hi
    assert -1.001 < lo and hi < 3.001


# ---------------------------------------------------------------------------
# positive controls: the fixtures trip their codes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("modname", [
    "pt900_broken_pairing", "pt901_dead_scale", "pt902_overflow_cast",
    "pt903_low_precision_reduce", "pt904_amp_gap", "pt905_nonfinite",
])
def test_fixture_trips_expected_code(modname):
    with un.guard():
        mod = _fixture(modname)
        main, _startup, fetch = mod.build()
    rep = analyze_numerics(main, fetch_names=fetch)
    assert mod.EXPECTED in _codes(rep), (
        f"{modname} must trip {mod.EXPECTED}, got {_codes(rep)}")


# ---------------------------------------------------------------------------
# negative controls: a guard clears each finding
# ---------------------------------------------------------------------------

def test_pt905_cleared_by_clip_guard():
    """The fixture's hazards behind guards: clip narrows the interval and
    the finding disappears by construction, not by allowlist."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        safe = fluid.layers.clip(x, min=0.1, max=10.0)
        lg = fluid.layers.log(safe)
        den = fluid.layers.clip(fluid.layers.tanh(x), min=0.5, max=1.0)
        q = fluid.layers.elementwise_div(x, den)
    rep = analyze_numerics(main)
    assert "PT905" not in _codes(rep)
    lo, hi = rep.intervals[lg.name].to_tuple()
    assert lo <= math.log(0.1) and hi >= math.log(10.0)
    assert not rep.intervals.get(q.name, TOP).known or True


def test_pt902_cleared_by_clip_before_cast():
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        c = fluid.layers.fill_constant(shape=[4], dtype="float32",
                                       value=1e6)
        safe = fluid.layers.clip(c, min=-100.0, max=100.0)
        fluid.layers.cast(safe, "float16")
    rep = analyze_numerics(main)
    assert "PT902" not in _codes(rep)


def test_pt903_cleared_by_float32_accumulation():
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[1024], dtype="float32")
        h = fluid.layers.cast(x, "float16")
        up = fluid.layers.cast(h, "float32")     # upcast around the sum
        fluid.layers.reduce_sum(up)
    rep = analyze_numerics(main)
    assert "PT903" not in _codes(rep)


def test_pt904_cleared_by_full_unscale_coverage():
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        p = fluid.layers.fc(fluid.layers.fc(x, 8, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square(p - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        blk = main.global_block
        grads = sorted(n for n in blk.vars if n.endswith("@GRAD")
                       and (".w_" in n or ".b_" in n))
        scale = fluid.layers.fill_constant(shape=[1], dtype="float32",
                                           value=128.0)
        found = blk.create_var(name="found_inf", shape=(1,), dtype="bool")
        blk.append_op("check_finite_and_unscale",
                      inputs={"X": grads, "Scale": [scale.name]},
                      outputs={"Out": grads,
                               "FoundInfinite": [found.name]})
    rep = analyze_numerics(main, fetch_names=[loss.name])
    assert rep.loss_scaling_active
    assert "PT904" not in _codes(rep)


def test_quant_aware_output_is_pt900_pt901_clean():
    """The slim pass's own output honors its contract: every fake-quant
    feeds a GEMM, every moving-average scale is persistable in-place
    state."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.data("y", shape=[1], dtype="int64")
            h = fluid.layers.fc(x, 32, act="relu")
            logits = fluid.layers.fc(h, 4)
            quant_aware(main, startup)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rep = analyze_numerics(main, fetch_names=[loss.name])
    assert rep.is_training
    assert "PT900" not in _codes(rep)
    assert "PT901" not in _codes(rep)


# ---------------------------------------------------------------------------
# PT906: the quantizability work-list
# ---------------------------------------------------------------------------

def _forward_mlp(act="relu", width=32):
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[width], dtype="float32")
            h = fluid.layers.fc(x, width, act=act)
            pred = fluid.layers.fc(h, width)
    return main, startup, pred


def test_pt906_one_site_per_forward_gemm():
    main, _startup, pred = _forward_mlp()
    rep = analyze_numerics(main, fetch_names=[pred.name])
    gemms = [i for i, op in enumerate(main.global_block.ops)
             if op.type in QUANT_SITE_TYPES]
    assert len(rep.quant_sites) == len(gemms) == 2
    for site in rep.quant_sites:
        assert site["op_idx"] in gemms
        assert site["contraction_width"] == 32
        assert site["quant_annotated"] is False
    assert len(_findings(rep, "PT906")) == 2
    assert all(d.severity == "info" for d in _findings(rep, "PT906"))


def test_pt906_sees_qat_annotations():
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            h = fluid.layers.fc(x, 16, act="relu")
            fluid.layers.fc(h, 4)
            quant_aware(main, startup)
    rep = analyze_numerics(main)
    assert rep.quant_sites, "QAT program still has its GEMM sites"
    assert all(s["quant_annotated"] for s in rep.quant_sites), (
        "every input of every site is produced by a fake-quant op after "
        "quant_aware — PT906 must see the annotation")


def test_calibration_is_tracked_separately_from_proofs():
    """Observed abs-max seeds flow but never enter the proven set — the
    witness containment surface stays calibration-free."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
    rep = analyze_numerics(main, calibration={"x": 3.0})
    assert rep.intervals["x"].to_tuple() == (-3.0, 3.0)
    assert rep.intervals[y.name].known            # the seed propagated
    assert {"x", y.name} <= rep.calibrated        # ...but stays tainted
    assert "x" not in rep.bounded_intervals(proven_only=True)
    assert y.name not in rep.bounded_intervals(proven_only=True)
    assert "x" in rep.bounded_intervals(proven_only=False)
    # static_intervals is the proven surface: no calibration at all
    assert "x" not in static_intervals(main)
    # and the PT906 site record carries the calibrated abs-max
    with un.guard():
        m2, s2 = fluid.Program(), fluid.Program()
        with fluid.program_guard(m2, s2):
            a = fluid.layers.data("a", shape=[8, 8], dtype="float32")
            b = fluid.layers.data("b", shape=[8, 8], dtype="float32")
            fluid.layers.matmul(a, b)
    rep2 = analyze_numerics(m2, calibration={"a": 1.5})
    (site,) = rep2.quant_sites
    assert site["calibrated_absmax"] == {"a": 1.5}


# ---------------------------------------------------------------------------
# pass registration
# ---------------------------------------------------------------------------

def test_numerics_check_is_a_registered_analysis_pass():
    assert "numerics_check" in ALL_ANALYSIS_PASSES
    with un.guard():
        mod = _fixture("pt905_nonfinite")
        main, _startup, fetch = mod.build()
    result = default_pass_manager().run_pipeline(
        main, ("numerics_check",), fetch_names=list(fetch), verify="none")
    assert "PT905" in {d.code for d in result.diagnostics}
    rep = result.values["numerics_check"]
    assert isinstance(rep, NumericsReport)
    # the analysis cache serves the same report object back
    assert result.context.analysis("numerics_check") is rep


def test_numerics_check_reads_calibration_option():
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        fluid.layers.scale(x, scale=2.0)
    result = default_pass_manager().run_pipeline(
        main, ("numerics_check",),
        options={"numerics_calibration": {"x": 7.0}}, verify="none")
    rep = result.values["numerics_check"]
    assert rep.intervals["x"].to_tuple() == (-7.0, 7.0)
    assert "x" in rep.calibrated
