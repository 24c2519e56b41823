"""paddle_tpu.analysis: one test per diagnostic code, clean-program
baselines, the executor FLAGS_check_program hook, and the registry audit.

Malformed-graph fixtures mutate ops *after* append (direct field writes,
bypassing Operator.set_attr) — exactly the bug class the static verifier
exists to catch before a JAX trace turns it into an XLA-flavoured error.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu.analysis import (CODES, ProgramVerificationError, Severity,
                                 audit_registry, check_program,
                                 coverage_summary, format_audit,
                                 format_diagnostics, liveness, verify_program)
from paddle_tpu.core import registry


def codes_of(diags):
    return {d.code for d in diags}


def errors_of(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


def _mlp_program(fetch=True):
    """Small clean net: data -> fc -> relu -> fc -> mean, with backward+SGD."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, 8, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


# ---------------------------------------------------------------------------
# clean programs produce no error findings
# ---------------------------------------------------------------------------

def test_clean_program_no_findings():
    main, startup, loss = _mlp_program()
    for prog, fetches in ((main, [loss.name]), (startup, [])):
        diags = verify_program(prog, fetch_names=fetches)
        assert not errors_of(diags), format_diagnostics(diags)


def test_book_model_programs_verify_clean():
    """The tests/test_book.py model suite (built by tools/lint_program.py's
    --builtin mode) must verify clean — main, startup AND test clones."""
    import tools.lint_program as lint

    for name, prog, fetches in lint._builtin_programs():
        diags = verify_program(prog, fetch_names=fetches)
        assert not errors_of(diags), f"{name}:\n" + format_diagnostics(diags)


def test_every_code_is_documented_and_tested():
    # the CODES table is the single source of truth; this file (or
    # test_pass_manager.py, which owns the PT70x-PT72x pass-manager
    # families, test_sharding_check.py, which owns PT73x,
    # test_concurrency_lint.py, which owns the source-level PT80x
    # family, or test_numerics.py, which owns the PT90x numerics
    # family) must cover every code
    import io
    import os

    here = os.path.abspath(__file__)
    me = ""
    for fname in (here,
                  os.path.join(os.path.dirname(here),
                               "test_pass_manager.py"),
                  os.path.join(os.path.dirname(here),
                               "test_sharding_check.py"),
                  os.path.join(os.path.dirname(here),
                               "test_concurrency_lint.py"),
                  os.path.join(os.path.dirname(here),
                               "test_numerics.py")):
        with io.open(fname, "r", encoding="utf-8") as f:
            me += f.read()
    assert len(CODES) >= 10
    for code in CODES:
        assert me.count(code) >= 1, f"diagnostic {code} lacks a test here"


# ---------------------------------------------------------------------------
# pass 1: schema conformance
# ---------------------------------------------------------------------------

def _tiny():
    """One relu op on a declared input; returns (program, block, op)."""
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        fluid.layers.relu(x)
    blk = p.global_block
    op = next(o for o in blk.ops if o.type == "relu")
    return p, blk, op


def test_pt100_unregistered_op():
    p, blk, op = _tiny()
    op.type = "totally_not_an_op"
    assert "PT100" in codes_of(verify_program(p))


def test_pt100_grad_of_unregistered_forward():
    p, blk, op = _tiny()
    op.type = "totally_not_an_op_grad"
    assert "PT100" in codes_of(verify_program(p))


def test_pt101_missing_required_input():
    p, blk, op = _tiny()
    del op.inputs["X"]
    diags = verify_program(p)
    assert "PT101" in codes_of(diags)
    d = next(d for d in diags if d.code == "PT101")
    assert d.op_type == "relu" and d.severity == Severity.ERROR


def test_pt102_unknown_input_slot():
    p, blk, op = _tiny()
    op.inputs["Bogus"] = list(op.inputs["X"])
    assert "PT102" in codes_of(verify_program(p))


def test_pt103_missing_required_output():
    p, blk, op = _tiny()
    del op.outputs["Out"]
    assert "PT103" in codes_of(verify_program(p))


def test_pt104_unknown_output_slot():
    p, blk, op = _tiny()
    op.outputs["Bogus"] = list(op.outputs["Out"])
    assert "PT104" in codes_of(verify_program(p))


def test_pt105_missing_required_attr():
    if not registry.has_op("pt_lint_reqattr"):
        @registry.register_op("pt_lint_reqattr", inputs=["X"],
                              outputs=["Out"],
                              attrs={"k": registry.AttrSpec(
                                  "k", required=True)})
        def _lower(ctx, ins, attrs):  # pragma: no cover - never lowered
            return {"Out": ins["X"]}

    p, blk, op = _tiny()
    blk.append_op("pt_lint_reqattr", inputs={"X": ["x"]},
                  outputs={"Out": ["x2"]}, attrs={"k": 1})
    del blk.ops[-1].attrs["k"]
    assert "PT105" in codes_of(verify_program(p))


def test_pt106_unknown_attr_warns():
    p, blk, op = _tiny()
    op.attrs["mystery_knob"] = 7
    diags = verify_program(p)
    d = next(d for d in diags if d.code == "PT106")
    assert d.severity == Severity.WARNING  # does not gate execution
    check_program(p)  # no raise


def test_pt107_nonduplicable_slot_with_list():
    p, blk, op = _tiny()
    op.inputs["X"] = [op.inputs["X"][0], op.inputs["X"][0]]
    assert "PT107" in codes_of(verify_program(p))


def test_grad_op_layout_checked():
    # a hand-built grad op with a bogus slot is caught (PT102/PT104 via the
    # grad-specific schema path)
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        out = fluid.layers.relu(x)
    blk = p.global_block
    blk.append_op("relu_grad",
                  inputs={"X": [x.name], "NotASlot": [x.name]},
                  outputs={"X@GRAD": [x.grad_name],
                           "Bogus@GRAD": [x.grad_name]},
                  attrs={"__fwd_type__": "relu"})
    codes = codes_of(verify_program(p))
    assert "PT102" in codes and "PT104" in codes


# ---------------------------------------------------------------------------
# pass 2: dataflow
# ---------------------------------------------------------------------------

def test_pt200_use_before_def():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.relu(x)
        fluid.layers.sigmoid(h)
    blk = p.global_block
    # swap the two compute ops: sigmoid now reads relu's output first
    relu_i = next(i for i, o in enumerate(blk.ops) if o.type == "relu")
    sig_i = next(i for i, o in enumerate(blk.ops) if o.type == "sigmoid")
    blk.ops[relu_i], blk.ops[sig_i] = blk.ops[sig_i], blk.ops[relu_i]
    diags = verify_program(p)
    assert "PT200" in codes_of(diags)
    with pytest.raises(ProgramVerificationError):
        check_program(p)


def test_pt201_uninitialized_read():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        fluid.layers.relu(x)
    blk = p.global_block
    blk.create_var(name="nowhere", shape=[4], dtype="float32")
    op = next(o for o in blk.ops if o.type == "relu")
    op.inputs["X"] = ["nowhere"]
    diags = verify_program(p)
    assert "PT201" in codes_of(diags)
    assert all(d.severity != Severity.ERROR for d in diags
               if d.code == "PT201")


def test_pt202_write_after_write():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        blk = p.global_block
        blk.create_var(name="t", shape=[2], dtype="float32")
        for val in (0.0, 1.0):
            blk.append_op("fill_constant", outputs={"Out": ["t"]},
                          attrs={"shape": [2], "dtype": "float32",
                                 "value": val})
    assert "PT202" in codes_of(verify_program(p))


def test_pt203_dangling_output_is_info():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        out = fluid.layers.relu(x)
    diags = verify_program(p)  # not fetched -> dangling
    assert "PT203" in codes_of(diags)
    # fetching it silences the finding
    assert "PT203" not in codes_of(verify_program(p, fetch_names=[out.name]))


# ---------------------------------------------------------------------------
# pass 3: lowerability
# ---------------------------------------------------------------------------

def test_pt300_missing_lower_rule():
    if not registry.has_op("pt_lint_nolower"):
        registry._OP_REGISTRY["pt_lint_nolower"] = registry.OpDef(
            type="pt_lint_nolower",
            inputs=[registry.IOSpec("X")],
            outputs=[registry.IOSpec("Out")])
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        p.global_block.append_op("pt_lint_nolower", inputs={"X": [x.name]},
                                 outputs={"Out": ["nl_out"]})
    assert "PT300" in codes_of(verify_program(p))


def test_pt301_grad_of_nondifferentiable():
    # psroi_pool registers grad=None; a hand-built psroi_pool_grad op is
    # suspicious (the generic vjp recomputation has no defined meaning)
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4, 4, 4], dtype="float32")
        blk = p.global_block
        blk.append_op("psroi_pool_grad",
                      inputs={"X": [x.name]},
                      outputs={"X@GRAD": [x.grad_name]},
                      attrs={"__fwd_type__": "psroi_pool"})
    diags = verify_program(p)
    assert "PT301" in codes_of(diags)


def test_pt302_rng_under_deterministic_flag():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        fluid.layers.dropout(x, dropout_prob=0.5)
    assert "PT302" not in codes_of(verify_program(p))
    fluid.set_flags({"FLAGS_cudnn_deterministic": True})
    try:
        assert "PT302" in codes_of(verify_program(p))
    finally:
        fluid.set_flags({"FLAGS_cudnn_deterministic": False})


# ---------------------------------------------------------------------------
# pass 4: shape/dtype replay
# ---------------------------------------------------------------------------

def test_pt400_shape_drift():
    p, blk, op = _tiny()
    out_name = op.outputs["Out"][0]
    blk.var(out_name).shape = (7, 7, 7)  # recorded metadata now stale
    diags = verify_program(p)
    assert "PT400" in codes_of(diags)
    # the replay restores the recorded (wrong) metadata: verification is
    # read-only even when it disagrees
    assert blk.var(out_name).shape == (7, 7, 7)


def test_pt401_dtype_drift():
    p, blk, op = _tiny()
    out_name = op.outputs["Out"][0]
    blk.var(out_name).dtype = "int64"
    assert "PT401" in codes_of(verify_program(p))


def test_shape_replay_catches_raw_attr_mutation():
    """The motivating bug: op.attrs['k'] = v (bypassing set_attr) leaves
    recorded var shapes stale; the replay pass surfaces it."""
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[6], dtype="float32")
        out = fluid.layers.reshape(x, shape=[-1, 2, 3])
    op = next(o for o in p.global_block.ops if o.type == "reshape2")
    op.attrs["shape"] = [-1, 3, 2]  # raw write: no version bump, no re-infer
    assert "PT400" in codes_of(verify_program(p))


# ---------------------------------------------------------------------------
# executor hook (FLAGS_check_program)
# ---------------------------------------------------------------------------

def test_executor_hook_rejects_malformed_program():
    main, startup, loss = _mlp_program()
    blk = main.global_block
    op = next(o for o in blk.ops if o.type == "relu")
    del op.inputs["X"]
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    fluid.set_flags({"FLAGS_check_program": True})
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(ProgramVerificationError) as ei:
            exe.run(main, feed={"x": np.zeros((2, 4), np.float32),
                                "y": np.zeros((2, 1), np.float32)},
                    fetch_list=[loss.name])
    assert "PT101" in str(ei.value)


def test_executor_hook_covers_compiled_program():
    """The CompiledProgram dispatch path must verify the wrapped program
    too — multi-device users get the same build-site diagnostics."""
    main, startup, loss = _mlp_program()
    op = next(o for o in main.global_block.ops if o.type == "relu")
    del op.inputs["X"]
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    fluid.set_flags({"FLAGS_check_program": True})
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(ProgramVerificationError, match="PT101"):
            exe.run(compiled,
                    feed={"x": np.zeros((8, 4), np.float32),
                          "y": np.zeros((8, 1), np.float32)},
                    fetch_list=[loss.name])


def test_executor_hook_verifies_once_per_version():
    main, startup, loss = _mlp_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.zeros((2, 4), np.float32),
            "y": np.zeros((2, 1), np.float32)}
    fluid.set_flags({"FLAGS_check_program": True})
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name])
        n = len(exe._verified)
        exe.run(main, feed=feed, fetch_list=[loss.name])
        assert len(exe._verified) == n  # cached: no re-verify per step


# ---------------------------------------------------------------------------
# pass 5: liveness & effects (PT50x) + donation + memory plan
# ---------------------------------------------------------------------------

def _while_program():
    """sum-loop program with two outer vars the body reads: ``step`` (read
    only inside the sub-block) and ``acc`` (read+written through the loop).
    Returns (main, startup, out_var)."""
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 4)
        step = layers.fill_constant([1], "float32", 2.5)
        acc = layers.fill_constant([1], "float32", 0.0)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            layers.assign(layers.elementwise_add(acc, step), acc)
            layers.increment(i, value=1)
            layers.assign(layers.less_than(i, n), cond)
        out = layers.scale(acc, scale=1.0)
    return main, startup, out


def test_pt500_donation_unsafe_fetch():
    """Fetching a parameter the step updates in place excludes it from
    donation (the old state_in ∩ state_out heuristic donated it, so the
    fetch could observe a consumed buffer)."""
    main, startup, loss = _mlp_program()
    blk = main.global_block
    param = next(n for n in blk.vars if n.endswith(".w_0"))
    feeds = {"x", "y"}

    diags = verify_program(main, fetch_names=[loss.name, param])
    d = next(d for d in diags if d.code == "PT500")
    assert param in d.message and d.severity == Severity.WARNING
    check_program(main, fetch_names=[loss.name, param])  # warning: no raise

    safe = liveness.safe_donation_set(blk, feeds, [loss.name, param])
    assert param not in safe
    # without the fetch the same param IS proven donatable — the pass is
    # not blanket-conservative
    assert param in liveness.safe_donation_set(blk, feeds, [loss.name])
    assert "PT500" not in codes_of(
        verify_program(main, fetch_names=[loss.name]))


def test_pt500_excluded_from_analyze_block_io():
    from paddle_tpu.executor import analyze_block_io

    main, startup, loss = _mlp_program()
    blk = main.global_block
    param = next(n for n in blk.vars if n.endswith(".w_0"))
    io = analyze_block_io(blk, {"x", "y"}, [loss.name, param])
    assert param not in io["donated"] and param in io["ro"]
    # updates still flow back to the scope via state_out
    assert param in io["state_out"]
    io2 = analyze_block_io(blk, {"x", "y"}, [loss.name])
    assert param in io2["donated"]


def test_pt501_write_after_fetch():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        h = fluid.layers.relu(x)
        blk = p.global_block
        blk.append_op("fetch", inputs={"X": [h.name]},
                      outputs={"Out": ["fetched_h"]}, attrs={})
        # rewrite h AFTER its fetch op: compiled steps fetch final values,
        # diverging from fetch-at-op-position semantics
        blk.append_op("scale", inputs={"X": [h.name]},
                      outputs={"Out": [h.name]}, attrs={"scale": 2.0})
    diags = verify_program(p, fetch_names=[h.name])
    d = next(d for d in diags if d.code == "PT501")
    assert h.name in d.message and d.severity == Severity.WARNING


def test_pt502_dead_op():
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        kept = fluid.layers.relu(x)
        fluid.layers.sigmoid(x)  # output never read, not fetched
    diags = verify_program(p, fetch_names=[kept.name])
    dead = [d for d in diags if d.code == "PT502"]
    assert len(dead) == 1 and dead[0].op_type == "sigmoid"
    assert dead[0].severity == Severity.INFO


def test_pt502_side_effect_op_is_not_dead():
    # a fetch op's output is observable outside the value graph (kind =
    # side_effect), so an unread output does not make the op dead
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        kept = fluid.layers.relu(x)
        p.global_block.append_op("fetch", inputs={"X": [kept.name]},
                                 outputs={"Out": ["fetch_sink"]}, attrs={})
    assert "PT502" not in codes_of(verify_program(p, fetch_names=[kept.name]))


def test_pt503_dead_var():
    p, blk, op = _tiny()
    blk.create_var(name="never_touched", shape=[3], dtype="float32")
    diags = verify_program(p)
    d = next(d for d in diags if d.code == "PT503")
    assert "never_touched" in d.message and d.severity == Severity.INFO


def test_pt504_persistable_rebound_in_sub_block():
    """A persistable written inside a sub-block that does NOT escape through
    the owning op's outputs: the compiled step's state threading only scans
    the global block, so the scope would silently never see the update."""
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        blk = p.global_block
        blk.create_var(name="stat", shape=[1], dtype="float32",
                       persistable=True)
        cv = fluid.layers.fill_constant([1], "bool", True)
        sub = p._create_block()
        sub.append_op("fill_constant", outputs={"Out": ["stat"]},
                      attrs={"shape": [1], "dtype": "float32", "value": 1.0})
        p._rollback()
        # owning while op does NOT list 'stat' in Out -> the write is lost
        blk.append_op("while", inputs={"X": [], "Condition": [cv.name]},
                      outputs={"Out": []},
                      attrs={"sub_block": sub.idx, "max_len": 1})
    diags = verify_program(p)
    d = next(d for d in diags if d.code == "PT504")
    assert "stat" in d.message and d.severity == Severity.ERROR
    with pytest.raises(ProgramVerificationError, match="PT504"):
        check_program(p)


def test_while_outer_var_stays_live_and_not_donatable():
    """Satellite: a while body reading an outer var must keep it live (no
    dead-op/dead-var false positive) and must never mark it donatable."""
    main, startup, out = _while_program()
    blk = main.global_block
    step_name = next(o.output_arg_names[0] for o in blk.ops
                     if o.type == "fill_constant"
                     and abs(o.attrs.get("value", 0) - 2.5) < 1e-9)

    diags = verify_program(main, fetch_names=[out.name])
    assert not errors_of(diags), format_diagnostics(diags)
    for d in diags:
        if d.code in ("PT502", "PT503"):
            assert step_name not in d.message, format_diagnostics([d])

    live = liveness.block_liveness(blk, (), [out.name])
    wi = next(i for i, o in enumerate(blk.ops) if o.type == "while")
    vl = live[step_name]
    # the sub-block read is charged at the while op's index
    assert wi in vl.uses
    assert vl.interval(len(blk.ops))[1] >= wi + 1
    assert step_name not in liveness.safe_donation_set(blk, (), [out.name])

    # the loop actually runs and agrees with the analysis: 4 * 2.5
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        (res,) = exe.run(main, fetch_list=[out.name])
    assert float(res[0]) == 10.0


def test_effect_classification():
    main, startup, loss = _mlp_program()
    kinds = {op.type: liveness.classify_op_effects(op).kind
             for op in main.global_block.ops}
    assert kinds["sgd"] == liveness.INPLACE
    assert kinds["mul"] == liveness.PURE
    wmain, _, _ = _while_program()
    wop = next(o for o in wmain.global_block.ops if o.type == "while")
    eff = liveness.classify_op_effects(wop)
    assert eff.kind == liveness.CONTROL_FLOW and not eff.eliminable
    p = fluid.Program()
    with un.guard(), fluid.program_guard(p, fluid.Program()):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        fluid.layers.dropout(x, dropout_prob=0.5)
    dop = next(o for o in p.global_block.ops if o.type == "dropout")
    assert liveness.classify_op_effects(dop).kind == liveness.RNG


def test_safe_donation_subset_of_heuristic_on_builtin_programs():
    """Acceptance: donation decisions are identical or strictly safer than
    the old state_in ∩ state_out heuristic on every tier-1 program — and
    not vacuously so: the mnist training program still donates its params."""
    import tools.lint_program as lint
    from paddle_tpu.executor import analyze_block_io

    donated_somewhere = False
    for name, prog, fetches in lint._builtin_programs():
        blk = prog.global_block
        feeds = {v.name for v in blk.vars.values() if v.is_data}
        io = analyze_block_io(blk, feeds, fetches)
        old_heuristic = {n for n in io["state_in"] if n in io["state_out"]}
        assert set(io["donated"]) <= old_heuristic, name
        donated_somewhere = donated_somewhere or bool(io["donated"])
    assert donated_somewhere


def test_memory_plan_within_2x_of_actual_bytes():
    """Acceptance: plan peak bytes within 2x of actual live array bytes on a
    small traced program (feed + params + fetch, all fp32)."""
    main, startup = fluid.Program(), fluid.Program()
    with un.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[256], dtype="float32")
        h = fluid.layers.fc(x, 128, bias_attr=False)
        out = fluid.layers.scale(h, scale=2.0)
    batch = 64
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.zeros((batch, 256), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        fetched = exe.run(main, feed=feed, fetch_list=[out.name])
        actual = sum(np.asarray(scope.find_var(n)).nbytes
                     for n in scope.vars)
    actual += feed["x"].nbytes + fetched[0].nbytes
    actual += batch * 128 * 4  # the single live intermediate (h)
    plan = main.memory_plan(feed_names=["x"], fetch_names=[out.name],
                            batch_size=batch)
    assert actual / 2 <= plan.peak_bytes <= actual * 2, (
        f"plan {plan.peak_bytes} vs actual {actual}")
    # the breakdown classifies the fc weight as weight, the feed as
    # activation, and the hot-spot list leads with the largest buffer
    at_peak = plan.by_class_at(plan.peak_op_idx)
    assert at_peak.get("weight", 0) == 256 * 128 * 4
    hot = plan.top_hot_spots(3)
    assert hot and hot[0].bytes == max(e.bytes for e in plan.entries)


def test_memory_plan_while_subblock_charged():
    main, startup, out = _while_program()
    plan = main.memory_plan(fetch_names=[out.name], batch_size=1)
    assert plan.sub_plans, "while sub-block must be planned"
    wi = next(i for i, o in enumerate(main.global_block.ops)
              if o.type == "while")
    assert wi in plan.sub_plans
    assert plan.timeline[wi] >= plan.sub_plans[wi].peak_bytes


def test_fetch_updated_param_regression():
    """Satellite: Executor.run fetching a parameter the step updates must
    return the post-step value AND leave the scope consistent — under the
    old heuristic the param's buffer was donated while fetched."""
    main, startup, loss = _mlp_program()
    blk = main.global_block
    param = next(n for n in blk.vars if n.endswith(".w_0"))
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    feed = {"x": np.random.RandomState(0).randn(8, 4).astype(np.float32),
            "y": np.ones((8, 1), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = scope.numpy(param).copy()
        loss1, w_fetched = exe.run(main, feed=feed,
                                   fetch_list=[loss.name, param])
        w_scope = scope.numpy(param)
        # the fetch observes the post-update value, same as the scope
        np.testing.assert_array_equal(w_fetched, w_scope)
        assert not np.array_equal(w_fetched, w0), "SGD must move the param"
        # second step: scope state chains, no consumed-buffer error
        loss2, w_fetched2 = exe.run(main, feed=feed,
                                    fetch_list=[loss.name, param])
        np.testing.assert_array_equal(w_fetched2, scope.numpy(param))
        assert float(np.ravel(loss2)[0]) < float(np.ravel(loss1)[0])


# ---------------------------------------------------------------------------
# pass 5: registry audit
# ---------------------------------------------------------------------------

def test_registry_audit_full_coverage():
    rows = audit_registry()
    assert len(rows) > 200
    summary = coverage_summary(rows)
    # every registered op must carry a lower rule (the PT300 invariant,
    # CI-gated via tools/audit_registry.py --strict)
    real = [r for r in rows if not r["op"].startswith("pt_lint_")]
    assert all(r["lower"] for r in real)
    assert summary["differentiable"] > 100
    table = format_audit(rows)
    assert "relu" in table and "infer_shape" in table


def test_registry_audit_test_references():
    import os

    rows = audit_registry(test_dir=os.path.dirname(__file__))
    by_op = {r["op"]: r for r in rows}
    assert by_op["relu"]["tested"] is True
    summary = coverage_summary(rows)
    assert summary["tested"] is not None and summary["tested"] > 100


def test_lint_cli_flags_errors(tmp_path, capsys):
    import tools.lint_program as lint

    main, startup, loss = _mlp_program()
    op = next(o for o in main.global_block.ops if o.type == "relu")
    del op.inputs["X"]  # survives serde (the ctor only checks op types)
    bad = tmp_path / "bad.json"
    bad.write_text(main.to_json())
    good = tmp_path / "good.json"
    good.write_text(startup.to_json())
    assert lint.main([str(good)]) == 0
    assert lint.main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "PT101" in out
