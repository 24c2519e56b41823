"""Static SPMD sharding analysis (analysis/sharding_check.py, ISSUE 12):
positive + negative controls for every PT730-PT744 code, spec propagation
over the real zoo layouts, per-chip memory plans (incl. while sub-blocks),
collective wire volumes and the comms gauges."""
import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.unique_name as un
from paddle_tpu import monitor
from paddle_tpu.analysis import default_pass_manager
from paddle_tpu.analysis.cost_model import (comms_compute_ratio,
                                            estimate_comms, estimate_cost)
from paddle_tpu.analysis.sharding_check import (propagate_sharding,
                                                spec_divisor,
                                                staging_bytes_by_op)
from paddle_tpu.parallel.sharding import extract_param_specs, zero1_spec_for


def codes(analysis):
    return {d.code for d in analysis.diagnostics}


def run(program, mesh, specs=None, fetches=(), feed_spec=None, batch=8,
        **kw):
    return propagate_sharding(program, mesh, param_specs=specs,
                              feed_spec=feed_spec, fetch_names=fetches,
                              batch_size=batch, **kw)


def _param_program(*params, builder=None):
    """Program with the given (name, shape) f32 params and an optional
    builder(block, vars) appending ops."""
    with un.guard():
        main = fluid.Program()
        gb = main.global_block
        vars_ = {}
        for name, shape in params:
            vars_[name] = gb.create_parameter(name, list(shape), "float32")
        if builder is not None:
            builder(gb, vars_)
    return main


# ---------------------------------------------------------------------------
# PT730-PT733: the input-spec contract
# ---------------------------------------------------------------------------

def test_pt730_unknown_mesh_axis():
    p = _param_program(("w", (8, 4)))
    an = run(p, {"dp": 2}, {"w": ("tp",)})
    assert "PT730" in codes(an)
    assert an.param_specs["w"] == (None, None)  # degraded, not crashed
    an2 = run(p, {"dp": 2}, {"w": ("dp",)})
    assert "PT730" not in codes(an2)


def test_pt731_spec_rank_exceeds_var_rank():
    p = _param_program(("w", (8, 4)))
    an = run(p, {"dp": 2}, {"w": ("dp", None, None)})
    assert "PT731" in codes(an)
    assert "PT731" not in codes(run(p, {"dp": 2}, {"w": ("dp", None)}))


def test_pt732_axis_reused_across_dims():
    p = _param_program(("w", (8, 4)))
    an = run(p, {"dp": 2}, {"w": ("dp", "dp")})
    assert "PT732" in codes(an)
    # first use wins, second degrades
    assert an.param_specs["w"] == ("dp", None)
    assert "PT732" not in codes(run(p, {"dp": 2}, {"w": ("dp", None)}))


def test_pt733_indivisible_static_dim():
    p = _param_program(("w", (10, 4)))
    an = run(p, {"dp": 4}, {"w": ("dp",)})
    assert "PT733" in codes(an)
    assert an.param_specs["w"] == (None, None)  # kept whole
    p2 = _param_program(("w", (8, 4)))
    assert "PT733" not in codes(run(p2, {"dp": 4}, {"w": ("dp",)}))


def test_pt733_dynamic_dim_is_runtime_contract():
    """A -1 batch dim is resolved at feed time — no static indivisibility
    error (the per-chip plan re-checks at the resolved batch)."""
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            fluid.layers.scale(x, 2.0)
    an = run(main, {"dp": 8}, batch=2)   # resolved batch NOT divisible
    assert "PT733" not in codes(an)


# ---------------------------------------------------------------------------
# PT734/PT735: inconsistent and unsatisfiable input layouts
# ---------------------------------------------------------------------------

def _add_program(spec_a, spec_b):
    def build(gb, v):
        out = gb.create_var(name="out", shape=(8, 8), dtype="float32")
        gb.append_op("elementwise_add", {"X": "a", "Y": "b"},
                     {"Out": "out"}, {"axis": -1})
    p = _param_program(("a", (8, 8)), ("b", (8, 8)), builder=build)
    return p, {"a": spec_a, "b": spec_b}


def test_pt734_conflicting_elementwise_inputs():
    p, specs = _add_program(("dp",), ("tp",))
    an = run(p, {"dp": 2, "tp": 2}, specs)
    assert "PT734" in codes(an)
    # the losing input pays a reshard
    assert any(c.kind == "reshard" for c in an.collectives)
    p2, specs2 = _add_program(("dp",), ("dp",))
    assert "PT734" not in codes(run(p2, {"dp": 2, "tp": 2}, specs2))


def _matmul_program(spec_x, spec_y):
    def build(gb, v):
        gb.create_var(name="out", shape=(4, 4), dtype="float32")
        gb.append_op("matmul", {"X": "x", "Y": "y"}, {"Out": "out"},
                     {"transpose_X": False, "transpose_Y": False})
    p = _param_program(("x", (4, 8)), ("y", (8, 4)), builder=build)
    return p, {"x": spec_x, "y": spec_y}


def test_pt735_contraction_layout_conflict():
    p, specs = _matmul_program((None, "dp"), ("tp", None))
    an = run(p, {"dp": 2, "tp": 2}, specs)
    assert "PT735" in codes(an)
    # agreeing contraction shardings are a partial sum, not a conflict
    p2, specs2 = _matmul_program((None, "dp"), ("dp", None))
    an2 = run(p2, {"dp": 2, "tp": 2}, specs2)
    assert "PT735" not in codes(an2)
    assert any(c.kind == "all_reduce" and c.var == "out"
               for c in an2.collectives)


# ---------------------------------------------------------------------------
# PT736: implicit full replication of a large tensor
# ---------------------------------------------------------------------------

def _reshape_fold_program():
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[64, 512], dtype="float32",
                                  append_batch_size=False)
            fluid.layers.reshape(x, shape=[64 * 512])
    return main


def test_pt736_large_tensor_replicated():
    an = run(_reshape_fold_program(), {"dp": 8}, batch=64, large_bytes=1024)
    assert "PT736" in codes(an)
    # the lost batch axis costs an all-gather of the input
    assert any(c.kind == "all_gather" for c in an.collectives)
    # raising the threshold silences it (and nothing else fires)
    an2 = run(_reshape_fold_program(), {"dp": 8}, batch=64,
              large_bytes=1 << 30)
    assert "PT736" not in codes(an2)


def test_pt736_not_fired_when_collective_explains_it():
    """A DP grad all-reduce produces a replicated grad by contract — the
    recorded collective explains the replication, no PT736."""
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[512], dtype="float32")
            y = fluid.layers.fc(x, 512, bias_attr=False, name="big")
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
    an = run(main, {"dp": 8}, batch=64, fetches=[loss.name],
             large_bytes=1024)
    assert any(c.kind == "all_reduce" and c.var.endswith("@GRAD")
               for c in an.collectives)
    assert "PT736" not in codes(an)


# ---------------------------------------------------------------------------
# PT737/PT741: resharding inside the training loop / donation invalidated
# ---------------------------------------------------------------------------

def _state_reshard_program(p_spec):
    def build(gb, v):
        gb.create_var(name="z", shape=(8, 4), dtype="float32")
        # read w (so it is live-in and donation-eligible) ...
        gb.append_op("elementwise_add", {"X": "w", "Y": "w"}, {"Out": "z"},
                     {"axis": -1})
        # ... then overwrite it from another layout
        gb.append_op("assign", {"X": "p"}, {"Out": "w"})
    return _param_program(("w", (8, 4)), ("p", (8, 4)), builder=build), \
        {"w": ("dp",), "p": p_spec}


def test_pt737_pt741_state_layout_change():
    from paddle_tpu.analysis.liveness import _donation_analysis

    p, specs = _state_reshard_program(())          # p replicated
    cands, unsafe, _live = _donation_analysis(p.global_block, [], [])
    an = run(p, {"dp": 2}, specs,
             liveness_info={"cands": cands, "unsafe": unsafe})
    assert "PT737" in codes(an)
    assert "PT741" in codes(an)
    # same layout in and out: neither fires
    p2, specs2 = _state_reshard_program(("dp",))
    cands2, unsafe2, _ = _donation_analysis(p2.global_block, [], [])
    an2 = run(p2, {"dp": 2}, specs2,
              liveness_info={"cands": cands2, "unsafe": unsafe2})
    assert "PT737" not in codes(an2)
    assert "PT741" not in codes(an2)


# ---------------------------------------------------------------------------
# PT738/PT739/PT740: the optimizer update layouts
# ---------------------------------------------------------------------------

def _sgd_program(grad_spec):
    def build(gb, v):
        gb.create_var(name="lr", shape=(1,), dtype="float32",
                      persistable=True)
        gb.append_op("sgd", {"Param": "w", "Grad": "g",
                             "LearningRate": "lr"}, {"ParamOut": "w"})
    p = _param_program(("w", (8, 4)), ("g", (8, 4)), builder=build)
    return p, {"g": grad_spec} if grad_spec else {}


def test_pt738_grad_param_layout_disagreement():
    p, specs = _sgd_program(("dp",))
    an = run(p, {"dp": 2}, specs)
    assert "PT738" in codes(an)
    p2, specs2 = _sgd_program(None)
    assert "PT738" not in codes(run(p2, {"dp": 2}, specs2))


def _momentum_program(vel_spec):
    def build(gb, v):
        gb.create_var(name="lr", shape=(1,), dtype="float32",
                      persistable=True)
        gb.append_op("momentum",
                     {"Param": "w", "Grad": "g", "Velocity": "vel",
                      "LearningRate": "lr"},
                     {"ParamOut": "w", "VelocityOut": "vel"},
                     {"mu": 0.9})
    p = _param_program(("w", (8, 8)), ("g", (8, 8)), ("vel", (8, 8)),
                       builder=build)
    return p, {"vel": vel_spec}


def test_pt739_non_zero_state_layout():
    # dim-1 sharded state is NOT the ZeRO dim-0-over-dp pattern
    p, specs = _momentum_program((None, "dp"))
    an = run(p, {"dp": 2}, specs)
    assert "PT739" in codes(an)
    assert "PT740" not in codes(an)


def test_pt740_zero_layout_recognized():
    p, specs = _momentum_program(("dp",))
    an = run(p, {"dp": 2}, specs)
    assert "PT740" in codes(an)
    assert "PT739" not in codes(an)
    kinds = {c.kind for c in an.collectives}
    assert "reduce_scatter" in kinds and "all_gather" in kinds


def test_pt740_zero_rewrites_grad_all_reduce():
    """Under the ZeRO layout the grad's DP all-reduce becomes a
    reduce-scatter (plus the param all-gather) — never both an AR and an
    RS for the same grad."""
    with un.guard():
        m = fluid.Program()
        with fluid.program_guard(m, fluid.Program()):
            x = fluid.layers.data("x", shape=[16], dtype="float32")
            y = fluid.layers.fc(x, 8, bias_attr=False, name="zf")
            loss = fluid.layers.mean(y)
            fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    specs, feed_spec = extract_param_specs(m, {"dp": 8}, zero=True)
    assert any(v == ("dp",) for v in specs.values())
    an = run(m, {"dp": 8}, specs, fetches=[loss.name], batch=16)
    assert "PT740" in codes(an)
    grads_ar = {c.var for c in an.collectives if c.kind == "all_reduce"}
    grads_rs = {c.var for c in an.collectives if c.kind == "reduce_scatter"}
    assert not (grads_ar & grads_rs)
    assert any(v.endswith("@GRAD") for v in grads_rs)


# ---------------------------------------------------------------------------
# PT742/PT743/PT744
# ---------------------------------------------------------------------------

def _fc_loss_program():
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, 4, name="f")
            loss = fluid.layers.mean(y)
    return main, y.name, loss.name


def test_pt742_feed_not_dp_sharded():
    main, _, loss = _fc_loss_program()
    an = run(main, {"dp": 8}, feed_spec=(), fetches=[loss], batch=16)
    assert "PT742" in codes(an)
    an2 = run(main, {"dp": 8}, fetches=[loss], batch=16)  # default ('dp',)
    assert "PT742" not in codes(an2)


def test_pt743_sharded_fetch():
    main, y, loss = _fc_loss_program()
    an = run(main, {"dp": 8}, fetches=[y], batch=16)
    assert "PT743" in codes(an)
    assert any(c.kind == "all_gather" and c.var == y
               for c in an.collectives)
    # a replicated fetch (post-reduction loss) is fine
    an2 = run(main, {"dp": 8}, fetches=[loss], batch=16)
    assert "PT743" not in codes(an2)


def test_pt744_unknown_op_conservative():
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            gb = main.global_block
            gb.create_var(name="shp", shape=(2,), dtype="int64")
            gb.append_op("shape", {"Input": x.name}, {"Out": "shp"})
    an = run(main, {"dp": 8}, batch=16)
    assert "PT744" in codes(an)
    assert an.spec_of("shp") == (None,)
    # with the feed replicated nothing is being dropped -> silent
    an2 = run(main, {"dp": 8}, feed_spec=(), batch=16)
    assert "PT744" not in codes(an2)


def test_known_reductions_do_not_pt744():
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            loss = fluid.layers.mean(x)
    an = run(main, {"dp": 8}, fetches=[loss.name], batch=16)
    assert "PT744" not in codes(an)
    assert any(c.kind == "all_reduce" and c.var == loss.name
               for c in an.collectives)


# ---------------------------------------------------------------------------
# propagation over real programs
# ---------------------------------------------------------------------------

def test_dp_grad_all_reduce_derived_for_every_param():
    """Data parallelism's defining collective — one all-reduce (or ZeRO
    reduce-scatter) per param grad — falls out of spec propagation."""
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    an = run(m["main"], {"dp": 8}, fetches=[m["loss"].name], batch=64)
    params = {p.name for p in m["main"].all_parameters()}
    reduced = {c.var[:-len("@GRAD")] for c in an.collectives
               if c.kind == "all_reduce" and c.var.endswith("@GRAD")}
    assert params == reduced
    # grad AR bytes equal the param bytes
    by_var = {c.var: c.bytes_full for c in an.collectives
              if c.kind == "all_reduce"}
    assert by_var["fc_0.w_0@GRAD"] == 784 * 200 * 4


def test_batch_spec_propagates_through_transformer():
    from paddle_tpu.models import BertConfig, build_bert_pretrain

    with un.guard():
        m = build_bert_pretrain(BertConfig.tiny(), seq_len=32)
    an = run(m["main"], {"dp": 8}, fetches=[m["loss"].name], batch=64)
    # no errors, and the batch axis survives the whole encoder stack —
    # embeddings, reshape/transpose head splits, fused attention, FFN:
    # the bulk of the activations stay dp-sharded
    assert not any(d.severity == "error" for d in an.diagnostics)
    params = {p.name for p in m["main"].all_parameters()}
    sharded_acts = [n for n, s in an.var_specs.items()
                    if s[:1] == ("dp",) and n not in params]
    assert len(sharded_acts) > 50, sharded_acts
    # attention outputs specifically (deepest layer)
    assert any(n.startswith("fused_multihead_attention_1")
               for n in sharded_acts)


def test_zoo_is_pt73x_clean_under_dp8_zero():
    """The lint-gate contract, as a test: training-zoo programs produce
    no gating PT73x findings under the dp=8 ZeRO assignment."""
    from paddle_tpu.models import build_deepfm

    with un.guard():
        m = build_deepfm()
    specs, _ = extract_param_specs(m["main"], {"dp": 8}, zero=True)
    an = run(m["main"], {"dp": 8}, specs,
             fetches=[m["loss"].name], batch=64)
    gating = {d.code for d in an.diagnostics
              if d.code in ("PT730", "PT731", "PT732", "PT733", "PT734",
                            "PT735", "PT736", "PT737", "PT738", "PT739",
                            "PT741", "PT742")}
    assert not gating, gating


def test_shared_subblock_collectives_counted_once():
    """seq2seq's recurrent bodies are each referenced by BOTH the forward
    recurrent op and recurrent_grad — propagation must walk a block once
    (the liveness _seen guard), never double-recording its collectives."""
    from paddle_tpu.models import build_seq2seq_train

    with un.guard():
        m = build_seq2seq_train(src_vocab=50, tgt_vocab=50)
    owners = {}
    for blk in m["main"].blocks:
        for op in blk.ops:
            sub = op.attrs.get("sub_block")
            if isinstance(sub, int):
                owners.setdefault(sub, []).append(op.type)
    assert any(len(v) > 1 for v in owners.values()), \
        "precondition: seq2seq shares sub-blocks between fwd and grad ops"
    an = run(m["main"], {"dp": 8}, fetches=[m["loss"].name], batch=64)
    seen = {}
    for c in an.collectives:
        key = (c.block_idx, c.op_idx, c.kind, c.var)
        assert key not in seen, f"collective recorded twice: {key}"
        seen[key] = c


def test_registered_pass_requires_liveness_and_noop_without_mesh():
    main, _, loss = _fc_loss_program()
    mgr = default_pass_manager()
    res = mgr.run_pipeline(main, ("sharding_check",), fetch_names=[loss],
                           verify="none")
    assert res.values["sharding_check"] is None
    assert not [d for d in res.diagnostics if d.code.startswith("PT73")]
    res2 = mgr.run_pipeline(main, ("sharding_check",), fetch_names=[loss],
                            batch_size=16,
                            options={"mesh": {"dp": 8}}, verify="none")
    an = res2.values["sharding_check"]
    assert an is not None and an.mesh == {"dp": 8}
    assert res2.context.has_analysis("liveness")  # the declared dependency


# ---------------------------------------------------------------------------
# per-chip memory plans
# ---------------------------------------------------------------------------

def test_single_device_plan_bit_identical():
    """The mesh=None path must be byte-identical to the pre-sharding
    planner: no spec keys in entries, no mesh keys in the dict."""
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    fetches = [m["loss"].name, m["acc"].name]
    p1 = m["main"].memory_plan(fetch_names=fetches, batch_size=64)
    p2 = m["main"].memory_plan(fetch_names=fetches, batch_size=64)
    assert p1.to_dict() == p2.to_dict()
    assert p1.mesh is None and p1.staging_timeline is None
    assert all("spec" not in e.to_dict() for e in p1.entries)


def test_per_chip_plan_divides_sharded_state():
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp(optimizer="adam")
    fetches = [m["loss"].name, m["acc"].name]
    plain = m["main"].memory_plan(fetch_names=fetches, batch_size=64)
    specs, _ = extract_param_specs(m["main"], {"dp": 8}, zero=True)
    chip = m["main"].memory_plan(fetch_names=fetches, batch_size=64,
                                 mesh={"dp": 8}, specs=specs)
    assert chip.mesh == {"dp": 8}
    assert chip.peak_bytes < plain.peak_bytes
    ent = {e.name: e for e in chip.entries}
    mom = next(e for n, e in ent.items() if n.startswith("moment1_fc_0.w"))
    assert mom.spec[:1] == ("dp",)
    assert mom.global_bytes == mom.bytes * 8
    # replicated params count whole
    w = ent["fc_0.w_0"]
    assert w.bytes == w.global_bytes
    # dp-sharded feed divides by 8
    img = ent["img"]
    assert img.global_bytes == img.bytes * 8


def test_per_chip_plan_includes_collective_staging():
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    fetches = [m["loss"].name]
    plan = m["main"].memory_plan(fetch_names=fetches, batch_size=64,
                                 mesh={"dp": 8})
    assert plan.staging_timeline is not None
    assert max(plan.staging_timeline) > 0
    st = staging_bytes_by_op(plan.sharding)
    (bidx, oi), nbytes = max(st.items(), key=lambda kv: kv[1])
    assert bidx == 0
    assert plan.staging_timeline[oi] >= nbytes


def test_per_chip_while_subblock_not_undercounted():
    """The conservative sub-block capture: sub-block-local vars carry no
    spec and count whole, and the sub-block peak still lands on the
    owning op — per-chip never under-counts the loop body."""
    from tests.test_while_grad import _build_while

    main, startup, loss = _build_while()
    plain = main.memory_plan(fetch_names=[loss.name], batch_size=16)
    chip = main.memory_plan(fetch_names=[loss.name], batch_size=16,
                            mesh={"dp": 4})
    assert plain.sub_plans and chip.sub_plans
    for oi, sub in chip.sub_plans.items():
        assert sub.mesh == {"dp": 4}
        # every sub-block entry either carries a propagated spec or is
        # counted at FULL size (never silently divided)
        for e in sub.entries:
            if not e.spec or all(a is None for a in e.spec):
                assert e.bytes == e.global_bytes
        # the owning op's timeline point carries the sub-block peak
        assert chip.timeline[oi] >= sub.peak_bytes
    # x is [T, B, D] with a STATIC leading dim — not batch sharded, so
    # the while program per-chip peak equals the single-device peak for
    # the sub-block portion (conservative, not divided)
    for oi in plain.sub_plans:
        assert chip.sub_plans[oi].peak_bytes == plain.sub_plans[oi].peak_bytes


# ---------------------------------------------------------------------------
# collective cost model + gauges
# ---------------------------------------------------------------------------

def test_wire_volume_formulas():
    from paddle_tpu.analysis.sharding_check import (CollectiveEvent,
                                                    ShardingAnalysis)

    an = ShardingAnalysis(
        mesh={"dp": 8}, batch_size=1, var_specs={}, param_specs={},
        feed_spec=(), diagnostics=[],
        collectives=[
            CollectiveEvent(0, 0, "all_reduce", "dp", "g", 800, ""),
            CollectiveEvent(0, 1, "all_gather", "dp", "p", 800, ""),
            CollectiveEvent(0, 2, "reduce_scatter", "dp", "h", 800, ""),
        ])
    comms = estimate_comms(an)
    # ring: AR = 2*(n-1)/n, AG/RS = (n-1)/n
    assert comms.wire_bytes_by_kind["all_reduce"] == int(800 * 2 * 7 / 8)
    assert comms.wire_bytes_by_kind["all_gather"] == int(800 * 7 / 8)
    assert comms.wire_bytes_by_kind["reduce_scatter"] == int(800 * 7 / 8)
    assert comms.total_wire_bytes == sum(comms.wire_bytes_by_kind.values())


def test_comms_compute_ratio_scales_with_bandwidth():
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    an = run(m["main"], {"dp": 8}, fetches=[m["loss"].name], batch=64)
    comms = estimate_comms(an)
    cost = estimate_cost(m["main"], batch_size=64)
    r_slow = comms_compute_ratio(comms, cost, peak_tflops=100.0,
                                 ici_gbytes_per_s=10.0)
    r_fast = comms_compute_ratio(comms, cost, peak_tflops=100.0,
                                 ici_gbytes_per_s=100.0)
    assert r_slow == pytest.approx(10.0 * r_fast)
    assert r_fast > 0


def test_observe_comms_cost_gauges():
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    monitor.reset()
    an = run(m["main"], {"dp": 8}, fetches=[m["loss"].name], batch=64)
    comms = estimate_comms(an)
    cost = estimate_cost(m["main"], batch_size=64)
    from paddle_tpu.analysis.cost_model import DEVICE_PEAKS

    serial = str(m["main"]._serial)
    # a mesh of chips the peaks table does not list: volume yes, ratio no
    monitor.observe_comms_cost(m["main"], comms, cost, device_kind="cpu")
    g = monitor.metric_value("executor_comms_gbytes_per_step",
                             program=serial, mesh="dp=8")
    assert g == pytest.approx(comms.gbytes_per_step)
    assert monitor.metric_value("executor_comms_compute_ratio", None,
                                program=serial, mesh="dp=8") is None
    monitor.observe_comms_cost(m["main"], comms, cost,
                               device_kind="TPU v5 lite")
    r = monitor.metric_value("executor_comms_compute_ratio",
                             program=serial, mesh="dp=8")
    assert r == pytest.approx(comms_compute_ratio(
        comms, cost, DEVICE_PEAKS["TPU v5 lite"].bf16_tflops))


def test_parallel_compile_emits_comms_gauges():
    """The CompiledProgram path records the predicted comms for the mesh
    it actually compiled (the monitor wiring, end to end)."""
    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8], dtype="float32")
            y = fluid.layers.fc(x, 4, name="cg")
            loss = fluid.layers.mean(y)
            fluid.optimizer.SGD(0.1).minimize(loss)
    monitor.reset()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name)
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(compiled,
                feed={"x": np.ones((16, 8), np.float32)},
                fetch_list=[loss.name])
    snap = monitor.get_registry().to_dict()
    fam = snap.get("executor_comms_gbytes_per_step")
    assert fam and fam["values"], "parallel compile did not record comms"


# ---------------------------------------------------------------------------
# spec extraction / runtime agreement
# ---------------------------------------------------------------------------

def test_zero1_spec_for_matches_build_rules():
    p = _param_program(("w", (8, 4)))
    v = p.global_block.var("w")
    assert zero1_spec_for(v, 1, True) == ()          # single device
    assert zero1_spec_for(v, 8, True) == ()          # not optimizer state
    v.is_optimizer_state = True
    assert zero1_spec_for(v, 8, True) == ("dp",)
    assert zero1_spec_for(v, 8, False) == ()         # AllReduce strategy
    assert zero1_spec_for(v, 16, True) == ()         # 8 % 16 indivisible
    v2 = p.global_block.create_var(name="emb", shape=(8, 4),
                                   dtype="float32", persistable=True)
    v2.is_distributed = True
    assert zero1_spec_for(v2, 8, False) == ("dp",)   # sharded table always


def test_extract_param_specs_zero_vs_allreduce():
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp(optimizer="adam")
    z, feed = extract_param_specs(m["main"], {"dp": 8}, zero=True)
    assert feed == ("dp",)
    assert any(n.startswith("moment") for n in z)
    assert all(s == ("dp",) for s in z.values())
    a, _ = extract_param_specs(m["main"], {"dp": 8}, zero=False)
    assert not any(n.startswith("moment") for n in a)


def test_spec_divisor_conservative_on_indivisible():
    assert spec_divisor(("dp",), {"dp": 8}, (16, 4)) == 8
    assert spec_divisor(("dp",), {"dp": 8}, (10, 4)) == 1   # kept whole
    assert spec_divisor(("dp", "tp"), {"dp": 2, "tp": 4}, (8, 8)) == 8
    assert spec_divisor((), {"dp": 8}, (16, 4)) == 1
    assert spec_divisor((None, "dp"), {"dp": 8}, (-1, 8), batch_size=4) == 8
    # one axis can split a value at most once — a malformed/composed spec
    # must never push the divisor past the mesh size (under-estimate)
    assert spec_divisor(("dp", "dp"), {"dp": 8}, (64, 64)) == 8


def test_composed_specs_never_reuse_an_axis():
    """A dp-sharded feed contracted against a param whose spec also uses
    dp must not compose to ('dp', 'dp') — the per-chip plan would divide
    by 64 on an 8-device mesh (the over-estimate invariant)."""
    with un.guard():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.layers.data("x", shape=[64], dtype="float32")
            y = fluid.layers.fc(x, 64, bias_attr=False, name="l1")
    an = run(main, {"dp": 8}, {"l1.w_0": (None, "dp")}, batch=64)
    for name, sp in an.var_specs.items():
        axes = [a for a in sp if a is not None]
        assert len(axes) == len(set(axes)), (name, sp)
    n = an.n_devices
    for name, sp in an.var_specs.items():
        v = main.global_block.vars.get(name)
        if v is not None and v.shape is not None:
            assert spec_divisor(sp, an.mesh, v.shape, 64) <= n, (name, sp)


def test_per_chip_class_breakdown_reconciles_with_peak():
    """by_class_at(peak) — including the collective_staging bucket — must
    sum to the reported per-chip peak (minus sub-block charges, which the
    sub_block bucket carries)."""
    from paddle_tpu.models.mlp import build_mnist_mlp

    with un.guard():
        m = build_mnist_mlp()
    plan = m["main"].memory_plan(fetch_names=[m["loss"].name],
                                 batch_size=64, mesh={"dp": 8})
    peak = plan.peak_op_idx
    assert sum(plan.by_class_at(peak).values()) == plan.timeline[peak]
    assert max(plan.staging_timeline) > 0
    assert "collective_staging" in plan.class_timeline
    # single-device plans never grow the bucket
    plain = m["main"].memory_plan(fetch_names=[m["loss"].name],
                                  batch_size=64)
    assert "collective_staging" not in plain.class_timeline
