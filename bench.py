"""Benchmark entry (driver contract): prints ONE JSON line.

Headline metric: ResNet-50 ImageNet TRAINING throughput (img/s) in bf16 via
the AMP policy — the BASELINE.json north-star metric. The reference publishes
no training numbers (BASELINE.md), so ``vs_baseline`` compares our bf16
INFERENCE latency against the reference's published ResNet50 bs=128 fp16
number (64.52 ms on 1x V100, paddle/contrib/float16/float16_benchmark.md:
41-45) — the only mixed-precision apples-to-apples figure that exists.

MEASUREMENT PROTOCOL: every timed section runs K data-dependent iterations
INSIDE one compiled dispatch via ``Executor.run_chained`` (a lax.scan over
the step — while-loop semantics serialize the bodies on-device), ends with a
host fetch, and removes the per-dispatch overhead by differencing two chain
lengths:

    per_step = (T(K_long) - T(K_short)) / (K_long - K_short)

Whether this is still needed against the plain loop (host clock around a
fetch; ``chip_smoke.py`` prints that figure) is ROADMAP A0's decision.

Feeds are staged on device once and reused every iteration (the DataLoader
double-buffers real input pipelines; reference BufferedReader does the same
on a side CUDA stream — reader/buffered_reader.cc).

The bench needs the chip: without an accelerator it raises
(``TPUPlace``), on a device that ``analysis.cost_model.DEVICE_PEAKS`` does
not list it raises, and a section that fails makes the exit code non-zero.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

REF_FP16_INFER_MS = 64.52  # V100 fp16 bs=128, float16_benchmark.md:41-45
RESNET50_TRAIN_GFLOP_PER_IMG = 3 * 4.1  # fwd ~4.1 GFLOP @224; bwd ~2x fwd

PROTOCOL = ("chained-scan per Executor.run_chained: K data-dependent steps "
            "in one dispatch, host fetch sync, per_step=(T_long-T_short)/"
            "(K_long-K_short), min over repeats")


def _device():
    """The chip under test; raises where JAX found no accelerator."""
    import paddle_tpu as fluid

    return fluid.TPUPlace().jax_device()


def _peak_tflops() -> float:
    """bf16 peak of the chip under test from THE peaks table; a device the
    table does not list is an error, not a default."""
    from paddle_tpu.analysis.cost_model import device_peak

    return device_peak(_device().device_kind).bf16_tflops


def time_chained(exe, program, feed, fetch_list, scope,
                 k_short=2, k_long=10, repeats=3):
    """Seconds per step by the chained protocol (module docstring),
    through the one shared implementation (tuning.chained_step_seconds) —
    bench, xla_sweep, fusion_check and measure_candidates must stay
    number-comparable."""
    from paddle_tpu import tuning

    return tuning.chained_step_seconds(exe, program, feed, fetch_list,
                                       scope, k_short=k_short,
                                       k_long=k_long, repeats=repeats)


def bench_resnet_train(amp: bool, batch=128, k_short=2, k_long=10):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import build_resnet

    model = build_resnet(depth=50, class_num=1000, amp=amp)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    dev = _device()
    feed = {"img": jax.device_put(
                rng.rand(batch, 3, 224, 224).astype(np.float32), dev),
            "label": jax.device_put(
                rng.randint(0, 1000, (batch, 1)).astype(np.int64), dev)}
    with fluid.scope_guard(scope):
        exe.run(model["startup"])
        dt = time_chained(exe, model["main"], feed, [model["loss"]], scope,
                          k_short, k_long)
    return batch / dt  # img/s


def bench_resnet_infer(amp: bool, batch=128, k_short=4, k_long=20,
                       fused: bool = False):
    """NOTE on the trajectory (docs/PERF_NOTES.md "The r05 infer
    discontinuity"): r03/r04 infer numbers timed pipelined async
    dispatches; r05 switched to the chained scan but the anti-hoisting
    chain did not engage for for_test programs whose only carried state is
    identity-written batch_norm statistics, so XLA could hoist the body
    and the differenced per-step time was unsound. The chain now engages
    for every non-training program — numbers from this round on are
    serialized per-step compute and NOT comparable to r03-r05."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import build_resnet

    from paddle_tpu.contrib import mixed_precision as mp

    model = build_resnet(depth=50, class_num=1000, build_optimizer=False)
    infer = model["main"].clone(for_test=True)
    if amp:
        mp.decorate_program(infer)  # forward-only bf16, no training graph
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    dev = _device()
    feed = {"img": jax.device_put(
                rng.rand(batch, 3, 224, 224).astype(np.float32), dev),
            "label": jax.device_put(
                rng.randint(0, 1000, (batch, 1)).astype(np.int64), dev)}
    logits = model["logits"].name
    prev = fluid.get_flags(["FLAGS_epilogue_fusion"])
    fluid.set_flags({"FLAGS_epilogue_fusion": fused})
    try:
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            dt = time_chained(exe, infer, feed, [logits], scope,
                              k_short, k_long)
    finally:
        fluid.set_flags(prev)
    return dt * 1e3  # ms/batch


def bench_bert_infer(batch=32, seq_len=512, k_short=2, k_long=8,
                     fused: bool = False):
    """BERT-base forward-only (the epilogue-fusion showcase: every
    q/k/v/out projection and FFN layer carries a mul+bias(+gelu) chain).
    ``fused=True`` runs the identical program under FLAGS_epilogue_fusion
    so the BENCH trajectory records the fused-vs-unfused win per round."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        synthetic_pretrain_batch)

    cfg = BertConfig.base()
    model = build_bert_pretrain(cfg, seq_len=seq_len, amp=True,
                                build_optimizer=False)
    infer = model["main"].clone(for_test=True)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    feed = {k: jax.device_put(v, _device()) for k, v in
            synthetic_pretrain_batch(cfg, batch, seq_len).items()}
    prev = fluid.get_flags(["FLAGS_epilogue_fusion"])
    fluid.set_flags({"FLAGS_epilogue_fusion": fused})
    try:
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            dt = time_chained(exe, infer, feed, [model["loss"].name],
                              scope, k_short, k_long)
    finally:
        fluid.set_flags(prev)
    return dt  # s/batch


def bench_bert_train(batch=32, seq_len=512, k_short=2, k_long=8,
                     use_flash=True, auto_remat=False):
    """BERT-base pretraining step. bs=32 fits the 16 GB chip without remat
    (VERDICT r4 reproduced the bs=64 HBM OOM); bs=64 needs
    ``auto_remat=True`` — FLAGS_auto_recompute segments the forward at
    layer boundaries and the memory planner picks the checkpoint set
    (analysis/remat.py; docs/PERF_NOTES.md)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        synthetic_pretrain_batch)

    prev_flash = fluid.get_flags(["FLAGS_use_flash_attention",
                                  "FLAGS_auto_recompute"])
    fluid.set_flags({"FLAGS_use_flash_attention": use_flash,
                     "FLAGS_auto_recompute": auto_remat})
    try:
        cfg = BertConfig.base()
        model = build_bert_pretrain(cfg, seq_len=seq_len, amp=True)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        feed = {k: jax.device_put(v, _device()) for k, v in
                synthetic_pretrain_batch(cfg, batch, seq_len).items()}
        n_params = 110e6  # BERT-base
        with fluid.scope_guard(scope):
            exe.run(model["startup"])
            dt = time_chained(exe, model["main"], feed, [model["loss"]],
                              scope, k_short, k_long)
    finally:
        fluid.set_flags(prev_flash)
    steps_per_s = 1.0 / dt
    # 6ND for the matmul path plus the attention-score term (QK^T + PV are
    # 4*B*S^2*hidden FLOPs/layer fwd, x3 with backward) which 6ND omits and
    # which is no longer negligible at seq 512.
    attn_flops = 3 * 4 * batch * seq_len**2 * cfg.hidden_size * cfg.num_layers
    tflops = (6 * n_params * batch * seq_len + attn_flops) * steps_per_s / 1e12
    return steps_per_s, tflops, batch, seq_len


def bench_gpt_decode(speculative: bool = False, n_requests: int = 8,
                     max_new: int = 56):
    """GPT-tiny generation tokens/s through the generative serving engine
    (ISSUE 20) — the decode headline. Single-stream latency-bound greedy
    traffic with a shared 12-token prefix, so the number reflects the
    real decode path: prefix-cache admission, chunked prefill, paged-KV
    decode chunks, and (``speculative=True``) k=8 draft-verify chunks
    committing up to 9 tokens per dispatch. Greedy speculative output is
    bit-exact vs plain by construction (tests + the load_check gate
    enforce it), so the two legs are directly comparable. Returns
    ``(tokens_per_s, generation_stats)``."""
    import paddle_tpu as fluid
    import paddle_tpu.unique_name as un
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GptConfig, build_gpt_generative

    with un.guard():
        net = build_gpt_generative(GptConfig.tiny(), batch_slots=4,
                                   max_seq=128, page_size=8,
                                   prompt_buckets=(8, 16), spec_k=8)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(net["startup"], scope=scope)
    eng = serving.GenerativeEngine(
        net, scope=scope, executor=exe,
        config=serving.ServingConfig(max_batch=4, queue_depth=64,
                                     deadline_s=0),
        gen_config=serving.GenerationConfig(decode_chunk=2,
                                            speculative=speculative))
    eng.warm_up()
    rng = np.random.RandomState(5)
    shared = rng.randint(1, 128, 12)
    toks, t0 = 0, time.time()
    with eng:
        for i in range(n_requests):
            p = np.concatenate([shared, rng.randint(1, 128, 2 + i % 3)])
            out = eng.submit(p, max_new_tokens=max_new) \
                .result(timeout=600)[0]
            toks += len(out)
    wall = time.time() - t0
    stats = eng.generation_stats()
    if not eng.accounting()["exact"] or stats["decode_recompiles"]:
        raise RuntimeError("decode bench integrity: accounting inexact "
                           "or warm recompiles observed")
    return toks / wall if wall > 0 else 0.0, stats


def main() -> int:
    """Sections run independently: one that RAISES never loses the others
    and the JSON line still prints — but the exit code is then non-zero (a
    section that hangs is still fatal — only the external driver's timeout
    can reap that)."""
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = _device()
    peak = _peak_tflops()
    extra = {"protocol": PROTOCOL,
             "device": {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}}
    failed = []

    # compile visibility for the BENCH trajectory: every compile the bench
    # pays is recorded via the monitor hook API (docs/OBSERVABILITY.md) so
    # a perf regression can be split into "compute got slower" vs "we
    # started recompiling"
    compile_log = []
    hook = monitor.add_hook(on_compile=lambda rec: compile_log.append(rec))

    def section(key, fn):
        t0 = time.time()
        try:
            val = fn()
            extra[f"{key}_bench_seconds"] = round(time.time() - t0, 1)
            return val
        except Exception as e:  # record, keep going, fail the run
            extra[f"{key}_error"] = f"{type(e).__name__}: {e}"[:200]
            failed.append(key)
            return None

    train_bf16 = section("resnet50_train_bf16",
                         lambda: bench_resnet_train(amp=True))
    infer_bf16_ms = section("resnet50_infer_bf16",
                            lambda: bench_resnet_infer(amp=True))
    # fused legs (FLAGS_epilogue_fusion): the MFU-gap round's win, recorded
    # per trajectory point. Training legs stay unfused BY DESIGN — the
    # fusion pass refuses backward-carrying programs (grad ops read the
    # epilogue intermediates); extra["fusion"] records that refusal
    # honestly instead of timing a no-op leg.
    infer_fused_ms = section("resnet50_infer_bf16_fused",
                             lambda: bench_resnet_infer(amp=True,
                                                        fused=True))
    bert_infer_s = section("bert_base_infer_bf16",
                           lambda: bench_bert_infer(fused=False))
    bert_infer_fused_s = section("bert_base_infer_bf16_fused",
                                 lambda: bench_bert_infer(fused=True))
    bert = section("bert", bench_bert_train)
    # the leg r5 said we could not reach: bs=64 needs auto-remat to fit
    # the 16 GB chip (bs=32 peak ~2x'd by doubling the batch)
    bert64 = section("bert_bs64_remat",
                     lambda: bench_bert_train(batch=64, auto_remat=True))
    # decode headline (ISSUE 20): tokens/s through the generative engine,
    # plain and speculative, plus the prefix-cache hit stats
    gpt_dec = section("gpt_tiny_decode", lambda: bench_gpt_decode(False))
    gpt_spec = section("gpt_tiny_decode_spec",
                       lambda: bench_gpt_decode(True))
    if gpt_dec is not None:
        tps_plain, dec_stats = gpt_dec
        extra["gpt_tiny_decode_tokens_per_s"] = round(tps_plain, 1)
        extra["prefix_cache"] = dec_stats["prefix_cache"]
    if gpt_spec is not None:
        tps_spec, spec_stats = gpt_spec
        extra["gpt_tiny_decode_spec_tokens_per_s"] = round(tps_spec, 1)
        extra["gpt_tiny_decode_spec"] = {
            "k": spec_stats["speculative"]["k"],
            "verify_chunks": spec_stats["speculative"]["chunks"],
            "accepted_tokens":
                spec_stats["speculative"]["accepted_tokens"],
        }
        if gpt_dec is not None and tps_plain > 0:
            extra["gpt_tiny_decode_spec_speedup"] = round(
                tps_spec / tps_plain, 3)

    if train_bf16 is not None:
        train_tflops = train_bf16 * RESNET50_TRAIN_GFLOP_PER_IMG / 1e3
        extra["resnet50_train_bf16_tflops"] = round(train_tflops, 1)
        extra["resnet50_train_mfu_vs_v5e_peak"] = round(
            train_tflops / peak, 3)
    if infer_bf16_ms is not None:
        extra["resnet50_infer_bs128_bf16_ms"] = round(infer_bf16_ms, 2)
        extra["ref_v100_fp16_infer_bs128_ms"] = REF_FP16_INFER_MS
        # r03-r05 infer values are NOT comparable: two generations of
        # broken serialization (async-dispatch pipelining, then a hoisted
        # scan body) — docs/PERF_NOTES.md "The r05 infer discontinuity"
        extra["infer_protocol"] = (
            "chained-v2: anti-hoisting chain forced for all non-training "
            "programs; r03-r05 infer points measured hoisted/pipelined "
            "bodies and are not comparable")
    if infer_fused_ms is not None:
        extra["resnet50_infer_bs128_bf16_fused_ms"] = round(infer_fused_ms,
                                                            2)
        if infer_bf16_ms:
            extra["resnet50_infer_fused_speedup"] = round(
                infer_bf16_ms / infer_fused_ms, 3)
    if bert_infer_s is not None:
        extra["bert_base_infer_bf16_ms"] = round(bert_infer_s * 1e3, 1)
    if bert_infer_fused_s is not None:
        extra["bert_base_infer_bf16_fused_ms"] = round(
            bert_infer_fused_s * 1e3, 1)
        if bert_infer_s:
            extra["bert_infer_fused_speedup"] = round(
                bert_infer_s / bert_infer_fused_s, 3)
    monitor.remove_hook(hook)
    extra["monitor"] = {
        "compiles": len(compile_log),
        "recompiles": monitor.recompile_count(),
        "compile_seconds_total": round(sum(
            (rec.trace_lower_s or 0) + (rec.compile_s or 0)
            for rec in compile_log), 2),
        "chained_iterations": int(monitor.metric_value(
            "executor_chained_iterations_total") or 0),
        "steps": {p: int(monitor.metric_value("executor_steps_total",
                                              path=p) or 0)
                  for p in ("run", "chained")},
    }

    # cost-model accounting (analysis/cost_model.py, this round): model
    # FLOPs derived from the programs' infer_shape metadata, reported
    # next to the hand-derived analytic counts (docs/PERF_NOTES.md "Cost
    # model"; the trace gate asserts the ratios stay within 10%). The
    # legacy headline keys keep their historical 1/MAC ResNet constant
    # for trajectory continuity; cost_model.* uses 2 FLOPs per MAC
    # everywhere (the 6ND convention the BERT legs always used).
    def _cost_section():
        import paddle_tpu.unique_name as un
        from paddle_tpu.analysis.cost_model import estimate_cost
        from paddle_tpu.models.bert import BertConfig, build_bert_pretrain
        from paddle_tpu.models.resnet import build_resnet

        cm = {"convention": "2 FLOPs per multiply-add (6ND)"}
        with un.guard():
            rn = build_resnet(depth=50, class_num=1000, amp=True)
        rep = estimate_cost(rn["main"], batch_size=128)
        per_img = rep.flops_total / 128
        leg = {"gflops_per_img": round(per_img / 1e9, 2),
               "analytic_gflops_per_img": 24.55,
               "vs_analytic_ratio": round(per_img / 24.55e9, 3),
               "flops_per_byte": round(rep.flops_per_byte, 1)}
        if train_bf16 is not None:
            tf = train_bf16 * per_img / 1e12
            leg["achieved_tflops"] = round(tf, 1)
            leg["mfu"] = round(tf / peak, 3)
        cm["resnet50_train_bs128"] = leg
        if bert is not None:
            b_steps, _tf, b_bs, b_sl = bert
            cfg = BertConfig.base()
            with un.guard():
                bm = build_bert_pretrain(cfg, seq_len=b_sl, amp=True)
            rep_b = estimate_cost(bm["main"], batch_size=b_bs)
            analytic = (6 * 110e6 * b_bs * b_sl
                        + 3 * 4 * b_bs * b_sl ** 2
                        * cfg.hidden_size * cfg.num_layers)
            tf_b = rep_b.flops_total * b_steps / 1e12
            cm[f"bert_base_train_bs{b_bs}"] = {
                "tflops_per_step": round(rep_b.flops_total / 1e12, 3),
                "analytic_tflops_per_step": round(analytic / 1e12, 3),
                "vs_analytic_ratio": round(rep_b.flops_total / analytic,
                                           3),
                "achieved_tflops": round(tf_b, 1),
                "mfu": round(tf_b / peak, 3),
                "flops_per_byte": round(rep_b.flops_per_byte, 1)}
        return cm

    section("cost_model", lambda: extra.update(
        {"cost_model": _cost_section()}))

    if bert is not None:
        bert_steps, bert_tflops, bert_bs, bert_sl = bert
        extra["bert_base_train_bf16_steps_per_s"] = round(bert_steps, 3)
        extra["bert_base_train_bf16_tflops"] = round(bert_tflops, 1)
        extra["bert_base_train_mfu_vs_v5e_peak"] = round(
            bert_tflops / peak, 3)
        extra["bert_batch"], extra["bert_seq_len"] = bert_bs, bert_sl
    if bert64 is not None:
        b64_steps, b64_tflops, b64_bs, b64_sl = bert64
        extra["bert_bs64_remat_train_bf16_steps_per_s"] = round(b64_steps, 3)
        extra["bert_bs64_remat_train_bf16_tflops"] = round(b64_tflops, 1)
        extra["bert_bs64_remat_train_mfu_vs_v5e_peak"] = round(
            b64_tflops / peak, 3)
        extra["bert_bs64_remat_batch"] = b64_bs
        extra["bert_bs64_remat_seq_len"] = b64_sl
    # memory trajectory (this round on): auto-remat activity + the memory
    # planner's predicted peaks for the last transformed program (the bs=64
    # BERT leg), so BENCH_*.json tracks memory alongside throughput
    # epilogue-fusion + autotuner trajectory: chains fused per epilogue
    # kind during the fused legs, plus the documented training-program
    # refusal (static, no timing cost)
    def _fusion_section():
        import paddle_tpu.unique_name as un
        from paddle_tpu.analysis.epilogue_fusion import fuse_epilogues
        from paddle_tpu.models.resnet import build_resnet

        fam = monitor.get_registry().to_dict().get(
            "fusion_ops_fused_total", {})
        by_kind = {v["labels"].get("epilogue", "?"): int(v["value"])
                   for v in fam.get("values", ())}
        with un.guard():
            train = build_resnet(depth=50, class_num=1000, amp=True)
        dec = fuse_epilogues(train["main"],
                             fetch_names=[train["loss"].name])
        return {
            "programs_applied": int(monitor.metric_value(
                "fusion_programs_total", outcome="applied") or 0),
            "programs_refused": int(monitor.metric_value(
                "fusion_programs_total", outcome="refused") or 0),
            "chains_by_epilogue": by_kind,
            "train_program_decision": {"applied": dec.applied,
                                       "reason": dec.reason},
        }

    section("fusion", lambda: extra.update({"fusion": _fusion_section()}))
    extra["autotune"] = {
        "hits": int(monitor.metric_value("autotune_hits_total") or 0),
        "misses": int(monitor.metric_value("autotune_misses_total") or 0),
        "trials": int(monitor.metric_value("autotune_trials_total") or 0),
    }
    extra["remat"] = {
        "programs_applied": int(monitor.metric_value(
            "remat_programs_total", outcome="applied") or 0),
        "programs_refused": int(monitor.metric_value(
            "remat_programs_total", outcome="refused") or 0),
        "segments_inserted": int(monitor.metric_value(
            "remat_segments_inserted_total") or 0),
        "predicted_peak_bytes_plain": int(monitor.metric_value(
            "remat_predicted_peak_bytes", variant="plain") or 0),
        "predicted_peak_bytes_remat": int(monitor.metric_value(
            "remat_predicted_peak_bytes", variant="remat") or 0),
    }

    print(json.dumps({
        "metric": "resnet50_train_bf16_img_per_s",
        "value": round(train_bf16, 1) if train_bf16 is not None else -1,
        "unit": "img/s/chip",
        "vs_baseline": (round(REF_FP16_INFER_MS / infer_bf16_ms, 3)
                        if infer_bf16_ms else -1),
        "extra": extra,
    }))
    if failed:
        print(f"bench: FAILED sections: {failed}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
