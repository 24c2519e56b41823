"""paddle_tpu.monitor — executor runtime metrics, recompilation diagnostics
and structured step tracing.

The reference stack's profiler/CUPTI layer (platform/profiler.h,
device_tracer.h) gave Fluid per-event visibility; this package is the
TPU-native equivalent for the rebuild's actual hot paths, which are
otherwise opaque: the jit compile cache, liveness-gated buffer donation,
and ``run_chained``. Three layers:

* ``registry`` — thread-safe counters/gauges/histograms with JSON and
  Prometheus-text exporters (``monitor.get_registry()``,
  ``monitor.metric_value()``).
* ``hooks`` — ``monitor.add_hook(on_step_begin=..., on_step_end=...,
  on_compile=...)`` subscription API fed by the executor.
* ``recompile`` — cache-miss diagnostics that name *which* cache-key
  component changed (program / feed_signature / fetch_list / scope /
  flags) with build-site attribution, and warn after
  ``FLAGS_recompile_warn_threshold`` recompiles of one program.

``paddle_tpu.resilience`` reports through the same registry:
``resilience_retries_total`` / ``resilience_giveups_total`` (transient-site
retry), ``resilience_faults_injected_total`` (FLAGS_fault_plan),
``steps_skipped_nonfinite_total`` (FLAGS_nan_inf_policy) and
``trainer_ckpt_fallback_total`` (torn-checkpoint recovery) — see
docs/RESILIENCE.md.

Everything is on by default (``FLAGS_monitor=0`` disables collection —
hooks, counters and diagnostics all go quiet). The executor's build and
compile stages additionally flow through ``profiler.RecordEvent`` so they
land in the host timeline (``tools/timeline.py``). ``tools/metrics_report.py`` dumps
``monitor.snapshot()`` as the CI metrics artifact and gates on unexpected
recompiles. Metric names and semantics: docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import itertools
import time
from typing import Any, Dict, Optional

from .hooks import (CompileRecord, Hook, StepRecord, add_hook, clear_hooks,
                    dispatch, remove_hook)
from .lockwitness import (make_condition, make_lock, make_rlock,
                          reset_witness, witness_cycles, witness_edges,
                          witness_enabled, witness_report)
from .numwitness import (containment_violations, first_offender,
                         numerics_witness_enabled, numerics_witness_report,
                         numerics_witness_vars, reset_numerics_witness)
from .promtext import (ParsedFamily, PromParseError,
                       histogram_snapshot_from_samples,
                       parse_prometheus_text)
from .recompile import RecompileTracker, build_site, get_tracker
from .registry import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                       MetricFamily, MetricsRegistry, counter, gauge,
                       get_registry, histogram,
                       merge_histogram_snapshots, metric_value,
                       snapshot_quantile)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricFamily", "MetricsRegistry",
    "StepRecord", "CompileRecord", "Hook", "RecompileTracker",
    "add_hook", "remove_hook", "clear_hooks", "get_registry", "counter",
    "gauge", "histogram", "metric_value", "enabled", "record_cache_lookup",
    "observe_compile", "complete_compile", "step_begin", "step_end",
    "record_pass", "record_remat",
    "record_watchdog_timeout",
    "program_cost", "observe_step_cost", "observe_serving_cost",
    "observe_comms_cost",
    "recompile_events",
    "recompile_count", "snapshot", "reset", "get_tracker", "build_site",
    "make_lock", "make_rlock", "make_condition", "witness_enabled",
    "witness_report", "witness_edges", "witness_cycles", "reset_witness",
    "numerics_witness_enabled", "numerics_witness_report",
    "numerics_witness_vars", "reset_numerics_witness", "first_offender",
    "containment_violations",
    # telemetry plane: exact histogram-snapshot algebra + the
    # scrape-side Prometheus text parser (docs/OBSERVABILITY.md
    # "Fleet telemetry plane")
    "merge_histogram_snapshots", "snapshot_quantile",
    "parse_prometheus_text", "histogram_snapshot_from_samples",
    "ParsedFamily", "PromParseError", "telemetry_enabled",
]

_step_counter = itertools.count()


def enabled() -> bool:
    """Collection master switch (``FLAGS_monitor``, default on)."""
    from ..flags import flag

    return bool(flag("monitor"))


def telemetry_enabled() -> bool:
    """Fleet telemetry plane master switch (``FLAGS_fleet_telemetry``,
    default OFF): gates the aggregator scrape thread and trace-exemplar
    capture — off must stay a hot-path no-op
    (docs/OBSERVABILITY.md "Fleet telemetry plane")."""
    from ..flags import flag

    return bool(flag("fleet_telemetry"))


# -- executor instrumentation entry points ---------------------------------
# (called from Executor.run / run_chained / CompiledProgram; every entry
# no-ops when FLAGS_monitor=0)

def record_cache_lookup(path: str, hit: bool) -> None:
    if not enabled():
        return
    counter("executor_cache_lookups_total",
            "compile-cache lookups by path and result").labels(
        path=path, result="hit" if hit else "miss").inc()


def observe_compile(path: str, program, components: Dict[str, Any],
                    donated_names=()) -> Optional[CompileRecord]:
    """Record a compile-cache miss: compile counters, recompile diagnosis
    (component diff + build site), static donated-bytes estimate from the
    program's var shapes (``memory_plan`` sizing). Returns the record so
    the caller can fill stage timings and fire ``complete_compile``."""
    if not enabled():
        return None
    serial = int(getattr(program, "_serial", -1))
    rec = get_tracker().observe(path, serial, build_site(program),
                                components)
    counter("executor_compiles_total",
            "compile-cache misses that built a new executable").labels(
        path=path).inc()
    if rec.recompile:
        counter("executor_recompiles_total",
                "compiles of a program that was already compiled — the "
                "TPU perf tripwire").labels(path=path).inc()
    try:
        from ..analysis.liveness import _var_bytes

        blk = program.global_block
        rec.donated_bytes_est = sum(
            _var_bytes(blk.var(n), 1)[0]
            for n in donated_names if blk.has_var(n))
    except Exception:
        pass
    return rec


def complete_compile(rec: Optional[CompileRecord],
                     trace_lower_s: Optional[float],
                     compile_s: Optional[float]) -> None:
    """Attach stage timings to a compile record, export them, and fire the
    ``on_compile`` hooks. Called once per compile, after the executable
    exists (or after stage timing failed — timings then stay None)."""
    if rec is None:
        return
    rec.trace_lower_s = trace_lower_s
    rec.compile_s = compile_s
    if trace_lower_s is not None:
        histogram("executor_compile_seconds",
                  "compile-stage wall time by stage").labels(
            stage="trace_lower").observe(trace_lower_s)
    if compile_s is not None:
        histogram("executor_compile_seconds",
                  "compile-stage wall time by stage").labels(
            stage="xla_compile").observe(compile_s)
    dispatch("compile", rec)


def step_begin(path: str, program) -> Optional[StepRecord]:
    if not enabled():
        return None
    rec = StepRecord(path=path,
                     program_serial=int(getattr(program, "_serial", -1)),
                     step_index=next(_step_counter))
    # non-field stash for the cost model (step_end turns duration +
    # batch_rows into MFU gauges); transient — dies with the record
    rec._program = program
    rec._t0 = time.perf_counter()
    dispatch("step_begin", rec)
    return rec


def step_end(rec: Optional[StepRecord]) -> None:
    if rec is None:
        return
    if rec.duration_s is None and hasattr(rec, "_t0"):
        rec.duration_s = time.perf_counter() - rec._t0
    p = {"path": rec.path}
    counter("executor_steps_total", "executor dispatches").labels(**p).inc()
    if rec.path == "chained":
        counter("executor_chained_iterations_total",
                "scanned iterations inside run_chained dispatches").inc(
            rec.iterations)
    if rec.duration_s is not None:
        histogram("executor_step_seconds",
                  "wall time of one executor dispatch (feed packing + "
                  "device step + state writeback)").labels(**p).observe(
            rec.duration_s)
        # the same wall split in two, exactly: the executor.fetch phase
        # (host blocked on the device's results + the copy back) and the
        # rest (host work around an asynchronous launch)
        histogram("executor_fetch_wait_seconds",
                  "of one dispatch's wall: the host waiting for and "
                  "copying back the fetches").labels(**p).observe(
            rec.fetch_wait_s)
        histogram("executor_host_seconds",
                  "of one dispatch's wall: everything but the fetch "
                  "wait").labels(**p).observe(
            rec.duration_s - rec.fetch_wait_s)
        prog = getattr(rec, "_program", None)
        if prog is not None and rec.batch_rows:
            observe_step_cost(prog, rec.batch_rows, rec.duration_s,
                              iterations=rec.iterations, path=rec.path,
                              device_kind=rec.device_kind)
    if rec.ready_t is not None and rec.launch_t is not None:
        # a dispatch that fetched. With the gap before it (to the fetch
        # return before it on the same Executor) the two tile that
        # executor's life from its first launch to its last fetch, exactly:
        # where dispatches overlap (a fetch taken later, behind the next
        # launch) each observes from the fetch return before its own, so
        # the sum is the union of the walls, and a launch behind a
        # dispatch still in flight observes no gap
        head = rec.launch_t if rec.head_t is None else rec.head_t
        histogram("executor_inflight_seconds",
                  "the time the device had this executor's work in flight: "
                  "launch call to fetch return of one dispatch, less what "
                  "an earlier dispatch's fetch return already covered "
                  "(the sum is the union of the dispatches' walls)").labels(
            **p).observe(rec.ready_t - head)
        if rec.prev_ready_t is not None:
            histogram("executor_starved_seconds",
                      "fetch return of the dispatch before to this "
                      "dispatch's launch call, where nothing else was in "
                      "flight: the time the device had nothing of this "
                      "executor's in flight").labels(
                **p).observe(rec.launch_t - rec.prev_ready_t)
    if rec.feed_bytes:
        counter("executor_feed_bytes_total",
                "host->device feed transfer bytes").inc(rec.feed_bytes)
    if rec.fetch_bytes:
        counter("executor_fetch_bytes_total",
                "device->host fetch transfer bytes").inc(rec.fetch_bytes)
    if rec.donated_buffers:
        counter("executor_donated_buffers_total",
                "state buffers donated to XLA (updated in place)").inc(
            rec.donated_buffers)
    if rec.kept_buffers:
        counter("executor_kept_buffers_total",
                "state buffers kept/copied (donation-unsafe)").inc(
            rec.kept_buffers)
    if rec.donated_bytes:
        counter("executor_donated_bytes_total",
                "live bytes of donated buffers").inc(rec.donated_bytes)
    dispatch("step_end", rec)


# -- cost model: per-(program, batch) FLOPs -> MFU gauges -------------------
# (analysis/cost_model.py; ROADMAP item 4's accounting — the monitor turns
# measured step durations into model-FLOP utilisation per program and
# shape bucket. Reports are cached: estimation walks the ops once per
# (program version, batch); steady-state steps pay one dict probe.)

_cost_cache: Dict[tuple, Any] = {}
_COST_CACHE_MAX = 64


def program_cost(program, batch: int):
    """The cached ``CostReport`` for ``program`` at ``batch`` rows, or
    ``None`` when estimation failed (never raises into a step)."""
    if not hasattr(program, "blocks"):
        # CompiledProgram wrapper on the parallel path
        program = getattr(program, "program", program)
        if not hasattr(program, "blocks"):
            return None
    key = (int(getattr(program, "_serial", -1)),
           int(getattr(program, "_version", 0)), int(batch))
    if key in _cost_cache:
        return _cost_cache[key]
    try:
        from ..analysis.cost_model import estimate_cost

        rep = estimate_cost(program, batch_size=batch)
    except Exception:
        rep = None
    # unlocked bounded eviction: two step threads can race here, so the
    # pop must tolerate the other thread winning ('never raises into a
    # step' is the contract)
    while len(_cost_cache) >= _COST_CACHE_MAX:
        try:
            _cost_cache.pop(next(iter(_cost_cache)), None)
        except (StopIteration, RuntimeError):
            break
    _cost_cache[key] = rep
    return rep


def _peak_tflops(device_kind: str) -> Optional[float]:
    """bf16 peak of ``device_kind`` from THE peaks table
    (``analysis.cost_model.DEVICE_PEAKS``); None for a device that is not
    in it — its MFU gauges are then not set at all."""
    from ..analysis.cost_model import DEVICE_PEAKS

    peak = DEVICE_PEAKS.get(device_kind)
    return peak.bf16_tflops if peak is not None else None


def observe_step_cost(program, batch: int, duration_s: float,
                      iterations: int = 1, path: str = "run",
                      device_kind: str = ""):
    """Turn one measured dispatch into the cost-model gauges:
    ``executor_model_gflops_per_step`` (static, per program+batch),
    ``executor_achieved_tflops`` and — only when ``device_kind`` (the
    device that ran the step) has an entry in the peaks table —
    ``executor_mfu`` (per path+program+batch). Returns the achieved TF/s,
    or None when disabled/unmeasurable."""
    if not enabled() or not duration_s or duration_s <= 0:
        return None
    rep = program_cost(program, batch)
    if rep is None or rep.flops_total <= 0:
        return None
    peak = _peak_tflops(device_kind)
    achieved = rep.flops_total * max(1, int(iterations)) / duration_s / 1e12
    labels = {"path": path,
              "program": str(int(getattr(program, "_serial", -1))),
              "batch": str(int(batch))}
    gauge("executor_model_gflops_per_step",
          "cost-model FLOPs of one step (GF, 2 FLOPs/MAC) by program "
          "and batch").labels(program=labels["program"],
                              batch=labels["batch"]).set(
        rep.flops_total / 1e9)
    gauge("executor_achieved_tflops",
          "achieved model TF/s of the most recent dispatch, by path/"
          "program/batch").labels(**labels).set(achieved)
    if peak is not None:
        gauge("executor_mfu",
              "model-FLOP utilisation of the most recent dispatch vs the "
              "published bf16 peak of the device that ran it").labels(
            **labels).set(achieved / peak)
    return achieved


def observe_serving_cost(program, padded_rows: int, batch_s: float,
                         bucket: str, device_kind: str = ""):
    """Serving flavour of :func:`observe_step_cost`: per shape-bucket
    ``serving_bucket_achieved_tflops`` / ``serving_bucket_mfu`` gauges
    from one dispatched batch's wall time (the MFU gauge only for a
    ``device_kind`` in the peaks table)."""
    if not enabled() or not batch_s or batch_s <= 0:
        return None
    rep = program_cost(program, padded_rows)
    if rep is None or rep.flops_total <= 0:
        return None
    peak = _peak_tflops(device_kind)
    achieved = rep.flops_total / batch_s / 1e12
    gauge("serving_bucket_achieved_tflops",
          "achieved model TF/s of the most recent batch, per shape "
          "bucket").labels(bucket=bucket).set(achieved)
    if peak is not None:
        gauge("serving_bucket_mfu",
              "model-FLOP utilisation of the most recent batch vs the "
              "published bf16 peak of the device that ran it, per shape "
              "bucket").labels(bucket=bucket).set(achieved / peak)
    return achieved


def observe_comms_cost(program, comms, cost=None,
                       device_kind: str = "") -> None:
    """Static-sharding comms gauges (analysis.cost_model.estimate_comms):
    ``executor_comms_gbytes_per_step`` — predicted per-chip collective
    wire volume of one step under the compiled sharding assignment — and,
    for a mesh of ``device_kind`` chips that the peaks table lists,
    ``executor_comms_compute_ratio`` — predicted wire time over MXU time
    (>1 = communication-bound). Labels carry the program serial and the
    mesh shape so multi-mesh runs stay distinguishable."""
    if not enabled() or comms is None:
        return
    labels = {"program": str(int(getattr(program, "_serial", -1))),
              "mesh": "x".join(f"{k}={v}"
                               for k, v in sorted(comms.mesh.items()))}
    gauge("executor_comms_gbytes_per_step",
          "predicted per-chip collective wire GB of one step under the "
          "static sharding assignment, by program and mesh").labels(
        **labels).set(comms.gbytes_per_step)
    peak = _peak_tflops(device_kind)
    if cost is not None and cost.flops_total > 0 and peak is not None:
        from ..analysis.cost_model import comms_compute_ratio

        gauge("executor_comms_compute_ratio",
              "predicted comms-vs-compute time ratio of one step "
              "(>1 = communication-bound), by program and mesh").labels(
            **labels).set(comms_compute_ratio(comms, cost, peak))


def record_watchdog_timeout(section: str) -> None:
    """Account one step-watchdog expiry (resilience.distributed): the
    section name is the armed region (compile / step / chained /
    parallel_step / collective). The dump itself — thread stacks, active
    program serial, last recompile diagnosis — goes to the resilience
    logger and stderr; this records the event on the registry so CI
    artifacts show it (docs/OBSERVABILITY.md)."""
    if not enabled():
        return
    counter("watchdog_timeouts_total",
            "watchdog deadlines that expired (hangs converted to "
            "diagnosed failures)").labels(section=section).inc()


def record_pass(name: str, kind: str, seconds: float,
                cached: bool = False) -> None:
    """Account one IR-pass execution (analysis.pass_manager): run counts by
    pass/kind/result (``cached`` = the PassContext served the analysis from
    its cache) and wall-time histograms for real runs — the per-pass
    timings the ROADMAP item 5 refactor promised (docs/OBSERVABILITY.md)."""
    if not enabled():
        return
    counter("pass_runs_total",
            "IR pass executions by pass, kind and result (result=cached "
            "means the PassContext analysis cache was hit)").labels(
        **{"pass": name, "kind": kind,
           "result": "cached" if cached else "run"}).inc()
    if not cached:
        histogram("pass_duration_seconds",
                  "wall time of one IR pass execution, by pass").labels(
            **{"pass": name}).observe(seconds)


def record_remat(decision) -> None:
    """Record one FLAGS_auto_recompute decision (analysis/remat.py
    RematDecision): how many programs were transformed vs refused, segments
    inserted, and the planner's predicted peak bytes for the plain and
    remat variants (docs/OBSERVABILITY.md)."""
    if not enabled():
        return
    counter("remat_programs_total",
            "auto-remat decisions by outcome").labels(
        outcome="applied" if decision.applied else "refused").inc()
    if not decision.applied:
        return
    counter("remat_segments_inserted_total",
            "recompute segments inserted by FLAGS_auto_recompute").inc(
        decision.n_segments)
    gauge("remat_predicted_peak_bytes",
          "memory_plan predicted peak of the last transformed program, "
          "by variant").labels(variant="plain").set(decision.peak_before)
    gauge("remat_predicted_peak_bytes",
          "memory_plan predicted peak of the last transformed program, "
          "by variant").labels(variant="remat").set(decision.peak_after)


# -- introspection ---------------------------------------------------------

def recompile_events(recompiles_only: bool = True):
    """Recent compile records (bounded ring; newest last)."""
    return get_tracker().events(recompiles_only=recompiles_only)


def recompile_count(program_serial: Optional[int] = None) -> int:
    return get_tracker().recompile_count(program_serial)


def snapshot() -> dict:
    """One JSON-ready view of everything: metrics + compile/recompile
    events. This is the metrics artifact ``tools/metrics_report.py``
    writes for CI."""
    return {
        "metrics": get_registry().to_dict(),
        "compile_events": [e.to_dict() for e in
                           get_tracker().events()],
        "recompiles_total": get_tracker().recompile_count(),
    }


def reset() -> None:
    """Clear metrics, recompile history and the cost-report cache (hooks
    stay subscribed)."""
    get_registry().reset()
    get_tracker().reset()
    _cost_cache.clear()
