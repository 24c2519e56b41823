"""FLAGS_* config shim (reference paddle/fluid/platform/flags.cc + the
``FLAGS_*`` env contract surfaced through core.init_gflags).

Flags resolve, in order: explicit ``set_flags`` > ``FLAGS_<name>`` env var >
default. Memory/allocator knobs from the reference are accepted for script
compatibility but inert — XLA owns device memory (documented per flag).
"""
from __future__ import annotations

import os
from typing import Any, Dict

__all__ = ["get_flags", "set_flags", "flag", "xla_options"]

# name -> (type, default, meaning)
_DEFS: Dict[str, tuple] = {
    # live flags
    "check_nan_inf": (bool, False,
                      "per-op finite checks with op provenance on failure "
                      "(reference flags.cc:44; operator.cc fast_check_nan_inf)"),
    "check_program": (int, 0,
                      "static-verification level (paddle_tpu.analysis): "
                      "0 off; 1 verify each program once before first "
                      "execution (error-severity findings raise "
                      "ProgramVerificationError with the op's build site); "
                      "2 additionally re-run verify_program after every "
                      "transform pass in a PassManager pipeline — a "
                      "transform introducing new errors is refused with "
                      "PassVerificationError naming the pass. See "
                      "docs/ANALYSIS.md. Level 1 is on by default in the "
                      "test suite via tests/conftest.py"),
    "monitor": (bool, True,
                "runtime metrics collection (paddle_tpu.monitor): executor "
                "counters/histograms, step hooks, recompilation diagnostics "
                "— docs/OBSERVABILITY.md. Off disables all collection"),
    "lock_witness": (bool, False,
                     "instrument the named framework locks "
                     "(monitor.lockwitness factories): per-thread "
                     "acquisition-order edges, wait/hold histograms and "
                     "runtime lock-order cycle detection, gated against "
                     "the static PT800 lock-order graph by "
                     "tools/load_check.py --fleet-chaos. Off: the "
                     "factories return plain threading primitives"),
    "numerics_witness": (bool, False,
                         "compile per-var numeric range taps into every "
                         "step (monitor.numwitness): jitted abs-max/min/"
                         "max + nonfinite counts per float op output, "
                         "merged host-side and cross-checked against the "
                         "numerics_check pass's static intervals by "
                         "tools/lint_numerics.py --witness. Off: steps "
                         "trace without taps (no hot-path cost)"),
    "log_compiles": (bool, False,
                     "log every executor compile (INFO) and recompile "
                     "(WARNING, with the changed cache-key component and "
                     "program build site) — the jax_log_compiles analogue "
                     "for the step cache"),
    "recompile_warn_threshold": (int, 3,
                                 "warn via logging once a single program "
                                 "has recompiled this many times, even "
                                 "without FLAGS_log_compiles (0 disables)"),
    "nan_inf_policy": (str, "raise",
                       "what a tripped FLAGS_check_nan_inf step does: "
                       "raise (FloatingPointError with op provenance), "
                       "skip (drop the step, roll state back bit-exactly; "
                       "nan_inf_max_consecutive_skips trips escalate), "
                       "zero_grad (skip without escalation — the zero-"
                       "gradient approximation). docs/RESILIENCE.md"),
    "nan_inf_max_consecutive_skips": (int, 5,
                                      "under nan_inf_policy=skip, this many "
                                      "consecutive dropped steps escalate "
                                      "to FloatingPointError (0 disables "
                                      "escalation)"),
    "fault_plan": (str, "",
                   "deterministic fault-injection schedule, e.g. "
                   "'compile:2:RuntimeError,ckpt_write:1:kill' "
                   "(paddle_tpu.resilience.faults; sites: compile, "
                   "device_put, step, ckpt_write, shard_write, hang, "
                   "device_lost; actions add 'hang' — an interruptible "
                   "stall the step watchdog must break). Empty disables"),
    "elastic": (bool, True,
                "elastic preemption-tolerant training "
                "(resilience.elastic): a typed DeviceLostError in a "
                "parallel contrib.Trainer run with a checkpoint config "
                "tears down the failed CompiledProgram, re-forms the "
                "mesh on the surviving devices, restores from the last "
                "verified checkpoint and fast-forwards the data cursor. "
                "Off: the DeviceLostError propagates (die typed). "
                "docs/RESILIENCE.md"),
    "elastic_max_rescales": (int, 8,
                             "elastic rescales allowed per Trainer.train "
                             "call before escalating with PT612 — "
                             "repeated device loss is an outage, not "
                             "churn"),
    "elastic_upscale_after_steps": (int, 0,
                                    "after this many consecutive healthy "
                                    "steps at reduced capacity, probe the "
                                    "device set and rescale BACK UP when "
                                    "capacity returned (no state restore "
                                    "— the live state re-shards onto the "
                                    "bigger mesh). 0 disables (default)"),
    "step_timeout_s": (float, 0.0,
                       "step watchdog (resilience.distributed): arm a "
                       "deadline around compile/step/collective sections; "
                       "on expiry all thread stacks + the active program "
                       "serial + the last recompile diagnosis are dumped "
                       "and the section raises WatchdogTimeout instead of "
                       "hanging CI forever. 0 disables (default). "
                       "docs/RESILIENCE.md"),
    "watchdog_hard_exit": (bool, True,
                           "after a watchdog expiry, if the hung section "
                           "is still armed one extra timeout later (stuck "
                           "in uninterruptible native code), os._exit(124)"
                           " with the diagnosis already on stderr — a "
                           "diagnosed fast failure beats a CI wall-clock "
                           "kill. Off: dump + raise only"),
    "replica_check_interval": (int, 0,
                               "every N-th data-parallel step, checksum "
                               "replicated params/optimizer state across "
                               "the dp axis (jitted reduce, no host "
                               "gather) and trip ReplicaDivergenceError "
                               "naming the first diverged param when "
                               "replicas disagree. 0 disables (default). "
                               "docs/RESILIENCE.md"),
    "replica_divergence_policy": (str, "raise",
                                  "what a detected cross-replica "
                                  "divergence does: raise "
                                  "(ReplicaDivergenceError), or restore "
                                  "(roll back to the last verified "
                                  "checkpoint via the registered recovery"
                                  " walk — contrib.Trainer wires it — "
                                  "and keep training; escalates to raise "
                                  "when nothing restorable exists)"),
    "trace": (bool, False,
              "structured span tracing (paddle_tpu.trace): request/step "
              "trace-ID propagation through serving, executor, trainer, "
              "retry and the resilience failure paths, feeding the "
              "flight recorder and the Chrome/JSONL exporters. Off "
              "(default) the hot paths pay one flag read and a no-op "
              "singleton — tools/trace_check.py gates the overhead. "
              "docs/OBSERVABILITY.md"),
    "trace_buffer_size": (int, 4096,
                          "finished spans kept in the bounded trace "
                          "collector (oldest evicted); exporters and "
                          "trace_tree read from this buffer"),
    "flight_recorder_size": (int, 256,
                             "spans kept in the flight-recorder ring "
                             "dumped into the diagnosis when a "
                             "WatchdogTimeout / DeviceLostError / "
                             "replica divergence / BatchFailed fires; "
                             "0 disables the recorder (incidents then "
                             "ship without span context — the "
                             "trace_check negative control)"),
    "ici_gbytes_per_s": (float, 100.0,
                         "effective per-chip interconnect bandwidth "
                         "(GB/s) for the predicted comms-vs-compute "
                         "ratio (analysis.cost_model.estimate_comms); "
                         "default a conservative v5e ICI figure — set "
                         "per deployment. docs/PERF_NOTES.md"),
    "fault_seed": (int, 0,
                   "seed for probabilistic fault-plan rules and retry "
                   "jitter — the same plan+seed replays identically"),
    "fault_stall_s": (float, 5.0,
                      "duration of the 'stall' data-plane wire fault "
                      "action (resilience.faults wire_connect/"
                      "wire_response/wire_stream sites): the injected "
                      "sleep that models a stalling-but-listening peer "
                      "the router's per-replica breaker must eject"),
    "retry_max_attempts": (int, 3,
                           "attempts (first try included) for transient "
                           "failures at the compile/device_put sites; 1 "
                           "disables retry"),
    "retry_base_delay": (float, 0.05,
                         "first backoff delay in seconds (doubles per "
                         "retry, seeded jitter on top)"),
    "retry_max_delay": (float, 2.0, "backoff delay ceiling in seconds"),
    "retry_timeout": (float, 30.0,
                      "per-site wall-clock retry budget in seconds across "
                      "all attempts (0 = unlimited)"),
    # serving (paddle_tpu.serving — docs/SERVING.md). ServingConfig reads
    # these as its defaults; explicit config fields win.
    "serving_max_batch": (int, 8,
                          "serving: largest padded batch per dispatch; "
                          "shape buckets are powers of two up to this, so "
                          "one compiled executable per bucket absorbs "
                          "arbitrary traffic"),
    "serving_queue_depth": (int, 256,
                            "serving admission control: queued requests "
                            "above this are rejected with typed Overloaded "
                            "(load shedding, never a silent drop)"),
    "serving_queue_age_s": (float, 5.0,
                            "serving admission control: when the OLDEST "
                            "queued request is older than this, new "
                            "arrivals are shed as Overloaded — queue-age "
                            "pressure catches a stuck device before the "
                            "depth bound does (0 disables)"),
    "serving_deadline_s": (float, 0.0,
                           "default per-request deadline in seconds "
                           "(resilience.deadline); an expired request gets "
                           "typed DeadlineExceeded instead of a stale "
                           "response. 0 = no default; submit(deadline_s=) "
                           "overrides per request"),
    "serving_batch_window_s": (float, 0.0,
                               "how long the dispatcher waits for a "
                               "partially-filled batch to fill before "
                               "dispatching it anyway (0 = dispatch "
                               "whatever is queued — lowest latency)"),
    "serving_breaker_threshold": (int, 3,
                                  "consecutive batch failures that OPEN a "
                                  "shape bucket's circuit breaker (requests "
                                  "for that bucket are then rejected "
                                  "CircuitOpen until a half-open probe "
                                  "succeeds)"),
    "serving_breaker_cooldown_s": (float, 0.5,
                                   "base open->half-open cooldown; each "
                                   "re-open backs off through the "
                                   "resilience.retry schedule (doubling, "
                                   "capped) instead of hammering a broken "
                                   "bucket"),
    "serving_degrade_after_s": (float, 1.0,
                                "sustained overload pressure for this long "
                                "enters degraded mode: max batch halves "
                                "and sub-priority requests are shed "
                                "(docs/SERVING.md)"),
    "serving_recover_after_s": (float, 1.0,
                                "pressure-free time before degraded mode "
                                "restores the full batch ceiling"),
    "serving_degraded_min_priority": (int, 1,
                                      "in degraded mode, requests with "
                                      "priority below this are shed at "
                                      "admission with typed Overloaded"),
    "serving_bisect_depth": (int, 0,
                             "poison-request isolation (docs/SERVING.md): "
                             "when a batch fails with a state-safe error, "
                             "re-dispatch it as bisected halves up to this "
                             "depth until the culprit request is isolated "
                             "— innocents complete with correct results, "
                             "the culprit settles typed PoisonRequest and "
                             "its feed fingerprint is quarantined. 0 "
                             "disables (default): the whole batch fails "
                             "typed BatchFailed as before. Failures that "
                             "may have corrupted device state (watchdog "
                             "timeout, device loss, consumed donated "
                             "buffers) always fail the whole batch"),
    "serving_bisect_quarantine": (int, 64,
                                  "bounded count of poison feed "
                                  "fingerprints remembered per engine; a "
                                  "quarantined fingerprint is shed at "
                                  "admission (typed Overloaded, reason "
                                  "poison_quarantine) instead of failing "
                                  "another batch. Oldest evicted"),
    "serving_slo_latency_s": (str, "batch:30,standard:1.0,interactive:0.25",
                              "per-priority-class latency objective for "
                              "the SLO burn-rate tracker (serving/slo.py; "
                              "docs/SERVING.md 'SLO burn rate'): "
                              "'class:seconds' pairs, comma-separated. A "
                              "completed request slower than its class "
                              "target, or any non-completed terminal "
                              "outcome, consumes error budget"),
    "serving_slo_error_budget": (float, 0.01,
                                 "allowed bad-request fraction of the SLO "
                                 "objective; burn rate = observed bad "
                                 "fraction / this budget (1.0 = burning "
                                 "exactly at budget)"),
    "serving_slo_fast_window_s": (float, 60.0,
                                  "fast burn-rate window in seconds (the "
                                  "page-now signal of the multi-window "
                                  "burn alert)"),
    "serving_slo_slow_window_s": (float, 600.0,
                                  "slow burn-rate window in seconds (the "
                                  "sustained-burn confirmation window)"),
    # per-tenant quotas + weighted fair share (serving/engine.py;
    # docs/SERVING.md 'Fleet control loop'). ServingConfig reads these as
    # its defaults; explicit config fields win.
    "serving_tenant_fair_share": (bool, False,
                                  "per-tenant admission fairness: a tenant "
                                  "holding more than its queue quota is "
                                  "shed typed Overloaded(reason="
                                  "tenant_quota), and the dispatcher picks "
                                  "batches by weighted fair queueing "
                                  "(DWRR-equivalent stride scheduling) "
                                  "instead of strict FIFO. Off (default): "
                                  "admission and dispatch behave exactly "
                                  "as before"),
    "serving_tenant_weights": (str, "",
                               "'tenant:weight,...' fair-share weights "
                               "(e.g. 'acme:3,globex:1'); unlisted "
                               "tenants get weight 1. A tenant's queue "
                               "quota and dispatch share scale with its "
                               "weight"),
    "serving_tenant_quota_frac": (float, 0.5,
                                  "largest fraction of serving_queue_depth "
                                  "one weight-1 tenant may occupy before "
                                  "its NEW arrivals are shed typed "
                                  "Overloaded(reason=tenant_quota); a "
                                  "tenant with weight w gets w times this "
                                  "share (capped at the whole queue)"),
    # fleet autoscaler (serving/fleet/autoscaler.py; docs/SERVING.md
    # 'Fleet control loop'). AutoscalerConfig reads these as defaults.
    "serving_autoscale_min_replicas": (int, 1,
                                       "autoscaler floor: scale-in below "
                                       "this many replicas is refused "
                                       "typed at_min_replicas"),
    "serving_autoscale_max_replicas": (int, 4,
                                       "autoscaler ceiling: scale-out "
                                       "above this many replicas is "
                                       "refused typed at_max_replicas"),
    "serving_autoscale_interval_s": (float, 1.0,
                                     "autoscaler control-loop tick "
                                     "interval in seconds"),
    "serving_autoscale_cooldown_s": (float, 30.0,
                                     "minimum seconds between two scale "
                                     "actions (and from a drain start to "
                                     "the next action): decisions inside "
                                     "it are refused typed cooldown — the "
                                     "anti-flap half of the hysteresis"),
    "serving_autoscale_hot_sustain_s": (float, 5.0,
                                        "burn/pressure must be observed "
                                        "continuously for this long "
                                        "before a scale-out fires (one "
                                        "bad tick never scales)"),
    "serving_autoscale_calm_sustain_s": (float, 30.0,
                                         "the fleet must be calm (no "
                                         "burn, no pressure) continuously "
                                         "for this long before a drain-"
                                         "based scale-in fires"),
    "serving_autoscale_max_inflight_spawns": (int, 1,
                                              "spawns not yet ready the "
                                              "autoscaler may have in "
                                              "flight; further scale-outs "
                                              "are refused typed "
                                              "spawn_budget_spent"),
    "serving_autoscale_queue_high": (int, 8,
                                     "per-replica queue depth the "
                                     "autoscaler counts as pressure "
                                     "(alongside degraded mode and open "
                                     "breaker buckets)"),
    # fleet telemetry plane (serving/fleet/telemetry.py;
    # docs/OBSERVABILITY.md 'Fleet telemetry plane')
    "fleet_telemetry": (bool, False,
                        "fleet telemetry plane: when on, request-latency "
                        "observations carry trace-id exemplars into the "
                        "JSON /metrics form and FleetAggregator.start() "
                        "runs its scrape thread. Off (default) is a "
                        "hot-path no-op: no exemplar allocation, no "
                        "scrape thread"),
    "fleet_scrape_interval_s": (float, 1.0,
                                "FleetAggregator scrape interval in "
                                "seconds (per-replica GET /metrics)"),
    "auto_recompute": (bool, False,
                       "automatic rematerialisation: on Executor.run / "
                       "run_chained / CompiledProgram, training programs "
                       "are segmented at layer boundaries and gradient-"
                       "checkpointed (analysis/remat.py Pass 6), with the "
                       "checkpoint set chosen by Program.memory_plan() "
                       "scoring. Transformed programs get their own serial "
                       "so compile caches never alias remat and plain "
                       "variants. docs/PERF_NOTES.md"),
    "remat_budget_mb": (int, 0,
                        "peak-memory target for FLAGS_auto_recompute in "
                        "MiB: the cheapest checkpoint set (fewest "
                        "recomputed ops) whose PREDICTED peak fits is "
                        "chosen; 0 = no budget, sqrt(N) segmentation"),
    "aot_cache_dir": (str, "",
                      "warm-start AOT executable cache directory "
                      "(paddle_tpu.aot_cache): after every successful "
                      "XLA compile the executable is serialized here, "
                      "and later processes load instead of compiling — "
                      "a cold serving replica joins the fleet warm. "
                      "Keyed by program CONTENT fingerprint + arg "
                      "signature + compiler config + backend/versions; "
                      "corrupt or version-mismatched entries degrade to "
                      "a recompile with one warning. Empty disables "
                      "(default). docs/SERVING.md"),
    "xla_options": (str, "",
                    "XLA compiler options forwarded to jax.jit("
                    "compiler_options=...) on every executor compile; "
                    "JSON object or comma-separated k=v pairs, e.g. "
                    "'{\"xla_tpu_enable_latency_hiding_scheduler\": true}' "
                    "or 'xla_cpu_enable_fast_min_max=true'. Part of the "
                    "compile-cache key"),
    "paddle_num_threads": (int, 1, "host threads hint (XLA owns scheduling)"),
    "seq_bucket_sizes": (str, "", "override DataFeeder varlen buckets, csv"),
    "conv_use_nhwc": (str, "auto",
                      "conv/pool inner layout: auto (NHWC on TPU — channels "
                      "ride the 128-lane dim; boundary transposes cancel "
                      "between layers), always, never (NCHW as the "
                      "reference)"),
    "use_flash_attention": (str, "auto",
                            "fused_multihead_attention path: auto (Pallas "
                            "kernel on TPU, primitives elsewhere), always "
                            "(force kernel; interpret mode off-TPU — slow, "
                            "tests only), never"),
    # accepted-for-compat, inert on TPU (XLA/PJRT owns memory)
    "fraction_of_gpu_memory_to_use": (float, 0.92, "inert: XLA preallocates"),
    "allocator_strategy": (str, "auto_growth", "inert: XLA buffer assignment"),
    "eager_delete_tensor_gb": (float, 0.0, "inert: no GC, donation instead"),
    "memory_fraction_of_eager_deletion": (float, 1.0, "inert"),
    "init_allocated_mem": (bool, False, "inert"),
    "selected_gpus": (str, "", "inert: device choice is Place/mesh-driven"),
    "selected_tpus": (str, "", "device index hint for TPUPlace"),
    "cudnn_deterministic": (bool, False, "inert: XLA is deterministic"),
}

_overrides: Dict[str, Any] = {}

# bumped on every set_flags call: cheap change-detection for hot-path
# callers that memoize a flag value (paddle_tpu.trace.enabled caches
# FLAGS_trace against this, so the disabled tracing path costs an int
# compare instead of an env read per span). Env-var mutations AFTER the
# first read are not observed — the documented gflags-style contract.
_set_epoch = 0


def _coerce(typ, raw):
    if typ is bool:
        if isinstance(raw, (int, float, bool)):
            return bool(raw)  # gflags semantics: nonzero is true
        s = str(raw).strip().lower()
        if s in ("1", "true", "yes", "on"):
            return True
        if s in ("0", "false", "no", "off", ""):
            return False
        raise ValueError(f"not a boolean flag value: {raw!r}")
    return typ(raw)


def flag(name: str):
    """Current value of one flag."""
    if name not in _DEFS:
        raise KeyError(f"unknown flag '{name}' — known: {sorted(_DEFS)}")
    if name in _overrides:
        return _overrides[name]
    typ, default, _ = _DEFS[name]
    raw = os.environ.get(f"FLAGS_{name}")
    return default if raw is None else _coerce(typ, raw)


def get_flags(names=None) -> Dict[str, Any]:
    """reference fluid.get_flags."""
    if names is None:
        names = list(_DEFS)
    if isinstance(names, str):
        names = [names]
    return {f"FLAGS_{n}": flag(n) for n in (x.replace("FLAGS_", "")
                                            for x in names)}


def _parse_option_value(s: str):
    t = s.strip()
    low = t.lower()
    if low in ("true", "false"):
        return low == "true"
    for conv in (int, float):
        try:
            return conv(t)
        except ValueError:
            pass
    return t


# raw flag string -> parsed dict; the executor consults xla_options() on
# every dispatch to build cache keys, so parsing must not be per-step work
_xla_options_memo: Dict[str, Dict[str, Any]] = {}


def xla_options() -> Dict[str, Any]:
    """``FLAGS_xla_options`` parsed to the dict handed to
    ``jax.jit(compiler_options=...)``: a JSON object, or comma-separated
    ``k=v`` pairs with true/false/number coercion. The executor folds
    ``sorted(items())`` into every compile-cache key, so flipping options
    recompiles instead of silently reusing the old executable. Parses are
    memoized on the raw string (callers must not mutate the result)."""
    raw = str(flag("xla_options")).strip()
    cached = _xla_options_memo.get(raw)
    if cached is not None:
        return cached
    _xla_options_memo[raw] = opts = _parse_xla_options(raw)
    return opts


def _parse_xla_options(raw: str) -> Dict[str, Any]:
    if not raw:
        return {}
    if raw.startswith("{"):
        import json

        opts = json.loads(raw)
        if not isinstance(opts, dict):
            raise ValueError(
                f"FLAGS_xla_options JSON must be an object, got {opts!r}")
        return opts
    out: Dict[str, Any] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                f"FLAGS_xla_options entry {part!r} is not k=v "
                f"(or pass a JSON object)")
        k, v = part.split("=", 1)
        out[k.strip()] = _parse_option_value(v)
    return out


def set_flags(flags_dict: Dict[str, Any]) -> None:
    """reference fluid.set_flags({'FLAGS_check_nan_inf': 1})."""
    global _set_epoch
    for k, v in flags_dict.items():
        name = k.replace("FLAGS_", "")
        if name not in _DEFS:
            raise KeyError(f"unknown flag '{k}' — known: "
                           f"{sorted('FLAGS_' + n for n in _DEFS)}")
        typ = _DEFS[name][0]
        _overrides[name] = _coerce(typ, v)
    _set_epoch += 1
