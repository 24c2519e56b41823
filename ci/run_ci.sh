#!/usr/bin/env bash
# CI driver (the paddle_build.sh role, reference paddle/scripts/paddle_build.sh):
#   ci/run_ci.sh [fast|full|tpu]
#
# fast: import check + CPU unit tests (8 virtual devices, what the repo's
#       conftest configures)
# full: fast + the multichip dry-run the round driver executes
# tpu : chip_smoke.py, then the on-accelerator smoke suite (needs a real
#       chip; one process at a time owns it)
set -euo pipefail
cd "$(dirname "$0")/.."
MODE="${1:-fast}"

echo "== import check"
JAX_PLATFORMS=cpu python -c "
import paddle_tpu
print('ops registered:', len(paddle_tpu.op_registry.all_ops()))
print('version:', paddle_tpu.__version__)"

echo "== static program lint pipeline (full pass-manager run over the model"
echo "   zoo: verifier + PT700s/710s/720s; errors and non-allowlisted"
echo "   dead-code findings gate; JSON report is the CI artifact)"
JAX_PLATFORMS=cpu python tools/lint_program.py --zoo \
  --json "${CI_ARTIFACT_DIR:-.}/ci_lint_report.json" | tail -20

echo "== concurrency lint gate (analysis/concurrency: lock inventory +"
echo "   lock-order graph over the whole package; PT800 cycles, PT801"
echo "   blocking-under-lock and PT802 unguarded cross-thread attrs gate"
echo "   unless allowlisted with a reason; JSON report is the CI artifact"
echo "   — the fleet-chaos leg later merges its runtime lock_witness"
echo "   section into the same file)"
JAX_PLATFORMS=cpu python tools/lint_concurrency.py \
  --json "${CI_ARTIFACT_DIR:-.}/ci_concurrency_report.json"
echo "== concurrency lint negative control (broken fixtures, allowlist"
echo "   off: the gate must FAIL on all of PT800/PT801/PT802)"
CONC_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_concurrency_negative.log"
if JAX_PLATFORMS=cpu python tools/lint_concurrency.py \
     --negative-control > "$CONC_NEG_LOG" 2>&1; then
  echo "lint_concurrency did NOT fail on the broken fixtures" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the linter crashing
if ! grep -q -- "-> FAIL" "$CONC_NEG_LOG"; then
  echo "concurrency negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$CONC_NEG_LOG" >&2
  exit 1
fi

echo "== numerics lint gate (analysis/numerics: interval + dtype-precision"
echo "   flow over the model zoo incl. QAT-transformed variants; PT900"
echo "   broken quant pairing and PT902 overflowing casts are errors,"
echo "   PT901/PT903/PT904/PT905 warnings gate unless allowlisted; PT906"
echo "   is the int8 quantizability work-list; JSON report is the CI"
echo "   artifact)"
JAX_PLATFORMS=cpu python tools/lint_numerics.py \
  --json "${CI_ARTIFACT_DIR:-.}/ci_numerics_report.json" | tail -12
echo "== numerics lint negative control (broken fixtures, allowlist off:"
echo "   the gate must FAIL on all of PT900..PT905)"
NUM_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_numerics_negative.log"
if JAX_PLATFORMS=cpu python tools/lint_numerics.py \
     --negative-control > "$NUM_NEG_LOG" 2>&1; then
  echo "lint_numerics did NOT fail on the broken fixtures" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the linter crashing
if ! grep -q -- "-> FAIL" "$NUM_NEG_LOG"; then
  echo "numerics negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$NUM_NEG_LOG" >&2
  exit 1
fi

echo "== numerics witness cross-check (FLAGS_numerics_witness=1: jitted"
echo "   per-var abs-max/min/max + nonfinite taps over short train+infer"
echo "   runs of the zoo; every observed value must sit INSIDE its proven"
echo "   static interval — tolerance-free containment, the lock-witness"
echo "   idiom — and observed abs-max feeds PT906 calibration into"
echo "   ci_numerics_report.json)"
JAX_PLATFORMS=cpu python tools/lint_numerics.py --witness \
  --json "${CI_ARTIFACT_DIR:-.}/ci_numerics_report.json" | tail -8

echo "== op-registry conformance audit (ops without a lower rule gate)"
JAX_PLATFORMS=cpu python tools/audit_registry.py --strict \
  --json-file "${CI_ARTIFACT_DIR:-.}/ci_registry_audit.json" > /dev/null
JAX_PLATFORMS=cpu python tools/audit_registry.py --untested | tail -3

echo "== peak-memory plan + PT5xx liveness gate (JSON report is the CI artifact)"
JAX_PLATFORMS=cpu python tools/mem_report.py --check \
  --json "${CI_ARTIFACT_DIR:-.}/ci_mem_report.json"

echo "== per-chip memory plan gate (analysis/sharding_check: dp=8 ZeRO-1"
echo "   spec propagation; per-chip peaks must fit the HBM budget, and the"
echo "   static estimate must match the MEASURED live-sharding state bytes"
echo "   of a dp-sharded zoo model within 10% — multichip dryrun)"
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python tools/mem_report.py --mesh dp=8 --specs zero1 --check \
  --validate-live --hbm-budget-mb 15872 \
  --json "${CI_ARTIFACT_DIR:-.}/ci_mem_sharded_report.json" | tail -6

echo "== executor metrics + recompile gate (paddle_tpu.monitor; JSON artifact)"
JAX_PLATFORMS=cpu python tools/metrics_report.py --check \
  --json "${CI_ARTIFACT_DIR:-.}/ci_metrics_report.json"
echo "== recompile tripwire negative control (the gate must FAIL here)"
FORCED_LOG="${CI_ARTIFACT_DIR:-.}/ci_forced_recompile.log"
if JAX_PLATFORMS=cpu python tools/metrics_report.py --check \
     --force-recompile 3 > "$FORCED_LOG" 2>&1; then
  echo "metrics_report --check did NOT fail on a forced-recompile scenario" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the scenario crashing
if ! grep -q -- "-> FAIL" "$FORCED_LOG"; then
  echo "forced-recompile control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$FORCED_LOG" >&2
  exit 1
fi

echo "== auto-remat gate (analysis/remat.py: BERT-base predicted peak must"
echo "   drop >=30%, negative control: flag off => zero segments)"
JAX_PLATFORMS=cpu python tools/remat_check.py --check \
  --json "${CI_ARTIFACT_DIR:-.}/ci_remat_report.json"

echo "== chaos gate (paddle_tpu.resilience: kill-mid-checkpoint + transient"
echo "   compile faults must resume from the last verified checkpoint)"
JAX_PLATFORMS=cpu python tools/chaos_check.py --check \
  --json "${CI_ARTIFACT_DIR:-.}/ci_chaos_report.json"
echo "== chaos negative control (retries disabled: the gate must FAIL here)"
CHAOS_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_chaos_negative.log"
if JAX_PLATFORMS=cpu python tools/chaos_check.py --check \
     --negative-control > "$CHAOS_NEG_LOG" 2>&1; then
  echo "chaos_check --check did NOT fail with retries disabled" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$CHAOS_NEG_LOG"; then
  echo "chaos negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$CHAOS_NEG_LOG" >&2
  exit 1
fi

echo "== serving load gate (paddle_tpu.serving: under injected overload,"
echo "   compile faults and one watchdog-diagnosed hang, every submitted"
echo "   request reaches exactly one terminal outcome; p50/p99 latency"
echo "   histogram is the artifact. --decode adds the generative legs: a"
echo "   GPT-tiny multi-thread generation burst with exact accounting,"
echo "   zero warm recompiles and tokens/s + inter-token p50/p99 in the"
echo "   artifact, a chaos sub-leg killing one in-flight batch — every"
echo "   affected stream must settle with a typed outcome — plus the"
echo "   ISSUE 20 legs: a shared-prefix burst (prefix hits > 0, warm"
echo "   first-token faster than cold, hit ratio + first-token p99 in"
echo "   the artifact) and a speculative leg (greedy output bit-exact vs"
echo "   non-speculative at >= 1.5x tokens/s, acceptance histogram"
echo "   present)"
JAX_PLATFORMS=cpu python tools/load_check.py --ci --decode \
  --json "${CI_ARTIFACT_DIR:-.}/ci_serving_report.json" | tail -13
echo "== serving negative control (shedding disabled, prefix cache off —"
echo "   hit counters must stay zero — and speculation off — no"
echo "   acceptance histogram may exist: the gate must FAIL)"
SERVING_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_serving_negative.log"
if JAX_PLATFORMS=cpu python tools/load_check.py --ci --decode \
     --negative-control > "$SERVING_NEG_LOG" 2>&1; then
  echo "load_check --ci did NOT fail with shedding/prefix/spec disabled" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$SERVING_NEG_LOG"; then
  echo "serving negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$SERVING_NEG_LOG" >&2
  exit 1
fi

echo "== fleet serving gate (paddle_tpu.serving.fleet: two replica PROCESSES"
echo "   behind the load-aware router, one SIGTERMed mid-burst — the fleet"
echo "   sheds nothing it admitted, every request reaches exactly one outcome"
echo "   fleet-wide, p50/p99 end-to-end latency recorded; a cold replica"
echo "   restarted with the warm-start AOT executable cache must report"
echo "   measurably faster time-to-ready than its cold baseline. Then the"
echo "   telemetry-plane leg: fleet p50/p99 assembled from SCRAPED per-"
echo "   replica /metrics via the exact histogram merge and cross-checked"
echo "   against the router ledger, SLO burn state flips to burning under"
echo "   injected stalled batches and recovers, the per-tenant ledger"
echo "   reconciles exactly, exported exemplar trace ids resolve to"
echo "   recorded traces, and a corrupt-/metrics target degrades typed"
echo "   (stale-marked, counted) with zero aggregator crashes)"
JAX_PLATFORMS=cpu python tools/load_check.py --ci --fleet \
  --log-dir "${CI_ARTIFACT_DIR:-.}" \
  --json "${CI_ARTIFACT_DIR:-.}/ci_fleet_report.json" | tail -12
echo "== fleet negative control (router drain honoring + unadmitted retry"
echo "   disabled: the kill scenario must FAIL the gate)"
FLEET_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_fleet_negative.log"
if JAX_PLATFORMS=cpu python tools/load_check.py --ci --fleet \
     --negative-control --log-dir "${CI_ARTIFACT_DIR:-.}" \
     > "$FLEET_NEG_LOG" 2>&1; then
  echo "load_check --fleet did NOT fail with router drain disabled" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$FLEET_NEG_LOG"; then
  echo "fleet negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$FLEET_NEG_LOG" >&2
  exit 1
fi

echo "== fleet self-healing gate (supervisor + bisection + wire chaos: under"
echo "   injected drop/stall/corrupt wire faults a stalling replica is ejected"
echo "   by the router's transport breaker and unadmitted faults retry on the"
echo "   sibling; a poison request co-batched with innocents is isolated by"
echo "   bisection (innocents complete bit-exact, culprit typed PoisonRequest,"
echo "   repeat offender quarantined); a SIGKILLed replica restarts warm under"
echo "   the same id within its backoff budget; a forced crash loop retires"
echo "   with a typed ReplicaCrashLoop). Runs with FLAGS_lock_witness=1:"
echo "   zero runtime lock-order cycles and every observed edge predicted"
echo "   by the static graph also gate; the runtime lock_witness section"
echo "   (wait/hold histograms per named lock) lands in"
echo "   ci_concurrency_report.json"
JAX_PLATFORMS=cpu python tools/load_check.py --ci --fleet-chaos \
  --lock-witness \
  --concurrency-json "${CI_ARTIFACT_DIR:-.}/ci_concurrency_report.json" \
  --log-dir "${CI_ARTIFACT_DIR:-.}" \
  --json "${CI_ARTIFACT_DIR:-.}/ci_fleet_chaos_report.json" | tail -10
echo "== fleet self-healing negative control (supervisor restarts + bisection"
echo "   disabled: innocents must die with the poison and the killed replica"
echo "   must stay dead — the gate must FAIL)"
FLEET_CHAOS_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_fleet_chaos_negative.log"
if JAX_PLATFORMS=cpu python tools/load_check.py --ci --fleet-chaos \
     --negative-control --log-dir "${CI_ARTIFACT_DIR:-.}" \
     > "$FLEET_CHAOS_NEG_LOG" 2>&1; then
  echo "load_check --fleet-chaos did NOT fail with self-healing disabled" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$FLEET_CHAOS_NEG_LOG"; then
  echo "fleet-chaos negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$FLEET_CHAOS_NEG_LOG" >&2
  exit 1
fi

echo "== fleet control-loop gate (FleetAutoscaler + tenant fair-share: a"
echo "   hot-tenant flood is shed typed tenant_quota while innocent tenants"
echo "   keep their SLO, the shed storm burns the SLO budget and the"
echo "   autoscaler scales OUT a second replica warm through the fleet-shared"
echo "   AOT cache (faster time-to-ready than the cold baseline), refusals"
echo "   at the max are typed+metered, calm scales back"
echo "   IN strictly via preemption-drain with an exact exit ledger, and the"
echo "   floor holds typed at_min_replicas — fleet accounting exact"
echo "   throughout)"
JAX_PLATFORMS=cpu python tools/load_check.py --ci --autoscale \
  --log-dir "${CI_ARTIFACT_DIR:-.}" \
  --json "${CI_ARTIFACT_DIR:-.}/ci_autoscale_report.json" | tail -14
echo "== fleet control-loop negative control (no autoscaler, no tenant"
echo "   quotas: sustained hot pressure goes unanswered and the hot tenant"
echo "   is never shed typed — the gate must FAIL)"
AUTOSCALE_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_autoscale_negative.log"
if JAX_PLATFORMS=cpu python tools/load_check.py --ci --autoscale \
     --negative-control --log-dir "${CI_ARTIFACT_DIR:-.}" \
     > "$AUTOSCALE_NEG_LOG" 2>&1; then
  echo "load_check --autoscale did NOT fail without the control loop" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$AUTOSCALE_NEG_LOG"; then
  echo "autoscale negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$AUTOSCALE_NEG_LOG" >&2
  exit 1
fi

echo "== trace gate (paddle_tpu.trace: every request in exactly one complete"
echo "   trace, flight-recorder dumps on injected batch fault + watchdog hang,"
echo "   cost-model FLOPs within 10% of analytic, near-zero off overhead;"
echo "   MFU figures land in ci_trace_report.json)"
JAX_PLATFORMS=cpu python tools/trace_check.py --check \
  --json "${CI_ARTIFACT_DIR:-.}/ci_trace_report.json" | tail -10
echo "== trace negative control (flight recorder disabled: the gate must"
echo "   FAIL — the dump is what carries the fault context)"
TRACE_NEG_LOG="${CI_ARTIFACT_DIR:-.}/ci_trace_negative.log"
if JAX_PLATFORMS=cpu python tools/trace_check.py --check \
     --negative-control > "$TRACE_NEG_LOG" 2>&1; then
  echo "trace_check --check did NOT fail with the flight recorder disabled" >&2
  exit 1
fi
# non-zero exit must be the gate tripping, not the harness crashing
if ! grep -q -- "-> FAIL" "$TRACE_NEG_LOG"; then
  echo "trace negative control exited non-zero WITHOUT tripping the gate:" >&2
  tail -20 "$TRACE_NEG_LOG" >&2
  exit 1
fi

echo "== chaos multichip gate (resilience.distributed: kill inside one shard"
echo "   write -> serial unpublished + bit-identical resume; elastic 8->4->1"
echo "   restore; watchdog converts an injected hang, and without it the"
echo "   run provably hangs)"
python tools/chaos_check.py --check --multichip \
  --json "${CI_ARTIFACT_DIR:-.}/ci_chaos_dist_report.json"

echo "== chaos elastic gate (resilience.elastic: injected device loss at dp=8"
echo "   must auto-rescale to dp=4, resume from the last verified serial with"
echo "   an exact batch trace and a digest equal to an uninterrupted dp=4"
echo "   baseline; FLAGS_elastic=0 must die typed, retry must never absorb a"
echo "   DeviceLostError, and a capacity return upscales 4->8)"
python tools/chaos_check.py --check --elastic \
  --json "${CI_ARTIFACT_DIR:-.}/ci_chaos_elastic_report.json"

echo "== unit tests (CPU, 8 virtual devices; FLAGS_check_program on via conftest)"
python -m pytest tests/ -q -x

if [ "$MODE" = "full" ]; then
  echo "== multichip dry-run (8 VIRTUAL CPU devices)"
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import sys; sys.path.insert(0, '.'); \
               import __graft_entry__ as g; g.dryrun_multichip(8)"
fi

if [ "$MODE" = "tpu" ]; then
  echo "== chip smoke (BERT-base training + GPT-2-base serving end to end,"
  echo "   kernel routes against their oracles; fails without a TPU)"
  python chip_smoke.py
  echo "== on-chip smoke suite"
  PADDLE_TPU_TESTS=1 python -m pytest tests/test_tpu_smoke.py -m tpu -q
fi

echo "CI $MODE: OK"
