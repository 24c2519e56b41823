"""paddle_tpu.analysis — static program verification and registry auditing.

Public surface:

* ``verify_program(program, fetch_names=())`` — run the multi-pass verifier,
  return a list of ``Diagnostic``.
* ``check_program(...)`` — same, but raise ``ProgramVerificationError`` when
  error-severity findings exist (the FLAGS_check_program executor hook).
* ``audit_registry()`` / ``format_audit`` — per-op capability coverage.
* ``liveness`` — dataflow liveness & effect analysis: proven-safe buffer
  donation (``safe_donation_set``), peak-memory planning (``memory_plan``,
  surfaced as ``Program.memory_plan()``), PT5xx diagnostics.
* ``remat`` — Pass 6, automatic rematerialisation: memory_plan-scored
  checkpoint selection + program rebuild (``auto_recompute_program``),
  wired to the executor via ``FLAGS_auto_recompute`` (docs/PERF_NOTES.md).
* ``pass_manager`` — the uniform IR pass framework (ROADMAP item 5):
  ``Pass``/``PassRegistry``/``@register_pass`` with declared dependencies
  and invalidations, ``PassContext`` analysis caching,
  ``PassManager.run_pipeline`` with pre/post verification and per-pass
  monitor timings. All six passes above are registered on it; the three
  new static-analysis families (``static_checks``: PT700s dtype/shape
  consistency, PT710s donation-race, PT720s dead-code + opt-in DCE) run
  through it too.
* ``CODES`` — the diagnostic-code table (see docs/ANALYSIS.md).
"""
from .diagnostics import (CODES, Diagnostic, ProgramVerificationError,
                          Severity, format_diagnostics)
from .registry_audit import audit_registry, coverage_summary, format_audit
from .verifier import DEFAULT_PASSES, check_program, verify_program
from . import liveness
from .liveness import (MemoryPlan, block_liveness, classify_op_effects,
                       donation_report, memory_plan, safe_donation_set)
from . import remat
from .remat import (RematCandidate, RematDecision, auto_recompute_program,
                    remat_candidates)
from . import pass_manager
from .pass_manager import (ALL_ANALYSIS_PASSES, VERIFY_PASSES, FunctionPass,
                           Pass, PassContext, PassManager, PassRegistry,
                           PassVerificationError, PipelineResult,
                           clear_analysis_caches, default_pass_manager,
                           get_pass_registry, register_pass,
                           run_transform_pipeline, run_verify_pipeline)
from . import static_checks
from .static_checks import (DceDecision, DeadCodeReport, dce_program)
from . import cost_model
from .cost_model import (CommsReport, CostReport, comms_compute_ratio,
                         estimate_comms, estimate_cost)
from . import sharding_check
from .sharding_check import (CollectiveEvent, ShardingAnalysis,
                             propagate_sharding)
from . import numerics
from .numerics import (Interval, NumericsReport, analyze_numerics,
                       check_numerics, static_intervals)

__all__ = [
    "CODES", "Diagnostic", "ProgramVerificationError", "Severity",
    "format_diagnostics", "audit_registry", "coverage_summary",
    "format_audit", "DEFAULT_PASSES", "check_program", "verify_program",
    "liveness", "MemoryPlan", "block_liveness", "classify_op_effects",
    "donation_report", "memory_plan", "safe_donation_set",
    "remat", "RematCandidate", "RematDecision", "auto_recompute_program",
    "remat_candidates",
    "pass_manager", "Pass", "FunctionPass", "PassRegistry", "PassContext",
    "PassManager", "PassVerificationError", "PipelineResult",
    "register_pass", "get_pass_registry", "default_pass_manager",
    "run_verify_pipeline", "run_transform_pipeline", "clear_analysis_caches",
    "ALL_ANALYSIS_PASSES", "VERIFY_PASSES",
    "static_checks", "DceDecision", "DeadCodeReport", "dce_program",
    "cost_model", "CostReport", "estimate_cost", "CommsReport",
    "estimate_comms", "comms_compute_ratio",
    "sharding_check", "CollectiveEvent", "ShardingAnalysis",
    "propagate_sharding",
    "numerics", "Interval", "NumericsReport", "analyze_numerics",
    "check_numerics", "static_intervals",
]
