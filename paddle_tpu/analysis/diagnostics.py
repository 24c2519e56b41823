"""Structured diagnostics for the program verifier.

The reference stack surfaces malformed ProgramDescs as C++ enforce failures
at op-construction time (op_registry.h schema checks, OpProto required-slot
enforcement); this rebuild constructs graphs in pure Python, so the same bug
class used to surface deep inside a JAX trace. ``paddle_tpu.analysis`` turns
them back into build-site diagnostics: every finding is a ``Diagnostic`` with
a stable code (documented in docs/ANALYSIS.md), a severity, the op's position
and the user call site recorded by the ``op_callstack`` attr.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

__all__ = ["Diagnostic", "Severity", "CODES", "ProgramVerificationError",
           "format_diagnostics"]


class Severity:
    ERROR = "error"      # the program cannot lower / computes garbage
    WARNING = "warning"  # suspicious; lowers, but likely not what was meant
    INFO = "info"        # observation (dead code etc.); never gates


# code -> (severity, one-line meaning). The single source of truth used by
# the verifier, the tests and docs/ANALYSIS.md.
CODES = {
    # -- pass 1: schema conformance ------------------------------------
    "PT100": (Severity.ERROR,
              "op type is not in the registry (and is not an auto-grad op)"),
    "PT101": (Severity.ERROR, "required input slot absent or empty"),
    "PT102": (Severity.ERROR, "input slot not declared by the op's schema"),
    "PT103": (Severity.ERROR, "required output slot absent or empty"),
    "PT104": (Severity.ERROR, "output slot not declared by the op's schema"),
    "PT105": (Severity.ERROR, "required attr missing"),
    "PT106": (Severity.WARNING, "attr not declared by the op's schema"),
    "PT107": (Severity.ERROR, "non-duplicable slot holds more than one var"),
    # -- pass 2: dataflow ----------------------------------------------
    "PT200": (Severity.ERROR,
              "var is read before the op that produces it (use-before-def)"),
    "PT201": (Severity.WARNING,
              "var is read but never produced, fed or scope-initialized"),
    "PT202": (Severity.WARNING,
              "write-after-write: earlier value is dead (never read)"),
    "PT203": (Severity.INFO,
              "op output is never read, not fetched and not persistable"),
    # -- pass 3: lowerability ------------------------------------------
    "PT300": (Severity.ERROR, "op's OpDef has no lower rule"),
    "PT301": (Severity.WARNING,
              "grad op whose forward op declares grad=None"),
    "PT302": (Severity.WARNING,
              "needs_rng op under FLAGS_cudnn_deterministic"),
    # -- pass 4: shape/dtype replay ------------------------------------
    "PT400": (Severity.WARNING,
              "replayed infer_shape disagrees with recorded var shape"),
    "PT401": (Severity.WARNING,
              "replayed infer_shape disagrees with recorded var dtype"),
    # -- pass 5: liveness & effects ------------------------------------
    "PT500": (Severity.WARNING,
              "donation-unsafe fetch: var is updated in place AND fetched; "
              "its buffer is excluded from donation"),
    "PT501": (Severity.WARNING,
              "write-after-fetch: var is rewritten after an explicit fetch "
              "op (compiled steps fetch final values)"),
    "PT502": (Severity.INFO,
              "dead op: no output is read, fetched or persistable"),
    "PT503": (Severity.INFO,
              "dead var: declared but never read or written by any op"),
    "PT504": (Severity.ERROR,
              "persistable var written inside a sub-block never escapes to "
              "the scope (state threading only scans the global block)"),
    # -- pass: dtype/shape consistency (whole-program replay) ----------
    "PT700": (Severity.ERROR,
              "op's infer_shape fails under whole-program replay — the "
              "producer/consumer metadata contract is broken"),
    "PT701": (Severity.WARNING,
              "producer/consumer shape mismatch: whole-program replay "
              "propagates a shape a later consumer's record disagrees "
              "with"),
    "PT702": (Severity.WARNING,
              "producer/consumer dtype mismatch: whole-program replay "
              "propagates a dtype a later consumer's record disagrees "
              "with"),
    "PT703": (Severity.WARNING,
              "conflicting producers: two ops write the same var with "
              "different inferred shape/dtype"),
    "PT704": (Severity.INFO,
              "consumer reads a var with no recorded shape — propagation "
              "is blind past this boundary"),
    # -- pass: donation/alias race detector ----------------------------
    "PT710": (Severity.INFO,
              "donation race avoided: the state_in∩state_out heuristic "
              "would donate the var but a later op still reads it after "
              "its last write — the liveness proof refuses it (safe, but "
              "costs a host copy per step)"),
    "PT711": (Severity.WARNING,
              "unordered double write: two ops write the var with no "
              "data dependency or intervening read ordering them"),
    "PT712": (Severity.WARNING,
              "donated buffer aliased into a fetch: a fetched var is a "
              "view of a donated var taken before its in-place update"),
    "PT713": (Severity.WARNING,
              "op writes a feed var in place — the fed host buffer and "
              "the scope copy can diverge"),
    # -- pass: dead/unreachable code lint -------------------------------
    "PT720": (Severity.WARNING,
              "transitively dead op: every output flows only into other "
              "dead ops (never reaches a fetch, persistable or effect)"),
    "PT721": (Severity.INFO,
              "unused output: one output of an otherwise-live op is "
              "never read, fetched or persistable"),
    "PT722": (Severity.WARNING,
              "unreachable sub-block: no op references the block via its "
              "sub_block attr"),
    # -- pass: static SPMD sharding analysis (sharding_check) -----------
    "PT730": (Severity.ERROR,
              "sharding spec references a mesh axis the mesh does not "
              "have"),
    "PT731": (Severity.ERROR,
              "sharding spec names more dims than the var has"),
    "PT732": (Severity.ERROR,
              "one mesh axis shards two different dims of the same var"),
    "PT733": (Severity.ERROR,
              "shard-indivisible dim: the dim size is not divisible by "
              "the mesh axis size"),
    "PT734": (Severity.WARNING,
              "inconsistent input specs: dims that must agree elementwise "
              "arrive with different shardings — GSPMD inserts a reshard "
              "to reconcile them"),
    "PT735": (Severity.WARNING,
              "unsatisfiable contraction: the contracted dims of a "
              "matmul-class op arrive sharded over different axes — no "
              "partial-sum layout satisfies both without resharding"),
    "PT736": (Severity.WARNING,
              "implicit full replication: a large tensor produced from "
              "sharded inputs comes out fully replicated — every chip "
              "holds (and pays for) the whole value"),
    "PT737": (Severity.WARNING,
              "resharding inside the training loop: a persistable var is "
              "produced with a different layout than it enters with — "
              "every step pays the layout change"),
    "PT738": (Severity.WARNING,
              "gradient spec disagrees with its param's spec at the "
              "optimizer update — the grad is resharded every step"),
    "PT739": (Severity.WARNING,
              "optimizer-state spec disagrees with its param's spec "
              "outside the recognized ZeRO dim-0-over-dp layout"),
    "PT740": (Severity.INFO,
              "ZeRO layout: optimizer state sharded over dp against a "
              "replicated param — each step pays a grad reduce-scatter "
              "plus a param all-gather (the intended trade)"),
    "PT741": (Severity.WARNING,
              "donation invalidated by resharding: the liveness proof "
              "donates the buffer but its input and output layouts "
              "differ, so in-place reuse is impossible (extends PT710)"),
    "PT742": (Severity.WARNING,
              "feed not sharded over the mesh's dp axis: the global "
              "batch rides every chip whole — data parallelism is not "
              "engaged"),
    "PT743": (Severity.WARNING,
              "sharded fetch: the executor pins fetches replicated, so "
              "every step all-gathers the fetched value"),
    "PT744": (Severity.INFO,
              "no sharding propagation rule for this op: specs are "
              "conservatively replicated past it"),
    # -- source-level concurrency analysis (analysis/concurrency.py) ----
    # These three codes lint the framework's own Python source (lock
    # attributes, with-regions, thread entry points), not a Program IR;
    # Diagnostic.site carries file:line instead of an op_callstack.
    "PT800": (Severity.ERROR,
              "lock-order cycle: the static lock-order graph (nested "
              "with-regions + calls made while holding a lock) contains "
              "a cycle — two threads taking the locks in opposing order "
              "deadlock"),
    "PT801": (Severity.WARNING,
              "blocking call under a held lock: time.sleep, socket/HTTP "
              "I/O, subprocess waits, Event.wait() without timeout, "
              "block_until_ready or an unbounded queue op runs while a "
              "lock is held — every other thread needing the lock stalls "
              "for the full blocking duration"),
    "PT802": (Severity.WARNING,
              "unguarded cross-thread attribute: reachable from more "
              "than one thread entry point with at least one write and "
              "at least one access outside any lock region"),
    # -- numerics / precision analysis (analysis/numerics.py) -----------
    "PT900": (Severity.ERROR,
              "broken quant/dequant pairing: a fake-quant output is "
              "consumed where the int8 rewrite contract does not hold "
              "(non-GEMM consumer), or the quantized value is never "
              "consumed at all"),
    "PT901": (Severity.WARNING,
              "dead or non-persistable moving-average scale state in a "
              "training program: the running activation scale is not "
              "persistable (reset every step) or its update is never "
              "written back in place (the moving average never "
              "advances)"),
    "PT902": (Severity.ERROR,
              "overflowing cast: the statically-proven value interval "
              "exceeds the target dtype's finite range"),
    "PT903": (Severity.WARNING,
              "reduction accumulated in low precision: a reduce/"
              "layer_norm-family op sums a float16/bfloat16 input into a "
              "float16/bfloat16 output with no upcast around the "
              "accumulation"),
    "PT904": (Severity.WARNING,
              "AMP loss-scale coverage gap: loss scaling is active "
              "(check_finite_and_unscale present) but a gradient reaches "
              "an optimizer update without passing through unscale"),
    "PT905": (Severity.WARNING,
              "nonfinite-producing op: log/sqrt/rsqrt/div on an interval "
              "statically proven to contain 0 or negatives, with no "
              "guard narrowing the operand first"),
    "PT906": (Severity.INFO,
              "quantizable GEMM/conv site: eligible for int8 epilogue "
              "lowering (the quantizability work-list the int8 PR "
              "consumes)"),
}


@dataclasses.dataclass
class Diagnostic:
    code: str
    message: str
    block_idx: int = 0
    op_idx: Optional[int] = None
    op_type: Optional[str] = None
    site: str = ""  # user call site from the op's op_callstack attr

    @property
    def severity(self) -> str:
        return CODES[self.code][0]

    def __str__(self) -> str:
        loc = f"block {self.block_idx}"
        if self.op_idx is not None:
            loc += f" op {self.op_idx}"
        if self.op_type:
            loc += f" ({self.op_type})"
        s = f"{self.code} {self.severity}: {self.message} [{loc}]"
        if self.site:
            s += f"\n    created at {self.site}"
        return s


def format_diagnostics(diags: List[Diagnostic]) -> str:
    if not diags:
        return "no findings"
    order = {Severity.ERROR: 0, Severity.WARNING: 1, Severity.INFO: 2}
    by_sev = sorted(diags, key=lambda d: (order[d.severity], d.block_idx,
                                          d.op_idx if d.op_idx is not None
                                          else -1))
    counts = {}
    for d in diags:
        counts[d.severity] = counts.get(d.severity, 0) + 1
    head = ", ".join(f"{counts[s]} {s}(s)" for s in
                     (Severity.ERROR, Severity.WARNING, Severity.INFO)
                     if s in counts)
    return head + "\n" + "\n".join(str(d) for d in by_sev)


class ProgramVerificationError(ValueError):
    """Raised by ``check_program`` when error-severity findings exist; carries
    the full diagnostic list so callers can inspect programmatically."""

    def __init__(self, diags: List[Diagnostic]):
        self.diagnostics = diags
        errors = [d for d in diags if d.severity == Severity.ERROR]
        super().__init__(
            f"program verification failed with {len(errors)} error(s) "
            f"(FLAGS_check_program; see docs/ANALYSIS.md):\n"
            + format_diagnostics(diags))
