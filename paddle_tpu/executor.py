"""Scope + Executor: run programs as compiled XLA executables.

Reference: paddle/fluid/framework/executor.cc (per-op interpreter) and
python/paddle/fluid/executor.py:380 (Executor.run API). The rebuild keeps the
``exe.run(program, feed=..., fetch_list=...)`` contract but the execution model
is inverted: instead of dispatching 1 kernel per op per step, the whole block
is traced once into jax, jit-compiled, and cached keyed on (program version,
feed signature). Per step, the only Python work is a dict lookup + arg packing.

State threading: persistable vars live in a ``Scope`` as jax device arrays.
The compiled step function takes (feeds, state, rng_key) and returns
(fetches, new_state); state buffers PROVEN safe by the static liveness pass
(``analysis.liveness.safe_donation_set`` — every read precedes the last
write, var not fetched) are donated so XLA updates parameters in place —
the role of the reference's buffer-reuse/inplace passes
(ir/memory_optimize_pass/) is played by liveness-gated donation + XLA
buffer assignment.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import monitor as _monitor
from . import trace as _trace
from .core.types import np_dtype
from .framework import OpRole, Program, Variable, default_main_program
from .lowering import LowerCtx, lower_block, lower_op
from .profiler import RecordEvent
from .resilience import distributed as _dist
from .resilience import faults as _faults
from .resilience import nonfinite as _nonfinite
from .resilience.retry import call_with_retry

__all__ = ["Executor", "Scope", "global_scope", "scope_guard", "CPUPlace",
           "TPUPlace", "CUDAPlace", "default_place", "FETCH_LATER"]


# ---------------------------------------------------------------------------
# Places (reference: paddle/fluid/platform/place.h). CUDAPlace is accepted as
# an alias for TPUPlace so reference scripts run unmodified.
# ---------------------------------------------------------------------------

class Place:
    def __repr__(self):
        return type(self).__name__ + "()"


_runtime_started = False


def _start_runtime() -> None:
    """The program's first touch of JAX's backends, timed once into the
    gauge ``device_runtime_start_seconds`` (on a TPU host: the TPU
    runtime's own start, seconds of every process's set-up). Every place
    goes through here before it asks JAX for devices. Where the embedding
    process has started the backends already, there is nothing to time
    and the gauge stays unset."""
    global _runtime_started
    if _runtime_started:
        return
    _runtime_started = True
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return
    t0 = time.perf_counter()
    jax.local_devices()
    if _monitor.enabled():
        _monitor.gauge(
            "device_runtime_start_seconds",
            "wall of the process's first backend initialisation, when the "
            "program made it").set(time.perf_counter() - t0)


class CPUPlace(Place):
    def jax_device(self):
        _start_runtime()
        # local, not global: under multi-process the global list includes
        # other trainers' devices, which are not addressable here. backend=
        # "cpu" because plain local_devices() lists only the default backend
        # (on a TPU host that would silently hand back the TPU).
        return jax.local_devices(backend="cpu")[0]


class TPUPlace(Place):
    """The ``device_id``-th local accelerator. Asking for one where JAX
    found none is an error — never a quiet run on the host (a benchmark
    would print CPU times under device metric names)."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def jax_device(self):
        _start_runtime()
        devs = [d for d in jax.local_devices() if d.platform != "cpu"]
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: no accelerator #{self.device_id} — JAX found "
                f"{jax.local_devices()} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS')!r}); use CPUPlace() to "
                f"run on the host")
        return devs[self.device_id]


class CUDAPlace(TPUPlace):
    """Compat alias: reference scripts that say CUDAPlace(0) get the TPU."""


def default_place() -> Place:
    """The place every entry point (``Executor``, ``contrib.Trainer`` /
    ``Inferencer``, the serving engines, ``AnalysisPredictor``) uses when
    the caller names none: the accelerator when JAX's default backend is
    one, the host only where JAX itself is held to it
    (``JAX_PLATFORMS=cpu``, the test suite)."""
    _start_runtime()
    return CPUPlace() if jax.default_backend() == "cpu" else TPUPlace()


class Scope:
    """name -> device array store (reference: paddle/fluid/framework/scope.h).

    Flat rather than hierarchical: block-local temporaries never materialise
    (they are XLA intermediates), so only persistables and feeds live here.
    """

    # monotonic identity for executor cache keys: id(scope) can alias after
    # GC, silently handing a fresh Scope another scope's compiled step
    _serial_counter = itertools.count()

    def __init__(self, parent: Optional["Scope"] = None):
        self.vars: Dict[str, Any] = {}
        self.parent = parent
        self._serial = next(Scope._serial_counter)
        # serving dispatches from its own thread while user code may keep
        # running the same executor: the var map is lock-guarded so a
        # concurrent set_var can never tear a read (CPython dicts are
        # GIL-atomic per op, but read-modify-write sequences are not)
        self._lock = _monitor.make_rlock("Scope._lock")

    def var(self, name: str):
        with self._lock:
            return self.vars.get(name)

    def find_var(self, name: str):
        s = self
        while s is not None:
            with s._lock:
                if name in s.vars:
                    return s.vars[name]
            s = s.parent
        return None

    def set_var(self, name: str, value) -> None:
        with self._lock:
            self.vars[name] = value

    def drop_var(self, name: str) -> None:
        with self._lock:
            self.vars.pop(name, None)

    def new_scope(self) -> "Scope":
        return Scope(parent=self)

    def numpy(self, name: str) -> np.ndarray:
        v = self.find_var(name)
        return None if v is None else np.asarray(v)


def _shape_dtype_sig(v):
    """(shape, dtype) of a feed WITHOUT materializing it: np.asarray on a
    device-resident jax array forces a full device->host transfer on
    every cached-step lookup."""
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return (tuple(v.shape), str(v.dtype))
    a = np.asarray(v)
    return (tuple(a.shape), str(a.dtype))


def _feed_host_bytes(v) -> int:
    """Bytes a feed will move host->device, 0 for device-resident arrays.
    Never calls np.asarray on a jax array (that WOULD be the transfer)."""
    if isinstance(v, np.ndarray):
        return int(v.nbytes)
    if hasattr(v, "devices") or hasattr(v, "device_buffer"):
        return 0  # jax array: already on (some) device
    try:
        return int(np.asarray(v).nbytes)
    except Exception:
        return 0


def _feed_bytes(feed) -> int:
    return sum(_feed_host_bytes(v) for v in feed.values())


def _live_bytes(vals) -> int:
    return sum(int(getattr(v, "nbytes", 0) or 0) for v in vals)


def _feed_batch_rows(feed) -> int:
    """Leading feed dim (the cost-model batch); no host transfer."""
    batch = 1
    for v in (feed or {}).values():
        shape, _ = _shape_dtype_sig(v)
        if shape:
            batch = max(batch, int(shape[0]))
    return batch


def _has_nonfinite(v) -> bool:
    """Host-side coarse finite check (run_chained's FLAGS_check_nan_inf —
    a device->host pull per state var, only when the flag is on)."""
    a = np.asarray(v)
    return a.dtype.kind in "fc" and not np.isfinite(a).all()


def _own_donated(vals):
    """Donated step inputs must be jax Arrays the executor OWNS. A host
    numpy array (e.g. a param the user planted with scope.set_var) can be
    zero-copy-aliased by the runtime when alignment allows; donating that
    aliased buffer lets XLA write the step's output INTO the user's array.
    jit dispatch quietly skips donation for non-Array args; the AOT
    executables used since the monitor PR do not, so copy once here — the
    same host->device copy jit would have made."""
    return [v if isinstance(v, jax.Array) else jnp.array(v) for v in vals]


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


@contextlib.contextmanager
def scope_guard(scope: Scope):
    global _global_scope
    old, _global_scope = _global_scope, scope
    try:
        yield
    finally:
        _global_scope = old


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class _CompiledStep:
    """One jitted executable for (program, feed signature, fetch list)."""

    def __init__(self, fn, feed_names, donated_names, ro_names,
                 state_out_names, fetch_names):
        self.fn = fn
        self.feed_names = feed_names
        # donated: scope vars both read and re-written whose old buffer is
        # PROVEN dead after the step (analysis.liveness.safe_donation_set);
        # donated so XLA updates in place. ro: every other scope input —
        # read-only vars and donation-unsafe state (e.g. a fetched param);
        # never donated, updates still flow back via state_out.
        self.donated_names = donated_names
        self.ro_names = ro_names
        self.state_out_names = state_out_names
        self.fetch_names = fetch_names
        # ref set by the cache owner. Cache keys use program._serial (never
        # recycled), so this is no longer needed to prevent id() aliasing —
        # it is kept for debugging: step.program names the compiled source
        self.program = None
        # state_out vars that are read but NOT donated (donation-unsafe,
        # e.g. a fetched param): their old buffer is copied, not reused
        self.kept_names: List[str] = []
        # AOT executable: None = not yet built, False = a call found it
        # stricter than jit dispatch and switched this step to jit (see
        # _run_body), else the jax Compiled object. Set by
        # Executor._ensure_executable on the first call so trace+lower and
        # XLA-compile are timed as separate monitor stages.
        self._aot = None
        # pending monitor CompileRecord awaiting stage timings
        self._compile_event = None
        # durable-identity material for the warm-start executable cache
        # (FLAGS_aot_cache_dir): (kind, program, fetch, xla_opts,
        # extras...) stamped by the cache owner; combined with the arg
        # signature at first call (paddle_tpu.aot_cache)
        self._aot_cache_parts: Optional[tuple] = None
        # serializes the one-time AOT build when two threads race the same
        # step (serving dispatcher vs a user thread)
        self._aot_lock = _monitor.make_lock("_CompiledStep._aot_lock")


def analyze_block_io(block, feed_names: set, fetch_names) -> dict:
    """Classify the vars a compiled step reads/writes.

    Returns feed_order, state_in (scope vars read), state_out (persistables
    written), donated (read AND written AND proven safe to donate — see
    ``analysis.liveness.safe_donation_set``), ro (everything else the step
    reads: true read-only vars plus donation-unsafe state, whose buffers
    are never donated; their updates still flow back through state_out).
    Shared by Executor, CompiledProgram and the sharded trainer paths.

    Donation used to be the bare ``state_in ∩ state_out`` heuristic, which
    could hand XLA a buffer the fetch list still observes (a later fetch of
    the same array would then read a consumed buffer) and had no proof the
    old value was dead. The liveness pass supplies that proof; decisions
    are identical or strictly safer on every program.
    """
    from .analysis.liveness import safe_donation_set

    produced: set = set()
    state_in: List[str] = []
    state_out: List[str] = []
    for op in block.ops:
        if op.type in ("feed", "fetch"):
            continue
        for name in op.input_arg_names:
            if (name not in produced and name not in feed_names
                    and name not in state_in and name != "@EMPTY@"):
                state_in.append(name)
        for name in op.output_arg_names:
            if name == "@EMPTY@":
                continue
            produced.add(name)
            is_persistable = block.has_var(name) and block.var(name).persistable
            if is_persistable and name not in state_out:
                state_out.append(name)
    for n in fetch_names:
        if n not in produced and n not in feed_names and n not in state_in:
            state_in.append(n)
    safe = safe_donation_set(block, feed_names, fetch_names)
    donated = [n for n in state_in if n in state_out and n in safe]
    ro = [n for n in state_in if n not in donated]
    return {"feed_order": sorted(feed_names), "state_in": state_in,
            "state_out": state_out, "donated": donated, "ro": ro}


def make_step_fn(block, io: dict, fetch_names, mesh=None,
                 nan_check_meta=None, num_witness_meta=None, platform=None):
    """The traced step body shared by all execution paths.

    ``platform``: platform of the single device the step is lowered for
    (the executor passes its place's); a ``mesh`` speaks for itself. With
    neither, the step is lowered for no device and every kernel/layout
    route takes its portable primitive path
    (``lowering.lowering_platform``).

    ``nan_check_meta``: pass a list to enable FLAGS_check_nan_inf — at trace
    time it fills with one label per float op output and the step returns an
    extra bool vector (aligned with the labels) that the executor inspects
    host-side (reference operator.cc fast_check_nan_inf, but one fused
    check vector per step instead of a sync per op).

    ``num_witness_meta``: pass a list to enable FLAGS_numerics_witness — at
    trace time it fills with one var name per float op output and the step
    returns an extra ``(N, 4)`` [absmax, min, max, nonfinite-count] stats
    array as the LAST tuple element (after the nan-check vector when both
    are on); ``strip_witness_stats`` peels it off and merges it into
    ``monitor.numwitness``. One fused device->host stats transfer per step,
    same batching idiom as the nan checks."""

    def step_fn(feed_vals, donated_vals, ro_vals, rng_key):
        env: Dict[str, Any] = {}
        env.update(zip(io["feed_order"], feed_vals))
        env.update(zip(io["donated"], donated_vals))
        env.update(zip(io["ro"], ro_vals))
        checks = None if nan_check_meta is None else []
        taps = None if num_witness_meta is None else []
        ctx = LowerCtx(base_key=rng_key, mesh=mesh,
                       program=getattr(block, "program", None),
                       nan_checks=checks, num_taps=taps, platform=platform)
        lower_block(block, env, ctx)
        fetches = [env[n] for n in fetch_names]
        new_state = [env[n] for n in io["state_out"]]
        result = [fetches, new_state]
        if checks is not None:
            nan_check_meta.clear()
            nan_check_meta.extend(label for label, _ in checks)
            result.append(jnp.stack([ok for _, ok in checks])
                          if checks else jnp.ones((0,), bool))
        if taps is not None:
            num_witness_meta.clear()
            num_witness_meta.extend(name for name, _ in taps)
            result.append(jnp.stack([s for _, s in taps])
                          if taps else jnp.zeros((0, 4), jnp.float32))
        return tuple(result)

    return step_fn


def strip_witness_stats(step, result, to_host=np.asarray, path="run"):
    """FLAGS_numerics_witness protocol: a witness-instrumented step (one
    with ``step.num_witness_meta`` set) returns its ``(N, 4)`` per-var
    stats array as the LAST tuple element. Peel it off and merge it into
    ``monitor.numwitness`` BEFORE ``unpack_step_result`` runs — recording
    first means the witness attribution (``numwitness.first_offender``)
    is already fresh when a tripped nan check escalates or skips, which
    is what lets the skip counter and the flight recorder name the
    first offending var (docs/OBSERVABILITY.md)."""
    meta = getattr(step, "num_witness_meta", None)
    if meta is None:
        return result
    from .monitor import numwitness

    numwitness.record_step(list(meta), to_host(result[-1]), path=path)
    return result[:-1]


def unpack_step_result(step, result, scope, to_host=np.asarray, *,
                       path="run", exe=None, rollback=None):
    """Shared FLAGS_check_nan_inf protocol for every execution path: a
    3-tuple result carries the per-op finite flags.

    On a tripped check the outcome depends on ``FLAGS_nan_inf_policy``
    (resilience.nonfinite). With a ``rollback`` list of ``(name, pre-step
    value)`` pairs the scope is restored bit-exactly first; policy
    ``raise`` then raises FloatingPointError naming the op (catching it
    leaves a usable session on pre-step state), while ``skip``/
    ``zero_grad`` DROP the step — the skip is counted
    (``steps_skipped_nonfinite_total``) and ``(fetches, None)`` is
    returned, the caller skipping its state writeback. With
    ``rollback=None`` (a path that could not preserve pre-step buffers,
    e.g. multi-process global arrays) the step's outputs are written back
    FIRST (inputs were donated — without this the scope would reference
    deleted buffers and the session would be unusable after catching the
    error), then FloatingPointError names the op."""
    if len(result) != 3:
        return result
    fetches, new_state, ok_vec = result
    ok = np.asarray(to_host(ok_vec))
    if ok.all():
        _nonfinite.record_clean(exe)
        return fetches, new_state
    bad = int(np.argmin(ok))
    meta = getattr(step, "nan_check_meta", None) or []
    label = meta[bad] if bad < len(meta) else f"check #{bad}"
    if rollback is None:
        for n, v in zip(step.state_out_names, new_state):
            scope.set_var(n, v)
        raise FloatingPointError(
            f"FLAGS_check_nan_inf: non-finite value in {label}")
    for n, v in rollback:
        scope.set_var(n, v)
    if _nonfinite.policy() == "raise":
        raise FloatingPointError(
            f"FLAGS_check_nan_inf: non-finite value in {label} "
            f"(scope restored to pre-step values)")
    # counted AFTER the restore so even skip->raise escalation leaves the
    # scope holding the pre-step values
    _nonfinite.record_skip(path, label, exe)
    return fetches, None


def make_pipeline_step_fn(block, io: dict, fetch_names, mesh=None,
                          nan_check_meta=None, platform=None):
    """Microbatched step (PipelineOptimizer): the forward+backward ops run
    under a lax.scan over ``M`` microbatch slices of every feed,
    accumulating the parameter gradients; the optimize/lr ops then run ONCE
    on the averaged grads. This is the reference PipelineTrainer /
    SectionWorker schedule collapsed into one XLA program: the per-section
    scope queues (trainer.h:110, device_worker.h:267 SectionWorker) become
    the scan carry, and stage placement is GSPMD's job via sharding
    annotations rather than per-section Places.

    Fetches report the LAST microbatch's values (the reference fetches from
    the final section's scope). Requires batch % M == 0.
    """
    import jax.numpy as jnp

    from .framework import OpRole

    program = block.program
    M = int(getattr(program, "_pipeline_microbatches", 1))
    pgs = list(getattr(program, "_pipeline_param_grads", []))
    fb_ops = [op for op in block.ops
              if op.attrs.get("__op_role__", OpRole.Forward)
              in (OpRole.Forward, OpRole.Backward)]
    tail_ops = [op for op in block.ops
                if op.attrs.get("__op_role__", OpRole.Forward)
                not in (OpRole.Forward, OpRole.Backward)]
    grad_names = [g for _, g in pgs]
    param_names = [p for p, _ in pgs]
    # persistables the fwd/bwd section itself writes (e.g. BN stats) must
    # thread through the scan carry
    fb_written = {n for op in fb_ops for n in op.output_arg_names}
    fb_state = [n for n in io["state_out"] if n in fb_written]

    def step_fn(feed_vals, donated_vals, ro_vals, rng_key):
        base: Dict[str, Any] = {}
        base.update(zip(io["donated"], donated_vals))
        base.update(zip(io["ro"], ro_vals))
        feeds = []
        for n, v in zip(io["feed_order"], feed_vals):
            if v.shape[0] % M:
                raise ValueError(
                    f"pipeline: feed '{n}' batch {v.shape[0]} not divisible"
                    f" by num_microbatches={M}")
            feeds.append(v.reshape((M, v.shape[0] // M) + v.shape[1:]))
        keys = jax.random.split(rng_key, M)

        checks = None if nan_check_meta is None else []
        grads0 = [jnp.zeros(base[p].shape, base[p].dtype)
                  for p in param_names]
        carry0 = (grads0, {n: base[n] for n in fb_state})

        def micro(carry, xs):
            acc, st = carry
            key, slices = xs[0], xs[1:]
            env = dict(base)
            env.update(st)
            env.update(zip(io["feed_order"], slices))
            ctx = LowerCtx(base_key=key, mesh=mesh, program=program,
                           nan_checks=None, platform=platform)
            for op in fb_ops:
                lower_op(op, env, ctx)
            new_acc = [a + env[g] for a, g in zip(acc, grad_names)]
            new_st = {n: env[n] for n in fb_state}
            # only fb-PRODUCED fetches come from the scan; anything else
            # (params, lr) must read the post-tail env or it would fetch
            # stale pre-update values
            outs = {n: env[n] for n in fetch_names if n in fb_written}
            return (new_acc, new_st), outs

        (acc, st), fetched = jax.lax.scan(
            micro, carry0, (keys,) + tuple(feeds))
        env = dict(base)
        env.update(st)
        avg = bool(getattr(program, "_grad_merge_avg", True))
        for g, a in zip(grad_names, acc):
            env[g] = a / M if avg else a
        if checks is not None:
            # fb ops run inside the scan (their tracers can't escape), so
            # the fwd/bwd sanitizer coverage is the accumulated grads and
            # carried state checked here, plus per-op checks on tail ops
            for g, a in zip(grad_names, acc):
                checks.append((f"accumulated gradient '{g}' "
                               f"(fwd/bwd microbatch scan)",
                               jnp.isfinite(a).all()))
            for n, v in st.items():
                checks.append((f"carried state '{n}' (microbatch scan)",
                               jnp.isfinite(v).all()))
        ctx = LowerCtx(base_key=rng_key, mesh=mesh, program=program,
                       nan_checks=checks, platform=platform)
        for op in tail_ops:
            lower_op(op, env, ctx)
        fetches = [fetched[n][-1] if n in fetched else env[n]
                   for n in fetch_names]
        new_state = [env[n] for n in io["state_out"]]
        if checks is not None:
            nan_check_meta.clear()
            nan_check_meta.extend(label for label, _ in checks)
            flags_vec = (jnp.stack([ok for _, ok in checks])
                         if checks else jnp.ones((0,), bool))
            return fetches, new_state, flags_vec
        return fetches, new_state

    return step_fn


def pick_step_fn(program):
    """make_step_fn, or the microbatched variant when the program was
    prepared by PipelineOptimizer."""
    if int(getattr(program, "_pipeline_microbatches", 1)) > 1:
        return make_pipeline_step_fn
    return make_step_fn


class Executor:
    """Reference API (executor.py:380): run / close; plus train loop helpers."""

    def __init__(self, place: Optional[Place] = None):
        self.place = place or default_place()
        self._cache: Dict[tuple, _CompiledStep] = {}
        self._step_counter = 0
        # program fingerprints already verified under FLAGS_check_program
        self._verified: set = set()
        # FLAGS_auto_recompute: (program fingerprint, batch, budget) ->
        # transformed program (or the original when the pass refused).
        # The transformed program is a fresh Program with its own _serial,
        # so step-cache keys can never alias remat and plain variants.
        self._remat_cache: Dict[tuple, Program] = {}
        # guards the caches above + the seed counter: the serving engine
        # runs this executor from its dispatch thread while the owning
        # thread may still call run() — an unguarded dict resize mid-probe
        # or a torn counter would corrupt the compile cache
        self._lock = _monitor.make_rlock("Executor._lock")

    def _maybe_auto_remat(self, program: Program, feed, fetch_names):
        """FLAGS_auto_recompute entry shared by run / run_chained /
        CompiledProgram: swap a training program for its auto-checkpointed
        rebuild (analysis/remat.py). Inference programs, pipeline programs
        and anything the pass cannot faithfully rebuild pass through
        untouched. Decisions are cached per (program, batch, budget)."""
        from .flags import flag

        if not flag("auto_recompute") or not isinstance(program, Program):
            return program
        batch = 1
        for v in (feed or {}).values():
            shape, _ = _shape_dtype_sig(v)
            if shape:
                batch = max(batch, int(shape[0]))
        budget = int(flag("remat_budget_mb"))
        # fetch_names are part of the key: a transform built for one fetch
        # list keeps only THOSE fetches alive across segments, so a later
        # run fetching a different activation needs its own rebuild. The
        # lookup comes before any program scan so steady-state dispatches
        # pay one dict probe, nothing op-count-shaped.
        key = (self._program_fingerprint(program), batch, budget,
               tuple(fetch_names or ()))
        # whole decision under the executor lock: a racing second thread
        # must reuse the SAME transformed program (a duplicate rebuild
        # would fork two serials and recompile everything downstream)
        with self._lock:
            cached = self._remat_cache.get(key)
            if cached is not None:
                return cached
            from .analysis.remat import is_trainable_program

            # startup/inference programs cannot remat by construction; pass
            # through (cached) with no monitor record — a 'refused' count
            # here would read as a training program the pass could not
            # handle
            if not is_trainable_program(program):
                self._remat_cache[key] = program
                return program
            # the transform runs as a registered pass through the manager
            # (ROADMAP item 5): at FLAGS_check_program>=2 the pipeline
            # re-verifies the rebuilt program and refuses a corrupting
            # transform with PassVerificationError
            from .analysis.pass_manager import run_transform_pipeline

            result = run_transform_pipeline(
                program, ("auto_remat",), feed_names=sorted(feed or {}),
                fetch_names=list(fetch_names or ()), batch_size=batch,
                options={"budget_mb": budget})
            decision = result.values["auto_remat"]
            _monitor.record_remat(decision)
            self._remat_cache[key] = decision.program
            return decision.program

    def _verify_once(self, program: Program, fetch_names) -> None:
        """FLAGS_check_program pre-run hook: static-verify each program
        version once before it compiles (the build-time role of the
        reference's op_registry.h checks). Raises ProgramVerificationError
        with build-site diagnostics on error-severity findings. Runs the
        verifier passes through ``PassManager.run_pipeline`` (ROADMAP item
        5), so per-pass timings land on the monitor registry."""
        from .flags import flag

        if not int(flag("check_program")):
            return
        fp = self._program_fingerprint(program)
        with self._lock:
            if fp in self._verified:
                return
        from .analysis.pass_manager import run_verify_pipeline

        run_verify_pipeline(program, fetch_names=fetch_names)
        with self._lock:
            self._verified.add(fp)

    # -- public API ------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
    ):
        from .parallel.compiled_program import CompiledProgram

        if isinstance(program, CompiledProgram):
            self._verify_once(program.program,
                              [f.name if isinstance(f, Variable) else f
                               for f in (fetch_list or [])])
            return program._run(self, feed, fetch_list, scope, return_numpy)

        # pserver-role program from the DistributeTranspiler shim: nothing
        # to serve on TPU (params live on-chip), return immediately so 2019
        # PS launch scripts complete cleanly
        if getattr(program, "_is_pserver_noop", False):
            return []

        program = program or default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]

        # child of whatever request/step trace is ambient on this thread
        # (serving attaches the request root; the Trainer its step root).
        # Its phases, contiguous children (OBSERVABILITY.md "Phase spans"):
        # bind, feed, bind, step, writeback, fetch, writeback. bind comes
        # twice because the lookup names the feeds and the executable is
        # lowered from the fed arrays.
        with _trace.span("executor.run") as sp:
            mrec = None
            try:
                with _trace.phase("executor.bind") as ph:
                    program = self._maybe_auto_remat(program, feed,
                                                     fetch_names)
                    self._verify_once(program, fetch_names)
                    mrec = _monitor.step_begin("run", program)
                    step = self._get_compiled(
                        program, feed, fetch_names, scope,
                        use_cache=use_program_cache, mrec=mrec)
                    if ph.traced:
                        sp.set_attribute(
                            "program", int(getattr(program, "_serial", -1)))
                        ph.set_attributes(cache_hit=bool(mrec.cache_hit)
                                          if mrec is not None else None)
                return self._run_body(program, feed, scope, return_numpy,
                                      step, mrec)
            finally:
                # always paired with step_begin — a step that raises (e.g.
                # FLAGS_check_nan_inf) still counts and hooks stay in sync.
                # The monitor's accounting is the tail of the writeback
                # phase: results to the scope, then to the records.
                with _trace.phase("executor.writeback"):
                    self._step_end(mrec)

    def _run_body(self, program, feed, scope, return_numpy, step, mrec):
        device = self.place.jax_device()
        cache_hit = None
        if mrec is not None:
            mrec.fetch_names = step.fetch_names
            mrec.feed_bytes = _feed_bytes(feed)
            mrec.batch_rows = _feed_batch_rows(feed)
            mrec.device_kind = device.device_kind
            cache_hit = bool(mrec.cache_hit)
        with _trace.phase("executor.feed") as ph:
            feed_vals = [self._to_device_array(feed[n], program, n)
                         for n in step.feed_names]
            if ph.traced:
                ph.set_attributes(bytes=_feed_bytes(feed))

        def read_state(names):
            vals = []
            blk = program.global_block
            for n in names:
                v = scope.find_var(n)
                if v is None:
                    if blk.has_var(n) and blk.var(n).is_data:
                        raise RuntimeError(
                            f"Input variable '{n}' is declared as data but was "
                            f"not passed in feed={{...}}")
                    raise RuntimeError(
                        f"Variable '{n}' is not initialized in scope — run the "
                        f"startup program first (reference: executor.cc var-init check)"
                    )
                vals.append(v)
            return vals

        with _trace.phase("executor.bind", cache_hit=cache_hit):
            donated_vals = read_state(step.donated_names)
            ro_vals = read_state(step.ro_names)
            # step-site fault probe fires BEFORE any buffer is donated, so
            # an injected step failure leaves the scope fully usable
            _faults.fault_point("step")
            if mrec is not None:
                mrec.donated_buffers = len(step.donated_names)
                mrec.kept_buffers = len(step.kept_names)
                mrec.donated_bytes = _live_bytes(donated_vals)
            key = jax.random.key(self._next_seed(program))
            rollback = None
            with jax.default_device(device):
                if step.nan_check_meta is not None \
                        and _nonfinite.rollback_active():
                    # nan_inf_policy=skip|zero_grad must be able to restore
                    # the EXACT pre-step bits, but donation consumes the
                    # inputs — so donate fresh device copies and keep the
                    # originals
                    rollback = list(zip(step.donated_names, donated_vals))
                    donated_vals = [jnp.array(v) for v in donated_vals]
                else:
                    # inside default_device so the one-time host->device
                    # copy of planted numpy state lands on THIS executor's
                    # device
                    donated_vals = _own_donated(donated_vals)
                fn = self._ensure_executable(
                    step, (feed_vals, donated_vals, ro_vals, key))
        # watchdog-armed dispatch: a hang here (injected via the 'hang'
        # fault site, or a real stuck collective) is dumped + raised as
        # WatchdogTimeout under FLAGS_step_timeout_s
        with jax.default_device(device), \
                _trace.phase("executor.step", timed=mrec is not None) as ph, \
                self._launched(ph, step, mrec, cache_hit), \
                _dist.watchdog_section("step", program=program) as tok:
            _faults.fault_point("hang")
            try:
                result = fn(feed_vals, donated_vals, ro_vals, key)
            except (TypeError, ValueError):
                if fn is step.fn:
                    raise
                # the AOT executable is stricter than jit dispatch:
                # structure mismatches raise TypeError, committed-to-
                # another-device shardings raise ValueError — both are
                # checked before any buffer is donated, so retry through
                # jit (which adapts) and stop using the AOT fast path for
                # this step
                step._aot = False
                result = step.fn(feed_vals, donated_vals, ro_vals, key)
            if tok is not None:
                # dispatch is async — without this the section would
                # disarm before a stuck device computation ever ran. Only
                # under FLAGS_step_timeout_s, which opts into
                # deadline-over-overlap
                jax.block_until_ready(result)
        with _trace.phase("executor.writeback"):
            result = strip_witness_stats(step, result, path="run")
            fetches, new_state = unpack_step_result(step, result, scope,
                                                    path="run", exe=self,
                                                    rollback=rollback)
            if new_state is not None:
                for n, v in zip(step.state_out_names, new_state):
                    scope.set_var(n, v)
        if not return_numpy:
            return list(fetches)
        return self._fetched(fetches, mrec, return_numpy)

    def _fetch_to_host(self, fetches, mrec, parent=None):
        """The ``executor.fetch`` phase: the host blocks on the device's
        results and copies them back (``fetch_wait_s``; its exit ``ready_t``)."""
        with _trace.phase("executor.fetch", parent=parent,
                          timed=mrec is not None) as ph:
            outs = [np.asarray(v) for v in fetches]
            if ph.traced:
                ph.set_attributes(bytes=_live_bytes(outs))
        if mrec is not None:
            mrec.fetch_bytes = _live_bytes(outs)
            mrec.fetch_wait_s, mrec.ready_t = ph.seconds, ph.t1
            self._landed(mrec)
        return outs

    def run_chained(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence[Union[str, Variable]]] = None,
        steps: int = 1,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
    ):
        """Run ``steps`` iterations of ``program`` as ONE compiled dispatch:
        a ``lax.scan`` over the step body with the parameter state threaded
        through the carry. Returns fetches stacked along a leading ``steps``
        axis; the scope holds the final-step state, exactly as if ``run``
        had been called ``steps`` times with the same feed.

        This is the reference's run-the-loop-in-C++ role (trainer.cc
        multi-iteration RunFromDataset) done the XLA way: one dispatch and
        one fetch for ``steps`` iterations, which is how the generative
        engine decodes a chunk of tokens.

        The same feed batch is used for every iteration (overfit-one-batch
        semantics); real input pipelines stream via DataLoader + ``run``.
        FLAGS_check_nan_inf here is a COARSE whole-dispatch check (per-op
        flags would have to be stacked across steps): the final carried
        state is checked host-side after the
        scan, and a trip raises/skips the entire ``steps``-iteration
        dispatch per FLAGS_nan_inf_policy — use ``run`` for per-op
        provenance.
        """
        program = program or default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        if int(getattr(program, "_pipeline_microbatches", 1)) > 1:
            raise NotImplementedError(
                "run_chained with PipelineOptimizer programs: the pipeline "
                "step is already a scan; nest via GradientMergeOptimizer")

        with _trace.span("executor.run_chained", steps=int(steps)) as sp:
            mrec = None
            try:
                with _trace.phase("executor.bind") as ph:
                    program = self._maybe_auto_remat(program, feed,
                                                     fetch_names)
                    self._verify_once(program, fetch_names)
                    mrec = _monitor.step_begin("chained", program)
                    step, hit = self._lookup_chained(
                        program, program, feed, fetch_names, steps, scope,
                        mrec)
                    if ph.traced:
                        sp.set_attribute(
                            "program", int(getattr(program, "_serial", -1)))
                        ph.set_attributes(cache_hit=hit)
                return self._dispatch_chained(program, feed, steps, scope,
                                              return_numpy, step, mrec, hit)
            finally:
                with _trace.phase("executor.writeback"):
                    self._step_end(mrec)

    def _lookup_chained(self, submitted, program, feed, fetch_names, steps,
                        scope, mrec):
        """The chained cache key, its lookup, and the scan wrapper's build
        on a miss (first segment of ``executor.bind``)."""
        # ``submitted`` is unused: benchmark/tools/deviceless_decode.py and
        # deviceless_stored.py still pass it by position (ROADMAP D1, D2)
        from .flags import xla_options

        opts = xla_options()
        xla_opts = tuple(sorted(opts.items()))
        feed_sig = tuple(sorted(
            (n,) + _shape_dtype_sig(v) for n, v in feed.items()))
        key = ("chained", self._program_fingerprint(program), feed_sig,
               tuple(fetch_names), int(steps), scope._serial, xla_opts)
        with self._lock:
            step = self._cache.get(key)
        hit = step is not None
        if mrec is not None:
            mrec.cache_hit = hit
            mrec.iterations = int(steps)
            mrec.fetch_names = tuple(fetch_names)
            mrec.feed_bytes = _feed_bytes(feed)
            mrec.batch_rows = _feed_batch_rows(feed)
            mrec.device_kind = self.place.jax_device().device_kind
        _monitor.record_cache_lookup("chained", hit)
        if step is None:
            step = self._build_chained_step(program, feed, fetch_names,
                                            steps, scope, key, feed_sig,
                                            opts, xla_opts)
        return step, hit

    def _build_chained_step(self, program, feed, fetch_names, steps, scope,
                            key, feed_sig, opts, xla_opts):
        # under the executor lock with a double-check: a racing thread
        # must reuse the same scan wrapper, not fork a second compile
        with self._lock:
            step = self._cache.get(key)
            if step is not None:
                return step
            block = program.global_block
            io = analyze_block_io(block, set(feed.keys()), fetch_names)
            # carried: ALL read+written state threads through the scan carry
            # (a donation-unsafe var — e.g. a fetched param — must still
            # chain step to step; reading it as a loop-invariant would hand
            # every iteration the stale pre-run value). donated ⊆ carried is
            # the subset whose INPUT buffers may be donated at the jit
            # boundary.
            kept = [n for n in io["ro"] if n in io["state_out"]]
            carried = list(io["donated"]) + kept
            carried_set = set(carried)
            ro_names = [n for n in io["ro"] if n not in carried_set]
            io2 = dict(io, donated=carried, ro=ro_names)
            base_step = make_step_fn(
                block, io2, fetch_names,
                platform=self.place.jax_device().platform)
            idx = {n: i for i, n in enumerate(io["state_out"])}
            wo_names = [n for n in io["state_out"] if n not in carried_set]

            # The anti-hoisting chain (ROADMAP D2; ``chain_eps`` is the
            # seventh argument the benchmark's deviceless tools pass).
            # Inference programs would let XLA's loop-invariant code motion
            # hoist the whole body out of the scan. Feed a runtime-zero
            # perturbation chained off each step's first fetch into the
            # first float feed (falling back to the smallest float
            # read-only input, then the smallest float carried input, for
            # feed-less programs like GPT decode — the source falls back
            # from fetches to the smallest float carried output): exact
            # results (the scalar IS zero at runtime), but the compiler
            # cannot prove it, so the bodies stay serialized.
            # Keyed on "not training", not on "nothing carried": a for_test
            # clone whose only carried state is identity-written batch_norm
            # statistics is a fixed-point carry the while-loop simplifier
            # still hoists. Training programs chain through the optimizer's
            # parameter updates.
            is_training = any(
                op.attrs.get("__op_role__", OpRole.Forward)
                != OpRole.Forward for op in block.ops)
            needs_chain = not is_training

            def _is_float(v) -> bool:
                return jnp.issubdtype(jnp.result_type(v), jnp.inexact)

            def _smallest_float_i(vals):
                cands = [(v.size, i) for i, v in enumerate(vals)
                         if _is_float(v) and v.size]
                return min(cands)[1] if cands else None

            def multi_fn(feed_vals, donated_vals, kept_vals, ro_vals, keys,
                         wo_init, chain_eps):
                # perturbation target: float feed first (the original
                # protocol), else the SMALLEST float ro / carried input so
                # a feed-less decode program pays one tiny add per step,
                # not a KV-cache-sized one
                float_i = ro_i = carry_i = None
                carried_init = list(donated_vals) + list(kept_vals)
                if needs_chain:
                    float_i = next((i for i, v in enumerate(feed_vals)
                                    if _is_float(v)), None)
                    if float_i is None:
                        ro_i = _smallest_float_i(ro_vals)
                    if float_i is None and ro_i is None:
                        carry_i = _smallest_float_i(carried_init)
                chained = (float_i is not None or ro_i is not None
                           or carry_i is not None)

                def body(carry, k):
                    cur, _, s = carry
                    fv = list(feed_vals)
                    rv = ro_vals
                    cv = cur
                    if float_i is not None:
                        fv[float_i] = fv[float_i] + (
                            chain_eps * s).astype(fv[float_i].dtype)
                    elif ro_i is not None:
                        rv = list(ro_vals)
                        rv[ro_i] = rv[ro_i] + (
                            chain_eps * s).astype(rv[ro_i].dtype)
                    elif carry_i is not None:
                        cv = list(cur)
                        cv[carry_i] = cv[carry_i] + (
                            chain_eps * s).astype(cv[carry_i].dtype)
                    fetches, new_state = base_step(fv, cv, rv, k)
                    new_carried = [new_state[idx[n]] for n in carried]
                    new_wo = [new_state[idx[n]] for n in wo_names]
                    s_next = s
                    if chained:
                        # chain source: first float fetch (the original
                        # protocol), else any non-empty fetch (int token
                        # ids chain just as well — they depend on the
                        # perturbed input), else the smallest float
                        # carried output
                        src = next((f for f in fetches
                                    if _is_float(f) and f.size), None)
                        if src is None:
                            src = next((f for f in fetches if f.size),
                                       None)
                        if src is None:
                            j = _smallest_float_i(new_carried)
                            src = new_carried[j] if j is not None else None
                        if src is not None:
                            s_next = src.ravel()[0].astype(jnp.float32)
                    return (new_carried, new_wo, s_next), fetches

                (fin_carried, fin_wo, _), stacked = jax.lax.scan(
                    body, (carried_init, wo_init, jnp.float32(0)), keys)
                return stacked, fin_carried, fin_wo

            jitted = jax.jit(multi_fn, donate_argnums=(1,),
                             compiler_options=opts or None)
            step = _CompiledStep(jitted, io["feed_order"], io["donated"],
                                 ro_names, io["state_out"],
                                 tuple(fetch_names))
            step.program = program
            step.needs_chain = needs_chain
            step._aot_cache_parts = ("chained", program,
                                     tuple(fetch_names), xla_opts,
                                     int(steps))
            step._compile_event = _monitor.observe_compile(
                "chained", program,
                components={
                    "program": self._program_fingerprint(program)[1:],
                    "feed_signature": feed_sig,
                    "fetch_list": tuple(fetch_names),
                    "scope": scope._serial,
                    "steps": int(steps),
                    "xla_options": xla_opts,
                },
                donated_names=io["donated"])
            step.kept_names = kept
            step.carried_names = carried
            step.wo_names = wo_names
            step.io = io
            step.base_step = base_step
            step.wo_shapes = None
            self._cache[key] = step
            return step

    def _dispatch_chained(self, program, feed, steps, scope,
                          return_numpy, step, mrec, cache_hit):
        # the same phases as _run_body, children of executor.run_chained
        with _trace.phase("executor.feed") as ph:
            feed_vals = [self._to_device_array(feed[n], program, n)
                         for n in step.feed_names]
            if ph.traced:
                ph.set_attributes(bytes=_feed_bytes(feed))
        with _trace.phase("executor.bind", cache_hit=cache_hit):
            args, fn, rollback, check = self._bind_chained(
                program, steps, scope, step, mrec, feed_vals)
        with jax.default_device(self.place.jax_device()), \
                _trace.phase("executor.step", timed=mrec is not None) as ph, \
                self._launched(ph, step, mrec, cache_hit), \
                _dist.watchdog_section("chained", program=program) as tok:
            _faults.fault_point("hang")
            try:
                stacked, fin_carried, fin_wo = fn(*args)
            except (TypeError, ValueError):
                if fn is step.fn:
                    raise
                step._aot = False
                stacked, fin_carried, fin_wo = step.fn(*args)
            if tok is not None:
                # async dispatch: keep the section armed until the
                # scanned computation actually finished on device
                jax.block_until_ready((stacked, fin_carried, fin_wo))
        with _trace.phase("executor.writeback"):
            self._writeback_chained(steps, scope, step, rollback, check,
                                    fin_carried, fin_wo)
        if not return_numpy:
            return list(stacked)
        return self._fetched(stacked, mrec, return_numpy)

    def _bind_chained(self, program, steps, scope, step, mrec, feed_vals):
        """State, keys, write-only carries and the executable of one
        chained dispatch (second segment of ``executor.bind``)."""
        donated_vals = [scope.find_var(n) for n in step.donated_names]
        kept_vals = [scope.find_var(n) for n in step.kept_names]
        ro_vals = [scope.find_var(n) for n in step.ro_names]
        for n, v in zip(step.carried_names + step.ro_names,
                        donated_vals + kept_vals + ro_vals):
            if v is None:
                raise RuntimeError(
                    f"Variable '{n}' is not initialized in scope — run the "
                    f"startup program first")
        keys = jax.random.split(
            jax.random.key(self._next_seed(program)), steps)
        # write-only persistables (produced fresh each step, never read):
        # shape them abstractly so the scan carry can thread them
        if step.wo_shapes is None:
            out_shapes = jax.eval_shape(step.base_step, feed_vals,
                                        donated_vals + kept_vals, ro_vals,
                                        keys[0])
            wo_idx = {n: i for i, n in enumerate(step.io["state_out"])}
            step.wo_shapes = [(out_shapes[1][wo_idx[n]].shape,
                               out_shapes[1][wo_idx[n]].dtype)
                              for n in step.wo_names]
            if getattr(step, "needs_chain", not step.carried_names):
                # chained measurement honesty: the anti-hoisting chain (see
                # multi_fn) needs a float input to perturb (feed, or for
                # feed-less programs like GPT decode a read-only/carried
                # input) AND a non-empty output to carry the chain through
                # (any fetch, or a float carried output); without both, XLA
                # hoists the loop-invariant body and a timing of K steps
                # measures ONE — warn loudly rather than let a benchmark
                # silently report K x real throughput
                def _inexact(v):
                    return jnp.issubdtype(jnp.result_type(v), jnp.inexact)

                can_perturb = any(
                    _inexact(v) for v in feed_vals) or any(
                    _inexact(v) and v.size
                    for v in ro_vals + donated_vals + kept_vals)
                can_carry = any(
                    s.size for s in out_shapes[0]) or any(
                    _inexact(v) and v.size
                    for v in donated_vals + kept_vals)
                if not (can_perturb and can_carry):
                    import warnings

                    warnings.warn(
                        "run_chained: program has no trainable state and "
                        "no float input / non-empty output pair to chain "
                        "iterations through — XLA may hoist the body and "
                        "execute it ONCE; do not use this timing as a "
                        "per-step measurement",
                        RuntimeWarning, stacklevel=3)
        wo_init = [jnp.zeros(s, d) for s, d in step.wo_shapes]
        # step-site fault probe fires BEFORE donation, scope stays usable
        _faults.fault_point("step")
        from .flags import flag

        check = flag("check_nan_inf")
        rollback = None
        if mrec is not None:
            mrec.donated_buffers = len(step.donated_names)
            mrec.kept_buffers = len(step.kept_names)
            mrec.donated_bytes = _live_bytes(donated_vals)
        with jax.default_device(self.place.jax_device()):
            if check and _nonfinite.rollback_active():
                # pre-dispatch image of the donated carry so a tripped scan
                # can be dropped bit-exactly (see unpack_step_result)
                rollback = list(zip(step.donated_names, donated_vals))
                donated_vals = [jnp.array(v) for v in donated_vals]
            else:
                # inside default_device so the one-time host->device copy
                # of planted numpy state lands on THIS executor's device
                donated_vals = _own_donated(donated_vals)
            args = (feed_vals, donated_vals, kept_vals, ro_vals, keys,
                    wo_init, jnp.float32(0))
            fn = self._ensure_executable(step, args)
        return args, fn, rollback, check

    def _writeback_chained(self, steps, scope, step, rollback, check,
                           fin_carried, fin_wo) -> None:
        """The coarse non-finite check and the scope writeback of one
        chained dispatch; a dispatch dropped under ``FLAGS_nan_inf_policy``
        skip/zero_grad leaves the scope on its pre-scan values."""
        if check:
            bad = next((n for n, v in
                        list(zip(step.carried_names, fin_carried))
                        + list(zip(step.wo_names, fin_wo))
                        if _has_nonfinite(v)), None)
            if bad is not None:
                label = (f"final state '{bad}' after {steps} scanned "
                         f"iteration(s)")
                if rollback is None:
                    for n, v in zip(step.carried_names, fin_carried):
                        scope.set_var(n, v)
                    for n, v in zip(step.wo_names, fin_wo):
                        scope.set_var(n, v)
                    raise FloatingPointError(
                        f"FLAGS_check_nan_inf: non-finite value in {label} "
                        f"(run_chained coarse check; use run for per-op "
                        f"provenance)")
                for n, v in rollback:
                    scope.set_var(n, v)
                if _nonfinite.policy() == "raise":
                    raise FloatingPointError(
                        f"FLAGS_check_nan_inf: non-finite value in {label} "
                        f"(run_chained coarse check, scope restored to "
                        f"pre-scan values; use run for per-op provenance)")
                _nonfinite.record_skip("chained", label, self)
                return
            _nonfinite.record_clean(self)
        for n, v in zip(step.carried_names, fin_carried):
            scope.set_var(n, v)
        for n, v in zip(step.wo_names, fin_wo):
            scope.set_var(n, v)

    def close(self):
        with self._lock:
            self._cache.clear()
            self._verified.clear()
            self._remat_cache.clear()

    # -- internals -------------------------------------------------------
    def _next_seed(self, program: Program) -> int:
        with self._lock:
            self._step_counter += 1
            counter = self._step_counter
        base = program.random_seed or 0
        return (base * 1_000_003 + counter) & 0x7FFFFFFF

    def _to_device_array(self, value, program, name):
        if isinstance(value, (np.ndarray, list, tuple, int, float)):
            arr = np.asarray(value)
            blk = program.global_block
            if blk.has_var(name):
                want = np_dtype(blk.var(name).dtype)
                if arr.dtype != want and arr.dtype.kind == want.kind:
                    arr = arr.astype(want)

            def _put():
                # transient-site: host->device transfer can fail for
                # infrastructure reasons (preempted device, RPC hiccup);
                # retry with backoff, never for shape/dtype errors
                _faults.fault_point("device_put")
                return jnp.asarray(arr)
            return call_with_retry("device_put", _put)
        return value

    def _program_fingerprint(self, program: Program) -> tuple:
        # _version counts op appends AND Operator.set_attr mutations, so
        # flipping e.g. is_test on a cached program recompiles (the reference
        # invalidates via desc version); op count catches op removal, which
        # bumps no counter. _serial (not id()) so GC can never alias two
        # programs onto one cache entry.
        return (program._serial, getattr(program, "_version", 0),
                sum(len(b.ops) for b in program.blocks))

    def _get_compiled(self, program, feed, fetch_names, scope,
                      use_cache: bool = True, mrec=None) -> _CompiledStep:
        feed_sig = tuple(sorted(
            (n,) + _shape_dtype_sig(v) for n, v in feed.items()
        ))
        from .flags import flag, xla_options

        opts = xla_options()
        xla_opts = tuple(sorted(opts.items()))
        key = (self._program_fingerprint(program), feed_sig,
               tuple(fetch_names), scope._serial, flag("check_nan_inf"),
               flag("numerics_witness"), xla_opts)
        # the whole lookup-or-build runs under the executor lock: two
        # threads racing the same key must share ONE step (and one monitor
        # compile record); _compile only builds the jit wrapper — the
        # expensive XLA build happens later under the step's own _aot_lock,
        # so unrelated steps still compile in parallel
        with self._lock:
            hit = use_cache and key in self._cache
            _monitor.record_cache_lookup("run", hit)
            if mrec is not None:
                mrec.cache_hit = hit
            if hit:
                return self._cache[key]
            with RecordEvent("executor::build_step"):
                step = self._compile(program, set(feed.keys()), fetch_names,
                                     scope, xla_opts=opts)
            step.program = program
            if not flag("check_nan_inf") and not flag("numerics_witness"):
                # nan-checked steps are NOT disk-cached: their per-op
                # provenance labels (nan_check_meta) are filled at trace
                # time, which a loaded executable skips — a tripped
                # check would lose the op attribution that is the
                # flag's whole point. (The chained path's coarse
                # host-side check carries no meta, so it stays cached.)
                # Witness-instrumented steps skip it for the same reason:
                # num_witness_meta's var names are filled at trace time.
                step._aot_cache_parts = ("run", program,
                                         tuple(fetch_names), xla_opts)
            step._compile_event = _monitor.observe_compile(
                "run", program,
                components={
                    "program": self._program_fingerprint(program)[1:],
                    "feed_signature": feed_sig,
                    "fetch_list": tuple(fetch_names),
                    "scope": scope._serial,
                    "flags": (("check_nan_inf", flag("check_nan_inf")),),
                    "xla_options": xla_opts,
                },
                donated_names=step.donated_names)
            self._cache[key] = step
            return step

    def _compile(self, program: Program, feed_names: set, fetch_names,
                 scope, xla_opts=None):
        from .flags import flag, xla_options

        if xla_opts is None:
            xla_opts = xla_options()
        block = program.global_block
        io = analyze_block_io(block, feed_names, fetch_names)
        meta = [] if flag("check_nan_inf") else None
        maker = pick_step_fn(program)
        # numerics witness: make_step_fn path only — the microbatched
        # pipeline body runs under lax.scan, where per-op taps would be
        # tracer escapes (same reason its nan checks are the coarse kind)
        wmeta = ([] if flag("numerics_witness") and maker is make_step_fn
                 else None)
        kwargs = dict(nan_check_meta=meta,
                      platform=self.place.jax_device().platform)
        if wmeta is not None:
            kwargs["num_witness_meta"] = wmeta
        step_fn = maker(block, io, fetch_names, **kwargs)
        jitted = jax.jit(step_fn, donate_argnums=(1,),
                         compiler_options=xla_opts or None)
        step = _CompiledStep(jitted, io["feed_order"], io["donated"],
                             io["ro"], io["state_out"], tuple(fetch_names))
        step.kept_names = [n for n in io["ro"] if n in io["state_out"]]
        step.nan_check_meta = meta  # filled lazily at first trace
        step.num_witness_meta = wmeta  # ditto
        return step

    def _ensure_executable(self, step: _CompiledStep, args):
        """First call of a freshly compiled step: run the AOT pipeline
        explicitly so jaxpr-trace+StableHLO-lower and XLA-compile are
        measured as separate monitor stages (TVM's lesson in PAPERS.md:
        treat compile and execute cost as first-class, separately measured
        quantities). The compiled executable is kept on the step — later
        calls through it also skip jit dispatch overhead. A failed build
        raises to the caller (the step stays unbuilt).

        Serialized per step under ``_aot_lock`` (double-checked): when the
        serving dispatcher and a user thread race the first call of one
        step, exactly one of them builds and the other waits for the
        finished executable instead of burning a duplicate XLA compile."""
        if step._aot is None:
            with step._aot_lock:
                return self._ensure_executable_locked(step, args)
        return step._aot or step.fn

    def _ensure_executable_locked(self, step: _CompiledStep, args):
        if step._aot is None:
            ev, step._compile_event = step._compile_event, None
            t_trace = t_compile = None

            # warm-start probe (FLAGS_aot_cache_dir): a serialized
            # executable for this exact (program content, arg signature,
            # compiler config, backend/version) identity loads instead of
            # compiling — the fleet tier's cold-replica path. Loads never
            # raise; a miss falls through to the normal build, which then
            # publishes its executable for the next process.
            from . import aot_cache as _aot_cache

            cache_dir = _aot_cache.cache_dir_flag()
            cache_key = None
            if cache_dir and step._aot_cache_parts is not None:
                devices = [self.place.jax_device()]
                cache_key = _aot_cache.executable_key(
                    step._aot_cache_parts, args, devices)
                t0 = time.perf_counter()
                loaded = _aot_cache.load_executable(cache_dir, cache_key,
                                                    devices)
                if loaded is not None:
                    step._aot = loaded
                    # the monitor's compile record stays paired: the
                    # "xla compile" stage is the deserialize+load time
                    _monitor.complete_compile(ev, 0.0,
                                              time.perf_counter() - t0)
                    return step._aot

            def _build():
                # transient-site: compiles hit flaky infra (preempted
                # backend, cache-server hiccups) — retried with backoff.
                # Watchdog-armed: a hung compile is dumped + raised, not
                # waited on forever
                _faults.fault_point("compile")
                with _dist.watchdog_section("compile",
                                            program=step.program):
                    t0 = time.perf_counter()
                    with RecordEvent("executor::trace_lower"):
                        lowered = step.fn.lower(*args)
                    t1 = time.perf_counter()
                    with RecordEvent("executor::xla_compile"):
                        compiled = lowered.compile()
                    return compiled, t1 - t0, time.perf_counter() - t1

            try:
                with _trace.span(
                        "executor.compile",
                        program=int(getattr(step.program, "_serial", -1))):
                    # a build that fails RAISES, once, with its own text:
                    # trace/shape errors, a diagnosed hang, an exhausted
                    # retry budget and the compiler's own refusals (out of
                    # HBM, a Mosaic kernel it rejects — permanent for the
                    # retry classifier) all reach the caller here. Nothing
                    # falls back to a second, equally doomed compile
                    # through jit.
                    step._aot, t_trace, t_compile = \
                        call_with_retry("compile", _build)
                if cache_key is not None:
                    # publish for the next cold process (atomic; failures
                    # warn once and never break the step)
                    _aot_cache.save_executable(cache_dir, cache_key,
                                               step._aot, devices)
            finally:
                # always paired with the popped record — even a
                # KeyboardInterrupt mid-compile must not leave the
                # on_compile hooks waiting forever
                _monitor.complete_compile(ev, t_trace, t_compile)
        return step._aot or step.fn

    # -- in flight and starved -------------------------------------------
    # (down here, not beside ``run``: the lines from ``make_step_fn`` to
    # ``_ensure_executable_locked`` are on every kernel's call stack, whose
    # source locations are part of the persistent compile cache's key)

    # the ``StepRecord`` of the last launch, and the instant up to which
    # this executor's life has been told apart into in flight and starved
    # (a launch with nothing in flight moves it on and observes the gap, a
    # fetch return moves it on and observes the time in flight; None: no
    # stretch is running, the next launch starts one and observes no gap)
    _last_dispatch: Optional[_monitor.StepRecord] = None
    _tiled_to: Optional[float] = None

    def forget_last_dispatch(self) -> None:
        """The next dispatch observes no ``executor_starved_seconds``: what
        lies between it and the one before is no wait of the device's for
        the host (a warm-up's end, generation state planted anew, a
        ``CompiledProgram`` step)."""
        self._last_dispatch = self._tiled_to = None

    def _launched(self, ph, step: _CompiledStep, mrec, cache_hit):
        """Right after the ``executor.step`` phase ``ph`` is entered: the
        launch's clock reading goes on the ``StepRecord``, and where
        nothing of this executor's was in flight, the fetch return that
        started the gap (``executor_starved_seconds``). A launch behind a
        dispatch whose fetch is still to be taken (``FETCH_LATER``)
        observes no gap: the device has that one's work. Only while
        ``FLAGS_trace`` is on, what ties the launch to a profile: the span
        says which dispatch it is (the ``StepRecord``'s ``step_index``) and
        which module it launched (the executable's name as a device trace
        prints it on ``XLA Modules``), and the ``TraceAnnotation``
        returned, of the span's name, carries the same ``dispatch`` into
        the profile on the profiler's clock (``trace.join_dispatches``
        reads both)."""
        before, self._last_dispatch = self._last_dispatch, mrec
        ident = {}
        if mrec is not None:
            mrec.launch_t = ph.t0
            if before is not None and before.ready_t is not None:
                mrec.prev_ready_t = self._tiled_to
                self._tiled_to = ph.t0
            elif before is None or getattr(before, "_deferred", None) is None:
                # none before it, or one that never fetched: a new stretch
                self._tiled_to = ph.t0
            ident["dispatch"] = mrec.step_index
        if not ph.traced:
            return _NOT_TRACED
        ph.set_attributes(cache_hit=cache_hit,
                          module="jit_" + step.fn.__name__, **ident)
        return jax.profiler.TraceAnnotation("executor.step", **ident)

    def _landed(self, mrec) -> None:
        """A fetch returned: what of this executor's life lies before it
        and is not told yet was in flight (``StepRecord.head_t`` to
        ``ready_t``: the dispatch's whole wall where it ran alone, and
        where dispatches overlap the part no earlier fetch return covers,
        so the observations add up to the union of the walls)."""
        mrec.head_t = mrec.launch_t if self._tiled_to is None \
            else self._tiled_to
        self._tiled_to = mrec.ready_t

    # -- a fetch that is taken later --------------------------------------
    def _fetched(self, fetches, mrec, how):
        """The tail of ``run`` / ``run_chained`` where the caller wants
        host values: now (the ``executor.fetch`` phase), or under
        ``return_numpy=FETCH_LATER`` when it takes them."""
        if how != FETCH_LATER:
            return self._fetch_to_host(fetches, mrec)
        return DeferredFetch(self, fetches, mrec, _trace.current_span())

    def _step_end(self, mrec) -> None:
        """``monitor.step_end`` for a dispatch that is over; one whose
        fetch is still to be taken keeps its record open with the call's
        wall on it, and ``DeferredFetch`` closes it."""
        if mrec is not None and getattr(mrec, "_deferred", None) is not None:
            mrec.duration_s = time.perf_counter() - mrec._t0
        else:
            _monitor.step_end(mrec)


FETCH_LATER = "later"


class DeferredFetch:
    """What ``Executor.run`` / ``run_chained`` return under
    ``return_numpy=FETCH_LATER``: the dispatch is launched, its state is in
    the scope (as arrays the device is still computing), and its fetches
    stay on the device until the caller takes them. The caller may launch
    more in the meantime: the next program reads the state this one leaves,
    and the device runs them in order without waiting for the host.

    ``take()`` is the dispatch's ``executor.fetch`` phase, under the span of
    the call that launched it: it blocks until the device is done, copies
    the fetches back and closes the dispatch's ``StepRecord``
    (``fetch_wait_s``, ``ready_t``; ``duration_s`` is the call's wall plus
    this one's, the host's time between the two is not the dispatch's). An
    error of the device's surfaces here. ``drop()`` closes the record
    without fetching (the results are not wanted: a failure before it)."""

    def __init__(self, exe: "Executor", fetches, mrec, span):
        self._exe, self._fetches = exe, list(fetches)
        self._mrec, self._span = mrec, span
        if mrec is not None:
            mrec._deferred = self       # open: Executor._launched reads it

    def _close(self):
        fetches, self._fetches = self._fetches, None
        if fetches is None:
            raise RuntimeError("DeferredFetch: taken or dropped before")
        if self._mrec is not None:
            self._mrec._deferred = None
        return fetches

    def take(self) -> List[np.ndarray]:
        fetches, mrec = self._close(), self._mrec
        try:
            return self._exe._fetch_to_host(fetches, mrec, parent=self._span)
        finally:
            if mrec is not None:
                mrec.duration_s += mrec.fetch_wait_s
                _monitor.step_end(mrec)

    def drop(self) -> None:
        self._close()
        _monitor.step_end(self._mrec)


_NOT_TRACED = contextlib.nullcontext()
