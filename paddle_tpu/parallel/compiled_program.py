"""CompiledProgram: data-parallel (and later model-parallel) compilation.

Reference: python/paddle/fluid/compiler.py:65 CompiledProgram /
:143 with_data_parallel, which constructs a C++ ParallelExecutor running an
SSA graph with per-gradient NCCL AllReduceOpHandles
(framework/details/all_reduce_op_handle.cc).

TPU-native design: no graph surgery at all. The SAME lowering used by the
single-device Executor is jitted with sharding annotations over a
jax.sharding.Mesh — feeds are sharded along the batch ('dp') axis, parameters
and optimizer state are replicated (or sharded, = the reference's
BuildStrategy.reduce_strategy kReduce / ZeRO), and XLA GSPMD inserts the
gradient all-reduce over ICI automatically. The per-grad AllReduce builder
(multi_devices_graph_pass.cc:454 CreateAllReduceOp) has no equivalent because
the compiler owns collective placement.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import monitor as _monitor
from ..framework import Program, Variable
from ..executor import _feed_host_bytes, _live_bytes, _shape_dtype_sig
from ..lowering import LowerCtx, lower_block
from ..profiler import RecordEvent
from ..resilience import distributed as _dist
from ..resilience import elastic as _elastic
from ..resilience import faults as _faults
from ..resilience import nonfinite as _nonfinite
from ..resilience.retry import call_with_retry

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy", "data_parallel_mesh"]


class ReduceStrategy:
    AllReduce = 0  # replicate params, all-reduce grads (default)
    Reduce = 1     # shard optimizer states across devices (ZeRO-1 style)


class BuildStrategy:
    """Knobs carried over from details/build_strategy.h:37 that still mean
    something under XLA; the fusion/memory toggles are compiler-owned now."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = 0  # CoeffNumDevice
        self.num_trainers = 1
        self.trainer_id = 0
        self.sync_batch_norm = False


class ExecutionStrategy:
    """Reference execution_strategy.h:22; scheduling knobs are no-ops under
    XLA's static schedule but kept for API parity."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = True


def data_parallel_mesh(places=None) -> Mesh:
    if isinstance(places, Mesh):
        return places   # caller brought a full mesh (dp/tp/pp axes)
    devices = np.array(jax.devices() if places is None else places)
    return Mesh(devices, axis_names=("dp",))


def _ensure_global(v, sharding):
    """Promote a process-local array (e.g. fresh from the per-process startup
    run) to a global array on the multi-process mesh. Startup programs run
    identically on every process (same seeds), so replicated promotion is the
    reference's BCastParamsToDevices without the broadcast."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        if v.sharding.is_equivalent_to(sharding, v.ndim):
            return v  # already global with the right layout
        raise RuntimeError(
            f"state array has cross-process sharding {v.sharding} but the "
            f"step expects {sharding}; cannot reshard across processes")
    host = np.asarray(v)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


def _fetch_numpy(v) -> np.ndarray:
    """np.asarray for fetches that works when the array spans processes:
    fetch out_shardings are replicated, so shard 0 holds the full value."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        return np.asarray(v.addressable_data(0))
    return np.asarray(v)


class CompiledProgram:
    def __init__(self, program: Program, build_strategy: Optional[BuildStrategy] = None):
        self._program = program
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = ExecutionStrategy()
        self._loss_name: Optional[str] = None
        self._mesh: Optional[Mesh] = None
        self._is_data_parallel = False
        self._cache: Dict[tuple, Any] = {}
        # same contract as Executor._lock: the step cache must survive
        # concurrent dispatch threads (serving) without forking duplicate
        # compiles for one key
        self._cache_lock = _monitor.make_rlock("CompiledProgram._cache_lock")

    @property
    def program(self) -> Program:
        return self._program

    def with_data_parallel(self, loss_name: Optional[str] = None,
                           build_strategy: Optional[BuildStrategy] = None,
                           exec_strategy: Optional[ExecutionStrategy] = None,
                           places=None) -> "CompiledProgram":
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        if exec_strategy is not None:
            self._exec_strategy = exec_strategy
        self._mesh = data_parallel_mesh(places)
        return self

    def rescale(self, places) -> "CompiledProgram":
        """Elastic recovery (resilience.elastic): tear down every compiled
        step — the executables were built with shardings over the OLD
        mesh and must never dispatch onto the new one — and re-form the
        mesh on ``places`` (a device list or a ready Mesh). State in the
        scope re-shards lazily: the next dispatch's ``in_shardings``
        place it onto the new mesh (the same mechanism the PR 6 elastic
        restore relies on). The replica-divergence sweep counter resets
        with the mesh so the first post-rescale interval is a full one."""
        with self._cache_lock:
            self._cache.clear()
            self._mesh = data_parallel_mesh(places)
            self._replica_steps = 0
        return self

    # -- execution (called by Executor.run) ------------------------------
    def _run(self, exe, feed, fetch_list, scope, return_numpy):
        from ..executor import global_scope

        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in (fetch_list or [])]
        # FLAGS_auto_recompute: the data-parallel path shares the executor's
        # remat cache; the transformed program's fresh _serial keys this
        # CompiledProgram's own step cache apart from the plain variant
        program = exe._maybe_auto_remat(self._program, feed, fetch_names)
        exe.forget_last_dispatch()    # no run / run_chained dispatch, this
        mrec = _monitor.step_begin("parallel", program)
        from .. import trace as _trace

        with _trace.span("executor.parallel_step",
                         program=int(getattr(program, "_serial", -1)),
                         mesh=str(dict(self._mesh.shape))
                         if self._mesh is not None else ""):
            try:
                # classification wraps the WHOLE dispatch, not just the jit
                # call: with async dispatch (watchdog unarmed) a real device
                # loss only surfaces when a result is read — at
                # unpack_step_result or the return_numpy materialization —
                # and must still come out typed (resilience.elastic)
                with _elastic.device_loss_classification("parallel_step"):
                    return self._run_body(exe, program, feed, fetch_names,
                                          scope, return_numpy, mrec)
            finally:
                # paired with step_begin even when the step raises
                _monitor.step_end(mrec)

    def _run_body(self, exe, program, feed, fetch_names, scope,
                  return_numpy, mrec):
        if mrec is not None:
            mrec.fetch_names = tuple(fetch_names)
        step = self._get_compiled(exe, program, feed, fetch_names, scope,
                                  mrec=mrec)
        if mrec is not None:
            from ..executor import _feed_batch_rows

            mrec.feed_bytes = sum(_feed_host_bytes(v)
                                  for v in feed.values())
            mrec.batch_rows = _feed_batch_rows(feed)
        multiproc = jax.process_count() > 1
        batch_shard = NamedSharding(
            self._mesh, P("dp") if "dp" in self._mesh.axis_names else P())
        repl = NamedSharding(self._mesh, P())
        state_shardings = getattr(step, "state_shardings", {})
        def _pack_feed(n):
            def _put():
                # device_put fault site + transient retry (host->device)
                _faults.fault_point("device_put")
                if multiproc:
                    # each trainer feeds its LOCAL batch shard; together
                    # they form the global batch (the reference's
                    # FeedAndSplitTensorIntoLocalScopes,
                    # parallel_executor.cc:75, inverted: feeds are split
                    # before the call, not inside it)
                    return jax.make_array_from_process_local_data(
                        batch_shard, np.asarray(feed[n]))
                return jnp.asarray(np.asarray(feed[n]))
            return call_with_retry("device_put", _put)

        feed_vals = [_pack_feed(n) for n in step.feed_names]

        def read(names):
            vals = []
            for n in names:
                v = scope.find_var(n)
                if v is None:
                    raise RuntimeError(f"Variable '{n}' not initialized in scope")
                if multiproc:
                    v = _ensure_global(v, state_shardings.get(n, repl))
                vals.append(v)
            return vals

        key = jax.random.key(exe._next_seed(program))
        donated_vals = read(step.donated_names)
        # step-site fault probe fires BEFORE donation, scope stays usable
        _faults.fault_point("step")
        rollback = None
        if step.nan_check_meta is not None and _nonfinite.rollback_active():
            if all(getattr(v, "is_fully_addressable", True)
                   for v in donated_vals):
                # host-side pre-step image: a device-side copy would lose
                # the mesh sharding; the restore re-shards on the next
                # step's read(). MUST be an owned copy — np.asarray of a
                # CPU-backend jax array can be a zero-copy VIEW of the
                # device buffer, and that buffer is donated below: XLA
                # would write the post-step (possibly non-finite) values
                # straight through the "pre-step" image
                rollback = [(n, np.array(v, copy=True))
                            for n, v in zip(step.donated_names,
                                            donated_vals)]
            # multi-process global arrays cannot be host-imaged here; the
            # policy degrades to raise for this dispatch
        if mrec is not None:
            mrec.donated_buffers = len(step.donated_names)
            mrec.kept_buffers = len(step.kept_names)
            mrec.donated_bytes = _live_bytes(donated_vals)
        # the parallel dispatch IS the collective section: a stuck ICI
        # collective here used to hang CI forever; under
        # FLAGS_step_timeout_s the watchdog dumps + raises instead
        # the classification at the _run boundary turns the jax/XLA
        # error zoo anywhere in this dispatch into typed DeviceLostError
        # (transient=False — retry never absorbs a dead chip) so
        # contrib.Trainer's elastic recovery can act on it
        with RecordEvent("executor::parallel_step"), \
                _dist.watchdog_section("parallel_step",
                                       program=program) as tok:
            # device_lost probe (resilience.elastic): fires BEFORE the
            # dispatch donates anything, like a preemption notice racing
            # the step; the classifier treats injected and real losses
            # identically
            _faults.fault_point("device_lost")
            _faults.fault_point("hang")
            result = step.fn(feed_vals, donated_vals,
                             read(step.ro_names), key)
            if tok is not None:
                # async dispatch: a wedged collective only blocks at the
                # first result read — keep the section armed through it
                jax.block_until_ready(result)
        from ..executor import unpack_step_result

        fetches, new_state = unpack_step_result(step, result, scope,
                                                to_host=_fetch_numpy,
                                                path="parallel", exe=exe,
                                                rollback=rollback)
        if new_state is not None:
            for n, v in zip(step.state_out_names, new_state):
                scope.set_var(n, v)
        self._maybe_check_replicas(step, scope)
        if return_numpy:
            outs = [_fetch_numpy(v) for v in fetches]
            if mrec is not None:
                mrec.fetch_bytes = _live_bytes(outs)
            return outs
        return list(fetches)

    def _maybe_check_replicas(self, step, scope):
        """FLAGS_replica_check_interval: every N-th parallel step, verify
        that state replicated over the dp axis still holds identical bytes
        on every replica (resilience.distributed — a jitted per-device
        checksum reduce, no host gather of tensors). Disagreement is
        handled by FLAGS_replica_divergence_policy."""
        from ..flags import flag

        interval = int(flag("replica_check_interval"))
        mesh = self._mesh
        if interval <= 0 or mesh is None \
                or mesh.shape.get("dp", 1) <= 1:
            return
        self._replica_steps = getattr(self, "_replica_steps", 0) + 1
        if self._replica_steps % interval:
            return
        values = {}
        for n in step.state_out_names:
            v = scope.find_var(n)
            if not isinstance(v, jax.Array):
                continue
            if getattr(v.sharding, "mesh", None) != mesh:
                continue
            values[n] = v
        if not values:
            return
        if _monitor.enabled():
            _monitor.counter(
                "resilience_divergence_checks_total",
                "cross-replica consistency sweeps run").inc()
        # axis=None: compare across EVERY axis a var is replicated over
        # (on a dp x tp mesh that covers both replica directions)
        diverged = _dist.replica_divergence_check(mesh, values)
        if diverged:
            _dist.handle_divergence(diverged, path="parallel", axis="dp")

    def _get_compiled(self, exe, program, feed, fetch_names, scope,
                      mrec=None):
        feed_sig = tuple(sorted(
            (n,) + _shape_dtype_sig(v) for n, v in feed.items()
        ))
        from ..flags import flag, xla_options

        xla_opts = tuple(sorted(xla_options().items()))
        key = (exe._program_fingerprint(program), feed_sig,
               tuple(fetch_names), flag("check_nan_inf"), xla_opts)
        with self._cache_lock:
            hit = key in self._cache
            _monitor.record_cache_lookup("parallel", hit)
            if mrec is not None:
                mrec.cache_hit = hit
            if hit:
                return self._cache[key]

        # compile-site fault probe + transient retry (the actual XLA
        # compile happens lazily at first dispatch on this path; the
        # probe models the build pipeline's transient failures). Only
        # the probe is retried: a real build failure must surface its
        # ORIGINAL diagnostic immediately, exactly like the
        # single-device path. OUTSIDE the cache lock: retry backoff can
        # sleep for seconds, and concurrent cache HITS must not queue
        # behind it
        call_with_retry("compile", _faults.fault_point, "compile")
        with self._cache_lock:
            step = self._cache.get(key)
            if step is not None:
                # a racing thread built it while we were probing
                return step
            with RecordEvent("executor::build_step"), \
                    _dist.watchdog_section("compile", program=program):
                step = self._compile(program, set(feed.keys()), fetch_names,
                                     scope)
            step.program = program
            # the data-parallel path keeps jit dispatch (shardings make the
            # AOT fast path fiddly across process topologies), so the
            # compile event completes here without stage timings
            _monitor.complete_compile(_monitor.observe_compile(
                "parallel", program,
                components={
                    "program": exe._program_fingerprint(program)[1:],
                    "feed_signature": feed_sig,
                    "fetch_list": tuple(fetch_names),
                    "flags": (("check_nan_inf", flag("check_nan_inf")),),
                    "xla_options": xla_opts,
                },
                donated_names=step.donated_names), None, None)
            self._cache[key] = step
        # outside the cache lock: a pure-metadata walk, but no reason to
        # queue concurrent cache hits behind it
        self._observe_static_sharding(program, fetch_names, feed)
        return step

    def _observe_static_sharding(self, program, fetch_names, feed) -> None:
        """Predicted per-chip collective volume + comms-vs-compute gauges
        for the layout this compile just fixed (analysis.sharding_check
        over the same zero1_spec_for rule the executable was built with).
        Advisory: never raises into a step."""
        if not _monitor.enabled() or self._mesh is None:
            return
        try:
            from ..analysis.cost_model import estimate_comms, estimate_cost
            from ..analysis.sharding_check import propagate_sharding
            from ..executor import _feed_batch_rows
            from .sharding import extract_param_specs

            mesh_shape = {str(k): int(v)
                          for k, v in dict(self._mesh.shape).items()}
            zero = (self._build_strategy.reduce_strategy
                    == ReduceStrategy.Reduce)
            specs, feed_spec = extract_param_specs(program, mesh_shape,
                                                   zero=zero)
            batch = _feed_batch_rows(feed) or 1
            analysis = propagate_sharding(
                program, mesh_shape, param_specs=specs,
                feed_spec=feed_spec, feed_names=list(feed.keys()),
                fetch_names=fetch_names, batch_size=batch)
            _monitor.observe_comms_cost(
                program, estimate_comms(analysis),
                estimate_cost(program, batch_size=batch),
                device_kind=self._mesh.devices.flat[0].device_kind)
        except Exception:
            pass

    def _compile(self, program: Program, feed_names: set, fetch_names, scope):
        """Same env-threading as Executor._compile, but jitted with shardings
        over the mesh: feeds split on 'dp', state replicated."""
        from ..executor import _CompiledStep, analyze_block_io, pick_step_fn

        from ..flags import flag, xla_options

        block = program.global_block
        io = analyze_block_io(block, feed_names, fetch_names)
        mesh = self._mesh
        nan_meta = [] if flag("check_nan_inf") else None
        step_fn = pick_step_fn(program)(block, io, fetch_names, mesh=mesh,
                                        nan_check_meta=nan_meta)

        batch_spec = NamedSharding(
            mesh, P("dp") if "dp" in mesh.axis_names else P())
        repl_spec = NamedSharding(mesh, P())

        # ZeRO-1 (BuildStrategy.ReduceStrategy.Reduce, ref build_strategy.h:58
        # kReduce / multi_devices_graph_pass.h:157 ReduceSSAGraphBuilder):
        # optimizer-state vars are sharded over the dp axis on dim 0. GSPMD
        # then partitions the update elementwise — grads reach each shard as
        # a reduce-scatter and fresh params are all-gathered, which is exactly
        # the reduce+broadcast the reference builder inserts by hand.
        zero1 = self._build_strategy.reduce_strategy == ReduceStrategy.Reduce
        dp = mesh.shape.get("dp", 1)

        def state_sharding(name):
            # the metadata rule is shared with the static sharding_check
            # pass (parallel/sharding.py), so the layout the analysis
            # reasons about IS the one this executable runs
            from .sharding import zero1_spec_for

            v = block.var(name) if block.has_var(name) else None
            spec = zero1_spec_for(v, dp, zero1)
            if not spec:
                return repl_spec
            return NamedSharding(mesh, P(*spec))

        state_shardings = {n: state_sharding(n)
                           for n in set(io["state_in"]) | set(io["state_out"])}
        in_shardings = (
            [batch_spec] * len(io["feed_order"]),
            [state_shardings[n] for n in io["donated"]],
            [state_shardings[n] for n in io["ro"]],
            None,
        )
        # fetches pinned replicated so multi-process fetch reads one
        # addressable shard; state keeps its (possibly dp-sharded) layout so
        # it stays valid as a next-step input
        out_shardings = (
            [repl_spec] * len(fetch_names),
            [state_shardings[n] for n in io["state_out"]],
        )
        if nan_meta is not None:
            out_shardings = out_shardings + (repl_spec,)
        jitted = jax.jit(step_fn, donate_argnums=(1,),
                         in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         compiler_options=xla_options() or None)
        step = _CompiledStep(jitted, io["feed_order"], io["donated"],
                             io["ro"], io["state_out"], tuple(fetch_names))
        step.kept_names = [n for n in io["ro"] if n in io["state_out"]]
        step.state_shardings = state_shardings
        step.nan_check_meta = nan_meta
        return step
