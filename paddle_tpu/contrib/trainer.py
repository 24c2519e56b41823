"""High-level Trainer / Inferencer (reference
python/paddle/contrib/trainer.py:169 Trainer with epoch/step events,
:100 CheckpointConfig, :663 incremental save_checkpoint;
python/paddle/contrib/inferencer.py:31 Inferencer).

The event loop, checkpointing cadence and callbacks mirror the reference;
execution rides the TPU executor (and CompiledProgram when num_devices>1).

On top of the reference shape, the Trainer is the wiring point for the
resilience stack (docs/RESILIENCE.md):

* **recovery walk** (PR 4): ``_load_latest`` resumes from the newest
  checkpoint that verifies, skipping torn serials;
* **divergence restore** (PR 6): ``FLAGS_replica_divergence_policy=
  restore`` rolls back through the same walk mid-run;
* **elastic preemption tolerance** (``resilience.elastic``,
  ``FLAGS_elastic``): a typed ``DeviceLostError`` from the parallel step
  — or a watchdog-diagnosed hang there, the same dead chip seen earlier
  — tears down the failed ``CompiledProgram``, re-forms the mesh on the
  surviving devices, restores from the last VERIFIED serial and
  fast-forwards the data cursor, so training continues at reduced width
  with the SAME global batch (the per-replica slice widens by the
  gradient-accumulation factor). ``BeginEpochEvent`` re-fires for the
  epoch a recovery re-enters — handlers must tolerate replays of
  batches that were never committed;
* **graceful shutdown** (``resilience.graceful``): ``train()`` installs
  SIGTERM handlers for its duration; on a preemption notice the
  in-flight step finishes, a final verified checkpoint (data cursor
  included) is written, and ``train()`` returns with ``.interrupted``
  set so the process can exit 0.

Checkpoints carry a ``data_cursor`` (epoch, batch offset, reader state)
in their meta, and ``train()`` fast-forwards the reader past committed
batches on resume — a resumed run consumes exactly the not-yet-committed
batch sequence, no re-trained and no skipped data (deterministic readers
assumed; seed shuffles via ``reader.shuffle(..., seed=N)``).
"""
from __future__ import annotations

import logging
import os
import time
from typing import Callable, Optional

import numpy as np

from .. import io as io_mod
from .. import monitor as _monitor
from .. import resilience as _resilience
from .. import trace as _trace
from ..executor import Executor, Scope, scope_guard
from ..framework import Program, program_guard
from ..parallel.compiled_program import CompiledProgram
from ..resilience import elastic as _elastic
from ..resilience import graceful as _graceful

__all__ = ["Trainer", "Inferencer", "CheckpointConfig",
           "BeginEpochEvent", "EndEpochEvent", "BeginStepEvent",
           "EndStepEvent"]

logger = logging.getLogger("paddle_tpu.resilience")


class _EpochRewind(Exception):
    """Internal control flow: a mid-step restore (divergence policy)
    rolled the state lineage back to a checkpoint that carries a data
    cursor — unwind to the epoch loop and re-enter from that cursor so
    the data stream rewinds WITH the state (each batch affects the
    committed lineage exactly once, same contract as the elastic path)."""


class BeginEpochEvent:
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent:
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent:
    def __init__(self, epoch_id, step_id):
        self.epoch, self.step = epoch_id, step_id
        self.fetch_metrics = True


class EndStepEvent:
    def __init__(self, epoch_id, step_id, metrics):
        self.epoch, self.step, self.metrics = epoch_id, step_id, metrics


class CheckpointConfig:
    """reference contrib/trainer.py:100; ``sharded=True`` selects the
    format_version-2 sharded checkpoint (resilience.distributed): one
    fsynced blob per mesh shard, elastic restore across device counts —
    the format ZeRO-sharded optimizer state needs so a checkpoint never
    forces a full gather."""

    def __init__(self, checkpoint_dir: str, max_num_checkpoints: int = 3,
                 epoch_interval: int = 1, step_interval: int = 10,
                 sharded: bool = False):
        self.checkpoint_dir = checkpoint_dir
        self.max_num_checkpoints = max_num_checkpoints
        self.epoch_interval = max(1, epoch_interval)
        self.step_interval = max(1, step_interval)
        self.sharded = bool(sharded)


class Trainer:
    """reference contrib/trainer.py:169: train_func returns the loss var
    (after building the whole model under this trainer's programs).

    ``build_strategy`` (parallel runs) reaches
    ``CompiledProgram.with_data_parallel`` — e.g.
    ``ReduceStrategy.Reduce`` for ZeRO-sharded optimizer state.
    ``elastic_devices_fn`` (optional zero-arg callable) overrides how the
    elastic recovery path enumerates healthy devices — the production
    default is ``jax.devices()`` (a lost chip disappears from the
    enumeration after the runtime restarts); tests and single-host
    simulations inject survivor sets through it."""

    def __init__(self, train_func: Callable, optimizer_func: Callable,
                 place=None, checkpoint_config: Optional[CheckpointConfig]
                 = None, parallel: bool = False, build_strategy=None):
        self.main_program = Program()
        self.startup_program = Program()
        self._ckpt = checkpoint_config
        with program_guard(self.main_program, self.startup_program):
            loss = train_func()
            if isinstance(loss, (list, tuple)):
                loss = loss[0]
            self.loss = loss
            optimizer_func().minimize(loss)
        self.exe = Executor(place)
        self.place = self.exe.place
        self.scope = Scope()
        self._parallel = parallel
        self._build_strategy = build_strategy
        self._step = 0
        self._train_mesh = None   # set by train() on the parallel path
        # set by a mid-step divergence restore: the step that just ran was
        # rolled back, so the loop must adopt the checkpoint's counter
        # instead of incrementing past state that no longer exists
        self._restored_step = None
        # elastic recovery state (resilience.elastic, FLAGS_elastic)
        self.elastic_devices_fn: Optional[Callable] = None
        self.elastic_events: list = []   # one dict per rescale, in order
        self.interrupted = False         # graceful shutdown unwound train()
        self._elastic_rescales = 0
        self._healthy_steps = 0
        self._full_dp = None             # dp width train() started with
        self._full_ndev = None
        self._last_global_batch = None   # rows of the most recent batch
        # data cursor: where the NEXT batch comes from (epoch, batch,
        # reader state); checkpointed in meta so resume fast-forwards
        self._cursor = _elastic.DataCursor()
        self._resume_cursor: Optional[_elastic.DataCursor] = None
        with scope_guard(self.scope):
            self.exe.run(self.startup_program)
        if self._ckpt:
            self._load_latest()

    # -- checkpoints -----------------------------------------------------
    def _ckpt_path(self, serial: int) -> str:
        return os.path.join(self._ckpt.checkpoint_dir, f"checkpoint_{serial}")

    def _serials(self):
        """Serials of ``checkpoint_<int>`` DIRECTORIES only, ascending.
        Stray files, torn temp dirs and non-numeric entries in the
        checkpoint dir are ignored (resilience.iter_serials)."""
        return [s for s, _ in
                _resilience.iter_serials(self._ckpt.checkpoint_dir)]

    def _ckpt_mesh(self):
        """Mesh handed to sharded saves: the training mesh when parallel,
        else every local device as a dp axis (a 1-device host writes a
        valid single-shard v2 checkpoint)."""
        if not (self._ckpt and self._ckpt.sharded):
            return None
        if self._train_mesh is not None:
            return self._train_mesh
        import jax

        return {"dp": max(1, jax.device_count())}

    def _save_checkpoint(self):
        serials = self._serials()
        serial = (serials[-1] + 1) if serials else 0
        with scope_guard(self.scope), \
                _trace.span("trainer.checkpoint", serial=serial,
                            step=self._step):
            io_mod.save_checkpoint(self.exe, self._ckpt_path(serial),
                                   self.main_program,
                                   meta={"step": self._step,
                                         "data_cursor":
                                             self._cursor.to_dict()},
                                   mesh=self._ckpt_mesh())
        if _monitor.enabled():
            _monitor.counter("trainer_checkpoints_total",
                            "checkpoints written by contrib.Trainer").inc()
        # rotate (reference keeps max_num_checkpoints); never the serial
        # just written, even with max_num_checkpoints=1 or a racing writer
        # that renumbered the listing under us. <=0 keeps full history
        # (the pre-resilience [:-0] behavior, kept on purpose)
        keep = int(self._ckpt.max_num_checkpoints)
        if keep <= 0:
            return
        for old in self._serials()[:-keep]:
            if old == serial:
                continue
            import shutil

            shutil.rmtree(self._ckpt_path(old), ignore_errors=True)

    def _load_latest(self):
        """Resume from the newest checkpoint that passes verification,
        walking serials newest -> oldest past torn/corrupt ones (each skip
        counts on ``trainer_ckpt_fallback_total`` and logs its PT6xx
        diagnostic). An empty or garbage-only checkpoint dir starts fresh
        at step 0 instead of crashing."""
        with scope_guard(self.scope):
            meta, serial, skipped = _resilience.load_latest_checkpoint(
                self.exe, self._ckpt.checkpoint_dir,
                main_program=self.main_program, scope=self.scope)
        if meta is None:
            self._step = 0
            return None
        self._step = int(meta.get("step", 0))
        self._resume_cursor = _elastic.DataCursor.from_dict(
            meta.get("data_cursor"))
        return serial

    def _recover_from_checkpoint(self) -> bool:
        """Divergence-restore hook (FLAGS_replica_divergence_policy=
        restore): reload the newest VERIFIED checkpoint through the PR 4
        recovery walk WITHOUT zeroing the step counter on failure —
        a divergence with nothing restorable must escalate, not silently
        restart training at step 0."""
        with scope_guard(self.scope):
            # allow_legacy=False: rolling diverged replicas back onto an
            # UNVERIFIED pre-manifest checkpoint would trade one kind of
            # corrupt state for another — escalate to raise instead
            meta, serial, _skipped = _resilience.load_latest_checkpoint(
                self.exe, self._ckpt.checkpoint_dir,
                main_program=self.main_program, scope=self.scope,
                allow_legacy=False)
        if meta is None:
            return False
        self._step = int(meta.get("step", 0))
        self._restored_step = self._step
        # checkpoints with a data cursor rewind the DATA with the state
        # (the step loop unwinds via _EpochRewind); legacy checkpoints
        # without one keep the old continue-forward semantics
        self._resume_cursor = _elastic.DataCursor.from_dict(
            meta.get("data_cursor"))
        return True

    # -- elastic recovery (resilience.elastic) ---------------------------
    def _probe_devices(self, err=None) -> list:
        """The healthy device set: the error's own attribution when the
        runtime provided one, else ``elastic_devices_fn`` (tests /
        simulations), else ``jax.devices()``."""
        if err is not None and getattr(err, "survivors", None):
            return list(err.survivors)
        if self.elastic_devices_fn is not None:
            return list(self.elastic_devices_fn())
        import jax

        return list(jax.devices())

    def _elastic_enabled(self) -> bool:
        from ..flags import flag

        return bool(flag("elastic")) and self._parallel \
            and self._ckpt is not None

    def _unshard_stale_state(self, mesh) -> None:
        """Pull scope values still committed to a mesh OTHER than
        ``mesh`` back to host: jit refuses to reshard a committed array
        whose mesh differs from its declared in_sharding, so after a
        rescale everything the restore did not rewrite must become an
        uncommitted host array the next dispatch places itself. A value
        that cannot be read (its device really died) is left for the
        checkpoint restore / next-dispatch diagnostics."""
        import jax

        for name in list(self.scope.vars):
            v = self.scope.find_var(name)
            if not isinstance(v, jax.Array):
                continue
            vmesh = getattr(getattr(v, "sharding", None), "mesh", None)
            if vmesh is None or vmesh == mesh:
                continue
            try:
                self.scope.set_var(name, np.array(v))
            except Exception:
                logger.warning(
                    "elastic: could not host-copy '%s' off the old mesh "
                    "(device really gone?) — the checkpoint restore "
                    "must cover it", name)

    def _record_rescale(self, old_axes, new_axes, direction, serial,
                        cause, duration_s) -> dict:
        """One audit event + the monitor emission every rescale makes
        (recovery is never silent): ``elastic_rescales_total`` with the
        old/new topology and the grad-accum gauge preserving the global
        batch."""
        new_dp = int(new_axes.get("dp", 1))
        accum = _elastic.grad_accum_steps(
            self._full_dp or int(old_axes.get("dp", 1)), new_dp)
        event = {"old": _elastic.format_axes(old_axes),
                 "new": _elastic.format_axes(new_axes),
                 "direction": direction, "serial": serial,
                 "step": self._step, "cause": cause,
                 "grad_accum_steps": accum, "duration_s": duration_s}
        self.elastic_events.append(event)
        if _monitor.enabled():
            _monitor.counter(
                "elastic_rescales_total",
                "elastic mesh rescales by old/new topology").labels(
                old=event["old"], new=event["new"],
                direction=direction).inc()
            _monitor.gauge(
                "elastic_grad_accum_steps",
                "per-replica gradient-accumulation factor preserving "
                "the global batch at reduced width").set(accum)
        return event

    def _elastic_recover(self, err, prog) -> CompiledProgram:
        """Device-loss recovery: tear down the failed CompiledProgram,
        re-form the mesh on the surviving devices, restore from the last
        VERIFIED serial and queue the data-cursor fast-forward. Raises
        (typed) when elastic is off, the topology cannot be satisfied
        (PT610/PT611), the rescale budget is spent (PT612) or nothing
        restorable exists (PT614) — recovery is never silent either way.
        The whole episode is one trace (``trainer.elastic_recover``) so
        the flight recorder shows rescale + restore as spans, not logs."""
        recover_span = _trace.root_span(
            "trainer.elastic_recover", cause=type(err).__name__,
            step=self._step)
        recover_span.__enter__()
        try:
            out = self._elastic_recover_body(err, prog)
        except BaseException as e:
            recover_span.set_attribute("outcome", "failed")
            recover_span.__exit__(type(e), e, None)
            raise
        recover_span.set_attribute("outcome", "recovered")
        recover_span.__exit__(None, None, None)
        return out

    def _elastic_recover_body(self, err, prog) -> CompiledProgram:
        from ..flags import flag
        from ..parallel.sharding import make_mesh
        from ..resilience.distributed import WatchdogTimeout, mesh_axes

        if isinstance(err, WatchdogTimeout):
            # only a parallel-step hang escalates here: on a dead device
            # the wedged collective is usually diagnosed by the watchdog
            # before the runtime reports the loss. Other sections
            # (compile, single-device step) keep their typed failure.
            if not (self._elastic_enabled()
                    and err.section == "parallel_step"):
                raise err
            _elastic.record_device_lost("watchdog")
        elif not self._elastic_enabled():
            raise err
        if not isinstance(prog, CompiledProgram) or prog._mesh is None:
            raise err
        t0 = time.perf_counter()
        self._elastic_rescales += 1
        budget = int(flag("elastic_max_rescales"))
        if budget and self._elastic_rescales > budget:
            raise _elastic.ElasticRescaleError(
                "PT612", f"{self._elastic_rescales - 1} rescale(s) "
                         f"already performed this train() call "
                         f"(FLAGS_elastic_max_rescales={budget})") from err
        old_axes = mesh_axes(prog._mesh)
        old_dp = int(old_axes.get("dp", 1))
        devices = self._probe_devices(err)
        # the non-dp axes are load-bearing and the global batch must
        # divide the surviving dp width; PT610/PT611/PT613 refuse loudly
        # when the survivors cannot satisfy them
        new_axes = _elastic.plan_rescale(
            old_axes, len(devices), global_batch=self._last_global_batch)
        survivors = _elastic.survivor_devices(devices, new_axes)
        prog.rescale(make_mesh(new_axes, survivors))
        self._train_mesh = prog._mesh
        # restore from the last VERIFIED serial (never legacy: rescaling
        # onto unverified bytes would launder corruption into the new
        # topology), then fast-forward the data cursor on re-entry
        with scope_guard(self.scope):
            meta, serial, _skipped = _resilience.load_latest_checkpoint(
                self.exe, self._ckpt.checkpoint_dir,
                main_program=self.main_program, scope=self.scope,
                allow_legacy=False)
        if meta is None:
            raise _elastic.ElasticRescaleError(
                "PT614", f"device loss at '{getattr(err, 'site', '?')}' "
                         f"but no serial in "
                         f"'{self._ckpt.checkpoint_dir}' verifies") \
                from err
        # whatever the restore did not rewrite must leave the old mesh
        self._unshard_stale_state(prog._mesh)
        self._step = int(meta.get("step", 0))
        cur = _elastic.DataCursor.from_dict(meta.get("data_cursor"))
        if cur is None:
            # legacy checkpoint without a cursor (pre-elastic writer):
            # keep the historic continue-forward data semantics — the
            # same contract as the divergence path — instead of
            # silently re-consuming every committed batch from zero
            logger.warning(
                "elastic: restored checkpoint_%s carries no data_cursor "
                "(pre-elastic writer) — the data stream continues "
                "forward from the pre-loss position; save once to "
                "upgrade the checkpoint format", serial)
            cur = _elastic.DataCursor(epoch=self._cursor.epoch,
                                      batch=self._cursor.batch)
        self._resume_cursor = cur
        self._healthy_steps = 0
        new_dp = int(new_axes.get("dp", 1))
        # 'same' = restart in place: the survivor probe reported no
        # shrink (a reset chip recovered, or — the production default
        # jax.devices() — the runtime cannot re-enumerate in-process).
        # Legitimate once for a recovered reset; a dead chip loops here
        # and the PT612 budget is the bound that turns it into a typed
        # outage instead of an infinite teardown/restore cycle.
        direction = ("down" if new_dp < old_dp
                     else "up" if new_dp > old_dp else "same")
        event = self._record_rescale(
            old_axes, new_axes, direction, serial, type(err).__name__,
            time.perf_counter() - t0)
        if direction == "same":
            logger.warning(
                "elastic: survivor probe reported no capacity change "
                "(%s) — restarting in place; repeated losses on this "
                "topology exhaust FLAGS_elastic_max_rescales (PT612). "
                "Provide elastic_devices_fn (or error survivors) for a "
                "real downscale.", event["old"])
        if _monitor.enabled():
            _monitor.counter(
                "elastic_restores_total",
                "elastic recoveries that restored a verified "
                "checkpoint").inc()
        logger.warning(
            "elastic: %s -> rescaled %s -> %s (%d surviving device(s)), "
            "restored from checkpoint_%s at step %d, global batch "
            "preserved via grad-accum x%d (%.2fs)",
            type(err).__name__, event["old"], event["new"], len(devices),
            serial, self._step, event["grad_accum_steps"],
            event["duration_s"])
        return prog

    def _maybe_upscale(self, prog) -> None:
        """Capacity-return probe (FLAGS_elastic_upscale_after_steps):
        after N consecutive healthy steps at reduced width, re-enumerate
        devices and rescale BACK UP — no state restore, the live state
        re-shards onto the bigger mesh at the next dispatch. Capped at
        the width train() started with (the global batch is known to
        divide it)."""
        from ..flags import flag
        from ..parallel.sharding import make_mesh
        from ..resilience.distributed import mesh_axes

        n = int(flag("elastic_upscale_after_steps"))
        if not n or not self._elastic_enabled() \
                or not isinstance(prog, CompiledProgram) \
                or prog._mesh is None or self._full_ndev is None:
            return
        if self.elastic_devices_fn is None:
            # the default jax.devices() enumeration cannot reflect a
            # lost chip in-process, so an upscale decided from it could
            # re-adopt the dead device and oscillate the PT612 budget
            # away — capacity-return probing needs an authoritative
            # prober (elastic_devices_fn)
            if not getattr(self, "_warned_upscale_probe", False):
                self._warned_upscale_probe = True
                logger.warning(
                    "elastic: FLAGS_elastic_upscale_after_steps is set "
                    "but no elastic_devices_fn is installed — skipping "
                    "capacity-return probes (the default device "
                    "enumeration cannot be trusted after a loss)")
            return
        current = int(prog._mesh.devices.size)
        if current >= self._full_ndev:
            return
        self._healthy_steps += 1
        if self._healthy_steps < n:
            return
        self._healthy_steps = 0
        devices = self._probe_devices()
        if len(devices) <= current:
            return
        old_axes = mesh_axes(prog._mesh)
        t0 = time.perf_counter()
        try:
            new_axes = _elastic.plan_rescale(
                old_axes, min(len(devices), self._full_ndev),
                global_batch=self._last_global_batch)
        except _elastic.ElasticRescaleError:
            return   # probe only; an unsatisfiable upscale is not fatal
        if new_axes == old_axes:
            return
        survivors = _elastic.survivor_devices(devices, new_axes)
        prog.rescale(make_mesh(new_axes, survivors))
        self._train_mesh = prog._mesh
        # no restore on the way up — but the live state is committed to
        # the smaller mesh and must re-shard at the next dispatch
        self._unshard_stale_state(prog._mesh)
        event = self._record_rescale(old_axes, new_axes, "up", None,
                                     "capacity_returned",
                                     time.perf_counter() - t0)
        logger.warning(
            "elastic: capacity returned — rescaled %s -> %s without "
            "restore (live state re-shards at the next dispatch)",
            event["old"], event["new"])

    # -- the loop --------------------------------------------------------
    def train(self, num_epochs: int, event_handler: Callable,
              reader: Callable, feed_order):
        from ..data_feeder import DataFeeder

        feeder = DataFeeder(feed_list=list(feed_order),
                            program=self.main_program)
        prog = self.main_program
        if self._parallel:
            prog = CompiledProgram(self.main_program).with_data_parallel(
                loss_name=self.loss.name,
                build_strategy=self._build_strategy)
            self._train_mesh = prog._mesh
            self._full_dp = int(prog._mesh.shape.get("dp", 1))
            self._full_ndev = int(prog._mesh.devices.size)
        from ..resilience import distributed as _dist

        # the rescale budget and upscale streak are per train() call
        # (FLAGS_elastic_max_rescales documents it that way); the
        # elastic_events audit list stays cumulative across calls
        self._elastic_rescales = 0
        self._healthy_steps = 0
        prev_recovery = _dist._recovery
        if self._ckpt:
            _dist.set_divergence_recovery(self._recover_from_checkpoint)
        # SIGTERM/preemption notice -> finish the step, checkpoint, exit 0
        # (resilience.graceful). Scoped to this call: handlers restore on
        # exit; non-main-thread callers fall back to event polling only.
        installed = _graceful.install_signal_handlers()
        self.interrupted = False
        try:
            self._train_loop(num_epochs, event_handler, feeder, reader,
                             prog)
        finally:
            # scoped to this loop: a stale trainer's recovery walk must
            # never swallow a later, unrelated run's divergence
            _dist.set_divergence_recovery(prev_recovery)
            if installed:
                _graceful.uninstall_signal_handlers()

    def _consume_resume_cursor(self, reader):
        """(epoch, skip) for re-entering the loop at the pending resume
        cursor — shared by initial resume, elastic recovery and the
        divergence rewind so all three paths keep identical semantics."""
        cur = self._resume_cursor or _elastic.DataCursor()
        self._resume_cursor = None
        cur.apply_to_reader(reader)
        return cur.epoch, cur.batch

    def _train_loop(self, num_epochs, event_handler, feeder, reader, prog):
        from ..resilience.distributed import WatchdogTimeout

        epoch, skip = 0, 0
        if self._resume_cursor is not None:
            epoch, skip = self._consume_resume_cursor(reader)
        with scope_guard(self.scope):
            while epoch < num_epochs:
                try:
                    stopped = self._run_epoch(epoch, event_handler,
                                              feeder, reader, prog, skip)
                except (_elastic.DeviceLostError, WatchdogTimeout) as e:
                    # detection already dumped the flight recorder (the
                    # device-loss classifier / the watchdog expiry); the
                    # recovery episode itself is traced below
                    prog = self._elastic_recover(e, prog)
                    epoch, skip = self._consume_resume_cursor(reader)
                    continue   # re-enter from the restored cursor
                except _EpochRewind:
                    # a mid-step divergence restore rolled the lineage
                    # back: rewind the data stream with it
                    epoch, skip = self._consume_resume_cursor(reader)
                    continue
                if stopped:
                    return     # graceful shutdown: checkpointed, exit 0
                skip = 0
                epoch += 1

    def _run_epoch(self, epoch, event_handler, feeder, reader, prog,
                   skip) -> bool:
        """One epoch; ``skip`` batches are fast-forwarded (deterministic
        resume: those batches are already committed in the restored
        state). Returns True when a graceful shutdown unwound the loop."""
        event_handler(BeginEpochEvent(epoch))
        for step, batch in enumerate(reader()):
            if step < skip:
                # resume fast-forward: the restored state already
                # contains these batches' effect — consume-and-drop so
                # the NEXT batch is exactly the first uncommitted one
                if _monitor.enabled():
                    _monitor.counter(
                        "elastic_data_fastforward_batches_total",
                        "batches skipped by the data-cursor "
                        "fast-forward on resume").inc()
                continue
            begin = BeginStepEvent(epoch, step)
            event_handler(begin)
            fetches = [self.loss.name] if begin.fetch_metrics else []
            # one trace per training step (root span; data fetch,
            # executor dispatch, divergence checks and checkpoint writes
            # land as children). The trace covers everything from feed
            # build through the post-step checkpoint decision, so a
            # device loss or watchdog hang leaves a complete error-status
            # step trace in the flight recorder.
            step_span = _trace.root_span("trainer.step", epoch=epoch,
                                         step=step,
                                         global_step=self._step)
            step_span.__enter__()
            step_err: Optional[BaseException] = None
            t0 = time.perf_counter()
            try:
                # the batch the elastic planner must keep divisible
                # across a surviving dp width (PT613 refusal)
                try:
                    self._last_global_batch = len(batch)
                except TypeError:
                    pass
                with _trace.span("trainer.data"):
                    fd = feeder.feed(batch)
                # belt and braces for fully-async dispatch: a real device
                # loss can surface only HERE, at the metric materialization
                # — classify it typed so the elastic recovery still fires
                with _elastic.device_loss_classification("parallel_step"):
                    vals = self.exe.run(prog, feed=fd, fetch_list=fetches)
                    metrics = [float(np.asarray(v).reshape(-1)[0])
                               for v in vals]
            except BaseException as e:
                step_err = e
                raise
            finally:
                if step_err is not None:
                    step_span.set_attribute("outcome",
                                            type(step_err).__name__)
                    step_span.__exit__(type(step_err), step_err, None)
            post_err = None
            try:
                if self._restored_step is not None:
                    # a divergence restore rolled this step back mid-
                    # run: the scope holds the checkpoint's state, so
                    # the counter adopts the checkpoint's step instead
                    # of advancing past state that no longer exists
                    self._step = self._restored_step
                    self._restored_step = None
                    if self._resume_cursor is not None:
                        # the checkpoint carries a data cursor: rewind the
                        # data stream with the state (no EndStepEvent — the
                        # step that just ran was rolled back)
                        step_span.set_attribute("outcome",
                                                "divergence_rewind")
                        raise _EpochRewind()
                    # legacy checkpoint without a cursor: keep the historic
                    # continue-forward semantics
                else:
                    self._step += 1
                # the committed data position: the NEXT batch is step+1 of
                # this epoch (checkpointed with the state as data_cursor)
                self._cursor = _elastic.DataCursor.capture(epoch, step + 1,
                                                           reader)
                if _monitor.enabled():
                    _monitor.counter(
                        "trainer_steps_total",
                        "steps run by contrib.Trainer.train").inc()
                    _monitor.histogram(
                        "trainer_step_seconds",
                        "Trainer step wall time (feed build + executor "
                        "dispatch + metric fetch)").observe(
                        time.perf_counter() - t0)
                    if metrics:
                        _monitor.gauge(
                            "trainer_last_loss",
                            "most recent fetched loss").set(metrics[0])
                event_handler(EndStepEvent(epoch, step, metrics))
                self._maybe_upscale(prog)
                saved_this_step = False
                if self._ckpt and self._step % \
                        self._ckpt.step_interval == 0:
                    self._save_checkpoint()
                    saved_this_step = True
                if _graceful.shutdown_requested():
                    # preemption notice: the in-flight step completed
                    # above; write the final verified checkpoint (data
                    # cursor included) and unwind so the process can exit
                    # 0 — but never a byte-identical duplicate of the
                    # interval save that just ran (the grace window is
                    # for exiting)
                    if self._ckpt and not saved_this_step:
                        self._save_checkpoint()
                    self.interrupted = True
                    if _monitor.enabled():
                        _monitor.counter(
                            "trainer_graceful_exits_total",
                            "train() calls unwound by a graceful-shutdown "
                            "request after a final checkpoint").inc()
                    logger.warning(
                        "graceful shutdown: step %d checkpointed, train() "
                        "returning cleanly", self._step)
                    step_span.set_attribute("outcome", "graceful_exit")
                    return True
            except BaseException as e:
                post_err = e
                raise
            finally:
                # close the step trace on every unwind; the dispatch-
                # failure path closed it in the except block above. A
                # post-dispatch failure (event handler, checkpoint write,
                # upscale) must NOT be mislabeled 'ok' — the flight
                # recorder consulted for that incident would lie.
                # _EpochRewind is control flow, not an error: its span
                # closes clean with the 'divergence_rewind' outcome.
                if post_err is not None \
                        and not isinstance(post_err, _EpochRewind):
                    if step_span.attrs.get("outcome") is None:
                        step_span.set_attribute("outcome",
                                                type(post_err).__name__)
                    step_span.__exit__(type(post_err), post_err, None)
                else:
                    if step_span.attrs.get("outcome") is None \
                            and not step_span.error:
                        step_span.set_attribute("outcome", "ok")
                    step_span.__exit__(None, None, None)
        event_handler(EndEpochEvent(epoch))
        # next batch after a completed epoch is the next epoch's first
        self._cursor = _elastic.DataCursor.capture(epoch + 1, 0, reader)
        if self._ckpt and (epoch + 1) % \
                self._ckpt.epoch_interval == 0:
            self._save_checkpoint()
        return False

    def save_params(self, dirname: str):
        with scope_guard(self.scope):
            io_mod.save_params(self.exe, dirname, self.main_program)

    def save_inference_model(self, dirname, feeded_var_names, target_vars):
        with scope_guard(self.scope):
            io_mod.save_inference_model(dirname, feeded_var_names,
                                        target_vars, self.exe,
                                        main_program=self.main_program)

    def stop(self):
        self.exe.close()


class Inferencer:
    """reference contrib/inferencer.py:31: infer_func rebuilds the forward
    under fresh programs; params load from ``param_path``."""

    def __init__(self, infer_func: Callable, param_path: str, place=None,
                 parallel: bool = False):
        self.main_program = Program()
        self.startup_program = Program()
        with program_guard(self.main_program, self.startup_program):
            self.predict_var = infer_func()
        self.exe = Executor(place)
        self.scope = Scope()
        with scope_guard(self.scope):
            self.exe.run(self.startup_program)
            io_mod.load_params(self.exe, param_path, self.main_program)

    def infer(self, inputs: dict):
        with scope_guard(self.scope):
            (out,) = self.exe.run(self.main_program, feed=inputs,
                                  fetch_list=[self.predict_var.name])
        return out
