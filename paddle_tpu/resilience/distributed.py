"""Distributed resilience: sharded elastic checkpoints, cross-replica
divergence detection, and the step watchdog.

PR 4 made restart-after-failure a first-class path for the single-host
executor; this module extends it into the parallel layer (ROADMAP item 3 —
ZeRO-sharded optimizer state per arXiv 2004.13336 — is only safe once a
dp-sharded Adam moment can be checkpointed WITHOUT a full gather and a
host crash cannot lose the run). Three pillars:

* **Sharded elastic checkpoints** (``io.save_checkpoint(..., mesh=...)``,
  manifest ``format_version`` 2): every mesh shard lands as its own
  fsynced blob under the serial, the manifest records per-shard sha256 +
  the mesh shape + a per-param sharding spec, and publish stays the PR 4
  atomic temp-dir + rename. Restore reassembles the full value
  (= the full-gather path, bit for bit), so a run saved on dp=8 resumes
  on dp=4 or on one host — the next dispatch re-shards onto whatever mesh
  exists. PT605–PT609 diagnose shard-count/spec mismatches and torn shard
  writes (``resilience.checkpoint.CKPT_CODES``).
* **Cross-replica divergence detection** (``FLAGS_replica_check_interval``):
  every N-th data-parallel step each device reduces its LOCAL copy of the
  replicated params/optimizer state to a pair of uint32 checksums inside a
  jitted ``shard_map`` — no host gather of tensors, only ``2*V`` words —
  and replicas that must hold identical bytes are compared host-side.
  Disagreement raises :class:`ReplicaDivergenceError` naming the first
  diverged param, or (``FLAGS_replica_divergence_policy=restore``) rolls
  back to the last verified checkpoint via the PR 4 recovery walk.
* **Step watchdog** (``FLAGS_step_timeout_s``): a daemon thread armed
  around compile/step/collective sections. On expiry it dumps every
  thread's stack, the active program serial and the last recompile
  diagnosis, then interrupts the hung section so it raises
  :class:`WatchdogTimeout` instead of hanging CI forever; a section still
  stuck one extra timeout later (native-code hang) hard-exits 124 with
  the diagnosis already on stderr (``FLAGS_watchdog_hard_exit``).

Deterministic testing: ``faults.py`` grew the ``shard_write`` site (before
each per-shard blob) and the ``hang`` site/action (an interruptible stall
inside the armed dispatch sections). End-to-end proof:
``tools/chaos_check.py --multichip``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import logging
import os
import sys
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..monitor.lockwitness import make_lock
from .faults import fault_point

__all__ = ["ReplicaDivergenceError", "WatchdogTimeout", "watchdog_section",
           "replica_divergence_check", "handle_divergence",
           "set_divergence_recovery", "save_sharded_vars",
           "load_sharded_vars", "shard_axis_of", "mesh_axes"]

logger = logging.getLogger("paddle_tpu.resilience")

COMMON_FILE = "common.npz"


# ---------------------------------------------------------------------------
# pillar 1: sharded elastic checkpoints (manifest format_version 2)
# ---------------------------------------------------------------------------

def mesh_axes(mesh) -> Dict[str, int]:
    """Normalise a mesh argument (jax Mesh | {'dp': 8} | 8) to axis sizes."""
    if mesh is None:
        return {}
    if isinstance(mesh, int):
        return {"dp": int(mesh)}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        return {str(k): int(v) for k, v in dict(shape).items()}
    raise TypeError(f"save_checkpoint: cannot read a mesh shape from "
                    f"{mesh!r} (want a jax Mesh, a dict of axis sizes, or "
                    f"an int shard count)")


def shard_axis_of(value, axis: str) -> Optional[int]:
    """The array dim ``value`` is sharded on over mesh axis ``axis``
    (from its live NamedSharding), or None when replicated/off-mesh."""
    sharding = getattr(value, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        if axis in names:
            return dim
    return None


def _shard_file_name(k: int, n: int) -> str:
    return f"shard_{k:05d}-of-{n:05d}.npz"


def save_sharded_vars(dirname: str, vars_: Sequence, scope, mesh) -> dict:
    """Write ``vars_`` (program Variables with scope values) as a sharded
    checkpoint into ``dirname`` (the temp dir of ``io.save_checkpoint``'s
    atomic publish). Vars whose live jax sharding splits a dim over the
    mesh's dp axis are written as one slice per shard file
    (``shard_write`` fault site fires before each); everything replicated
    goes to ``common.npz``. Returns the manifest skeleton
    (vars inventory + the ``sharding`` section) it wrote — fsync and the
    per-file sha256 happen in ``resilience.checkpoint.finalize_manifest``.
    """
    from .. import monitor as _monitor

    axes = mesh_axes(mesh)
    axis = "dp" if "dp" in axes else (next(iter(axes)) if axes else "dp")
    n = max(1, int(axes.get(axis, 1)))
    inventory: Dict[str, dict] = {}
    specs: Dict[str, dict] = {}
    common: Dict[str, np.ndarray] = {}
    shards: List[Dict[str, Any]] = [dict() for _ in range(n)]
    key_owner: Dict[str, str] = {}
    for v in vars_:
        val = scope.find_var(v.name)
        if val is None:
            raise RuntimeError(
                f"save: variable '{v.name}' has no value in scope")
        key = v.name.replace("/", "__")
        if key_owner.setdefault(key, v.name) != v.name:
            # the '/'->'__' mangling is not injective; refusing loudly
            # beats one var's bytes silently overwriting another's
            raise RuntimeError(
                f"save: var names '{key_owner[key]}' and '{v.name}' both "
                f"serialize to blob key '{key}' — rename one")
        dim = shard_axis_of(val, axis)
        shape = tuple(getattr(val, "shape", np.shape(val)))
        inventory[v.name] = {"shape": list(shape),
                             "dtype": str(getattr(val, "dtype",
                                                  np.asarray(val).dtype))}
        if n > 1 and dim is not None and dim < len(shape) \
                and shape[dim] % n != 0:
            # uneven live sharding cannot round-trip through equal-split
            # shard files; the replicated fallback below re-gathers the
            # whole value — loud, because that is the memory blow-up the
            # sharded format exists to avoid
            logger.warning(
                "sharded checkpoint: '%s' is sharded on dim %d but "
                "%d %% %d != 0 — falling back to a full-gather "
                "replicated write for this var", v.name, dim,
                shape[dim], n)
        if n > 1 and dim is not None and dim < len(shape) \
                and shape[dim] % n == 0:
            specs[v.name] = {"dim": int(dim), "parts": n}
            # slice-wise, never a full host gather: each piece is pulled
            # on its own so the host never holds more than one slice of a
            # dp-sharded value (the whole point of the sharded format)
            sz = shape[dim] // n
            for k in range(n):
                idx = (slice(None),) * dim + (slice(k * sz, (k + 1) * sz),)
                shards[k][key] = (val, idx)
        else:
            common[key] = np.asarray(val)
    with open(os.path.join(dirname, COMMON_FILE), "wb") as f:
        np.savez(f, **common)
    shard_files = [_shard_file_name(k, n) for k in range(n)]
    for k, fname in enumerate(shard_files):
        # one host of a distributed writer dying here is the failure the
        # format must survive: the manifest/publish never happens, the
        # serial stays unpublished, recovery falls back (chaos multichip)
        fault_point("shard_write")
        pieces = {key: np.asarray(val[idx])
                  for key, (val, idx) in shards[k].items()}
        with open(os.path.join(dirname, fname), "wb") as f:
            np.savez(f, **pieces)
    if _monitor.enabled():
        _monitor.counter(
            "resilience_shards_written_total",
            "per-shard blob files written by sharded checkpoints").inc(n)
    manifest = {"vars": inventory, "filename": None,
                "sharding": {"mesh": axes, "axis": axis, "num_shards": n,
                             "common_file": COMMON_FILE,
                             "shard_files": shard_files, "specs": specs}}
    with open(os.path.join(dirname, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    return manifest


def _load_npz(path: str) -> Dict[str, np.ndarray]:
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def load_sharded_vars(dirname: str, manifest: dict, vars_: Sequence,
                      scope) -> None:
    """Reassemble a format_version-2 sharded checkpoint into ``scope``.

    This IS the full-gather restore: every sharded var's pieces are
    concatenated back to the full value, so restoring on fewer devices (or
    one host) is bit-identical to a gather-then-save checkpoint — the next
    dispatch re-shards onto whatever mesh the resumed run has (elastic
    dp=8 -> dp=4 -> 1). Two-phase like ``io._load_var_list``: everything
    is read and validated before the first ``set_var`` so a failed load
    never half-mutates the scope. Content mismatches raise
    ``CheckpointCorruptError`` PT606/PT608."""
    import jax.numpy as jnp

    from .checkpoint import (CheckpointCorruptError,
                             verify_sharding_section)
    from .. import monitor as _monitor

    # structural checks again here: the verify=False path (and any direct
    # caller) must still get PT605/PT607/PT609 instead of a raw KeyError
    sh = verify_sharding_section(dirname, manifest)
    n = int(sh["num_shards"])
    specs = sh["specs"]
    inventory = manifest.get("vars") or {}
    common = _load_npz(os.path.join(dirname, sh.get("common_file",
                                                    COMMON_FILE)))
    shard_blobs = [_load_npz(os.path.join(dirname, f))
                   for f in sh["shard_files"]]
    staged: List[Tuple[str, np.ndarray]] = []
    for v in vars_:
        key = v.name.replace("/", "__")
        spec = specs.get(v.name)
        want = inventory.get(v.name)
        if spec is None:
            if key not in common:
                raise RuntimeError(
                    f"load: '{v.name}' missing from sharded checkpoint "
                    f"'{dirname}'")
            arr = common[key]
        else:
            dim = int(spec["dim"])
            if want is not None and dim >= len(want.get("shape", ())):
                raise CheckpointCorruptError(
                    "PT606", dirname,
                    f"'{v.name}' spec shards dim {dim} but the var is "
                    f"{len(want['shape'])}-d")
            pieces = []
            for k, blob in enumerate(shard_blobs):
                if key not in blob:
                    raise CheckpointCorruptError(
                        "PT606", dirname,
                        f"piece of '{v.name}' missing from shard {k}/{n}")
                pieces.append(blob[key])
            try:
                arr = np.concatenate(pieces, axis=dim)
            except Exception as e:
                raise CheckpointCorruptError(
                    "PT608", dirname,
                    f"'{v.name}' pieces do not concatenate on dim {dim}: "
                    f"{e}")
        if want is not None and list(arr.shape) != list(want["shape"]):
            raise CheckpointCorruptError(
                "PT608", dirname,
                f"'{v.name}' reassembled to {list(arr.shape)}, manifest "
                f"says {want['shape']}")
        if v.shape is not None and tuple(arr.shape) != tuple(v.shape) \
                and -1 not in (v.shape or ()):
            raise RuntimeError(
                f"load: shape mismatch for '{v.name}': checkpoint "
                f"{arr.shape} vs program {v.shape}")
        staged.append((v.name, arr))
    for name, arr in staged:
        scope.set_var(name, jnp.asarray(arr))
    if _monitor.enabled():
        _monitor.counter(
            "resilience_sharded_restores_total",
            "sharded (format_version 2) checkpoints reassembled into a "
            "scope").inc()


# ---------------------------------------------------------------------------
# pillar 2: cross-replica divergence detection
# ---------------------------------------------------------------------------

class ReplicaDivergenceError(RuntimeError):
    """Replicated state disagrees across data-parallel replicas. Carries
    ``param`` (the first diverged name) and ``diverged`` (all of them).
    Never retried (``transient = False``): diverged replicas are a
    determinism bug or corrupted memory, not infrastructure noise."""

    transient = False

    def __init__(self, diverged: Sequence[str], axis: str = "dp"):
        self.diverged = list(diverged)
        self.param = self.diverged[0] if self.diverged else "<unknown>"
        super().__init__(
            f"replica divergence across the '{axis}' axis: param "
            f"'{self.param}' holds different bytes on different replicas "
            f"({len(self.diverged)} diverged var(s): "
            f"{', '.join(self.diverged[:5])}"
            f"{', …' if len(self.diverged) > 5 else ''}). Replicated "
            f"state must be bit-identical; this is nondeterminism or "
            f"memory corruption, not noise — restore from the last "
            f"verified checkpoint (FLAGS_replica_divergence_policy="
            f"restore) or debug the step.")


def _bits_u32(x):
    """LOSSLESS uint32 view of an array's bit patterns, branched by item
    width so no dtype can alias two different bit patterns to one
    checksum word (wraparound arithmetic downstream is fine: the checksum
    only needs replica-equality)."""
    import jax.numpy as jnp
    import numpy as _np
    from jax import lax

    dt = _np.dtype(x.dtype)
    if dt.itemsize == 8:      # float64/int64/uint64 under jax_enable_x64
        w = lax.bitcast_convert_type(x, jnp.uint64).ravel()
        return jnp.concatenate([(w >> 32).astype(jnp.uint32),
                                (w & jnp.uint64(0xFFFFFFFF)).astype(
                                    jnp.uint32)])
    if dt.itemsize == 4:
        u = lax.bitcast_convert_type(x, jnp.uint32) if dt.kind == "f" \
            else x.astype(jnp.uint32)      # int32<->uint32 is bijective
    elif dt.itemsize == 2:    # float16/bfloat16/int16/uint16
        u = (lax.bitcast_convert_type(x, jnp.uint16)
             if dt.kind == "f" or dt.name == "bfloat16"
             else x).astype(jnp.uint32)
    else:                     # int8/uint8/bool — one word per element
        u = x.astype(jnp.uint32)
    return u.ravel()


_checker_cache: Dict[tuple, Any] = {}


def _pspec_of(v):
    from jax.sharding import PartitionSpec as P

    spec = getattr(getattr(v, "sharding", None), "spec", None)
    return spec if spec is not None else P()


def replica_divergence_check(mesh, values: Dict[str, Any],
                             axis: Optional[str] = None) -> List[str]:
    """Names in ``values`` whose device copies disagree where the sharding
    says they must agree.

    Each device reduces its LOCAL block to two uint32 checksums (bit-
    pattern sum + position-weighted sum) inside one jitted ``shard_map``
    over the whole mesh — the only host transfer is ``2`` words per var
    per device. Host-side, two devices are required to match iff they
    share coordinates on every axis the var is actually sharded over —
    for state replicated over ``dp`` (params, and Adam moments outside
    ZeRO) that compares physical replica bytes across the dp axis.
    ``axis`` restricts the sweep to ONE replication axis (vars sharded
    over it are skipped); the default ``None`` compares across every
    axis a value is replicated over, which is strictly stronger."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if not values:
        return []
    items = sorted(values.items())
    names = [n for n, _ in items]
    vals = [v for _, v in items]
    metas = tuple((tuple(v.shape), str(v.dtype), _pspec_of(v))
                  for v in vals)
    axis_names = tuple(mesh.axis_names)
    key = (mesh, metas, axis)
    fn = _checker_cache.get(key)
    if fn is None:
        n_axes = len(axis_names)
        in_specs = tuple(m[2] for m in metas)

        def local(*xs):
            sums = []
            for x in xs:
                u = _bits_u32(x)
                if u.size:
                    s1 = jnp.sum(u, dtype=jnp.uint32)
                    w = (jnp.arange(u.size, dtype=jnp.uint32) << 1) \
                        | jnp.uint32(1)
                    s2 = jnp.sum(u * w, dtype=jnp.uint32)
                else:
                    s1 = s2 = jnp.uint32(0)
                sums.append(jnp.stack([s1, s2]))
            out = jnp.stack(sums)                      # [V, 2] per device
            return out.reshape((1,) * n_axes + out.shape)

        fn = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=in_specs,
            out_specs=P(*axis_names, None, None)))
        # bounded: evict oldest so dead meshes / compiled checkers from
        # long sessions (notebooks, test suites) cannot accumulate forever
        while len(_checker_cache) >= 8:
            _checker_cache.pop(next(iter(_checker_cache)))
        _checker_cache[key] = fn
    sums = np.asarray(fn(*vals))     # [*mesh_shape, V, 2] — tiny
    mesh_shape = sums.shape[:len(axis_names)]
    diverged = []
    for i, (name, meta) in enumerate(zip(names, metas)):
        spec = meta[2]
        sharded_axes = set()
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, (tuple, list))
                      else (entry,)):
                if a:
                    sharded_axes.add(a)
        # collapse the axes this var is SHARDED over (each coordinate is a
        # different block — nothing to compare); whatever axes remain are
        # replication axes, along which every checksum must be identical
        # group-defining dims: sharded axes always (each coordinate is a
        # different block), plus — when the sweep is restricted to one
        # axis — every OTHER axis, so only ``axis`` replicas compare
        keep = {d for d, a in enumerate(axis_names)
                if a in sharded_axes or (axis is not None and a != axis)}
        per_var = sums[..., i, :]
        # iterate shard groups explicitly (mesh ranks are few): one group
        # per coordinate along the sharded axes, slicing all replica axes
        ranges = [range(mesh_shape[d]) if d in keep else (slice(None),)
                  for d in range(len(axis_names))]
        ok = True
        for coords in itertools.product(*ranges):
            flat = per_var[tuple(coords)].reshape(-1, 2)
            if flat.shape[0] > 1 and not (flat == flat[0]).all():
                ok = False
                break
        if not ok:
            diverged.append(name)
    return diverged


# restore policy wiring: contrib.Trainer registers its recovery walk here
# (the PR 4 newest->oldest verified-checkpoint reload); anything returning
# truthy means "state restored, keep training"
_recovery: Optional[Callable[[], Any]] = None


def set_divergence_recovery(fn: Optional[Callable[[], Any]]) -> None:
    global _recovery
    _recovery = fn


def block_until_ready_concrete(tree) -> None:
    """``jax.block_until_ready`` that no-ops for traced values (a jit
    caller's tracers) but lets REAL async runtime failures propagate —
    a bare except here would detach a failed dispatch from its call
    site. Used by the eager collective wrappers (parallel.pipeline /
    parallel.ring_attention) while watchdog-armed."""
    import jax

    try:
        from jax.core import Tracer
    except Exception:       # jax moved it; fall back to no filtering
        Tracer = ()
    leaves = jax.tree_util.tree_leaves(tree)
    if any(isinstance(leaf, Tracer) for leaf in leaves):
        return
    jax.block_until_ready(tree)


def handle_divergence(diverged: Sequence[str], path: str = "parallel",
                      axis: str = "dp") -> None:
    """Apply ``FLAGS_replica_divergence_policy`` to a non-empty diverged
    set: ``raise`` trips :class:`ReplicaDivergenceError`; ``restore``
    rolls the scope back to the last verified checkpoint through the
    registered recovery walk and keeps training (escalating to raise when
    nothing restorable exists)."""
    from .. import monitor as _monitor
    from ..flags import flag

    if _monitor.enabled():
        _monitor.counter(
            "resilience_divergence_detected_total",
            "cross-replica divergence detections").labels(path=path).inc()
    from .. import trace as _trace

    _trace.record_incident(
        "replica_divergence",
        detail=f"path {path}, axis {axis}: "
               f"{', '.join(list(diverged)[:5])}")
    policy = str(flag("replica_divergence_policy")).strip().lower()
    if policy not in ("raise", "restore"):
        raise ValueError(
            f"FLAGS_replica_divergence_policy={policy!r} — expected "
            f"raise or restore")
    err = ReplicaDivergenceError(diverged, axis=axis)
    if policy == "restore" and _recovery is not None:
        restored = False
        try:
            restored = bool(_recovery())
        except Exception:
            logger.exception("divergence recovery walk itself failed")
        if restored:
            if _monitor.enabled():
                _monitor.counter(
                    "resilience_divergence_restores_total",
                    "divergences resolved by rolling back to the last "
                    "verified checkpoint").inc()
            logger.warning(
                "replica divergence on '%s' (+%d more): restored the last "
                "verified checkpoint, training continues "
                "(FLAGS_replica_divergence_policy=restore)", err.param,
                max(0, len(err.diverged) - 1))
            return
        logger.error("replica divergence: restore policy had nothing to "
                     "restore — escalating to raise")
    raise err


# ---------------------------------------------------------------------------
# pillar 3: step watchdog
# ---------------------------------------------------------------------------

class _WatchdogInterrupt(BaseException):
    """Async exception the watchdog raises INSIDE a hung non-main thread
    (``PyThreadState_SetAsyncExc``) — the cross-thread analogue of the
    ``interrupt_main``/KeyboardInterrupt path the main thread gets. A
    ``BaseException`` so broad ``except Exception`` handlers inside the
    hung section cannot swallow it; ``watchdog_section`` converts it to
    :class:`WatchdogTimeout` before callers see it. Serving's dispatch
    thread is the reason this exists: a slow-batch hang there must die
    diagnosed and typed, not ride straight to the hard-exit escalation."""


def _interrupt_thread(thread_id: int) -> bool:
    """Raise :class:`_WatchdogInterrupt` asynchronously in ``thread_id``.
    Delivery happens at the thread's next bytecode boundary — enough for
    Python-level stalls (the ``hang`` fault action sleeps in 20 ms slices);
    a hang inside native code stays for the hard-exit escalation."""
    import ctypes

    res = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(thread_id), ctypes.py_object(_WatchdogInterrupt))
    if res > 1:
        # "affected more than one thread" — undo per CPython docs
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(thread_id), None)
        return False
    return res == 1


class WatchdogTimeout(RuntimeError):
    """An armed compile/step/collective section exceeded
    ``FLAGS_step_timeout_s``. The full diagnosis (all thread stacks, the
    active program serial, the last recompile diagnosis) was already
    dumped to the resilience logger and stderr when the deadline fired.
    ``transient = False``: a hang is never retried."""

    transient = False

    def __init__(self, section: str, seconds: float, detail: str = ""):
        self.section = section
        self.seconds = seconds
        self.detail = detail
        super().__init__(
            f"watchdog: section '{section}' exceeded "
            f"FLAGS_step_timeout_s={seconds:g}s"
            f"{' (' + detail + ')' if detail else ''} — thread stacks and "
            f"the last recompile diagnosis were dumped at expiry")


@dataclasses.dataclass
class _Section:
    token: int
    section: str
    detail: str
    timeout: float
    deadline: float
    thread_id: int
    expired: bool = False
    hard_deadline: Optional[float] = None


_wd_lock = make_lock("resilience.distributed._wd_lock")
_wd_armed: Dict[int, _Section] = {}
_wd_tokens = itertools.count(1)
_wd_thread: Optional[threading.Thread] = None


def _dump_section(s: _Section) -> str:
    lines = [
        f"watchdog: section '{s.section}' exceeded {s.timeout:g}s "
        f"({s.detail or 'no detail'})",
    ]
    try:
        from .. import monitor as _monitor

        evs = _monitor.get_tracker().events(recompiles_only=False)
        if evs:
            e = evs[-1]
            lines.append(
                f"  last compile: path={e.path} program_serial="
                f"{e.program_serial} recompile={e.recompile} "
                f"changed={list(e.changed)} at {e.build_site}")
        else:
            lines.append("  last compile: <none recorded>")
    except Exception:
        lines.append("  last compile: <monitor unavailable>")
    frames = sys._current_frames()
    by_id = {t.ident: t for t in threading.enumerate()}
    for tid, frame in frames.items():
        t = by_id.get(tid)
        name = t.name if t else "?"
        mark = " [hung section]" if tid == s.thread_id else ""
        lines.append(f"-- thread '{name}' ({tid}){mark} --")
        lines.append("".join(traceback.format_stack(frame)).rstrip())
    # flight recorder: the hang's diagnosis ships with the last N trace
    # spans (the hung request/step's chain among them) — incidents() /
    # the ci_trace_report artifact carry the structured form
    try:
        from .. import trace as _trace

        incident = _trace.record_incident(
            "watchdog_timeout",
            detail=f"section '{s.section}' ({s.detail or 'no detail'}) "
                   f"exceeded {s.timeout:g}s")
        if incident["recent_spans"]:
            lines.append(f"-- flight recorder: last "
                         f"{len(incident['recent_spans'])} span(s) --")
            for d in incident["recent_spans"][-12:]:
                lines.append(
                    f"  {d['name']} trace={d['trace_id']} "
                    f"status={d['status']} "
                    f"dur={d['duration_s'] if d['duration_s'] is not None else '?'} "
                    f"attrs={d['attrs']}")
        elif not incident["flight_recorder_enabled"]:
            lines.append("-- flight recorder: disabled (FLAGS_trace / "
                         "FLAGS_flight_recorder_size) — no span context --")
    except Exception:
        logger.exception("flight-recorder dump failed (diagnosis "
                         "continues without span context)")
    text = "\n".join(lines)
    logger.error("%s", text)
    print(text, file=sys.stderr, flush=True)
    return text


def _wd_loop() -> None:
    import _thread

    while True:
        now = time.monotonic()
        with _wd_lock:
            sections = list(_wd_armed.values())
        for s in sections:
            if not s.expired and now >= s.deadline:
                s.expired = True
                s.hard_deadline = now + max(s.timeout, 1.0)
                try:
                    _dump_section(s)
                except Exception:   # the dump must never kill the dog
                    logger.exception("watchdog diagnosis dump failed")
                try:
                    from .. import monitor as _monitor

                    _monitor.record_watchdog_timeout(s.section)
                except Exception:
                    pass
                with _wd_lock:
                    still = s.token in _wd_armed
                if still:
                    if s.thread_id == threading.main_thread().ident:
                        _thread.interrupt_main()
                    else:
                        # non-main thread (e.g. the serving dispatcher):
                        # deliver the typed interrupt directly into it
                        _interrupt_thread(s.thread_id)
            elif s.expired and s.hard_deadline is not None \
                    and now >= s.hard_deadline:
                with _wd_lock:
                    still = s.token in _wd_armed
                if not still:
                    continue   # disarmed between snapshot and deadline
                from ..flags import flag

                if flag("watchdog_hard_exit"):
                    print(f"watchdog: section '{s.section}' still hung "
                          f"{max(s.timeout, 1.0):g}s after the diagnosis "
                          f"dump (uninterruptible native code?) — "
                          f"os._exit(124)", file=sys.stderr, flush=True)
                    os._exit(124)
                s.hard_deadline = None   # dump once, then leave it be
        time.sleep(0.05 if sections else 0.2)


def _ensure_wd_thread() -> None:
    global _wd_thread
    if _wd_thread is None or not _wd_thread.is_alive():
        _wd_thread = threading.Thread(target=_wd_loop,
                                      name="paddle_tpu-watchdog",
                                      daemon=True)
        _wd_thread.start()


@contextlib.contextmanager
def watchdog_section(section: str, detail: str = "", timeout=None,
                     program=None):
    """Arm the watchdog around a compile/step/collective region.

    ``timeout`` defaults to ``FLAGS_step_timeout_s``; 0/None disarms (the
    default — the context manager is then a no-op). When the deadline
    fires the watchdog dumps the diagnosis and interrupts the hung
    thread — ``interrupt_main`` for the main thread, an async
    :class:`_WatchdogInterrupt` (``PyThreadState_SetAsyncExc``) for any
    other thread, e.g. the serving dispatcher. Either pending interrupt
    is converted to :class:`WatchdogTimeout` here, so callers see one
    typed, documented failure instead of a hang; a section stuck in
    uninterruptible native code still escalates to the hard exit."""
    if timeout is None:
        from ..flags import flag

        timeout = float(flag("step_timeout_s"))
    if not timeout or timeout <= 0:
        yield None
        return
    if program is not None and not detail:
        detail = f"program serial {getattr(program, '_serial', '?')}"
    s = _Section(token=next(_wd_tokens), section=section, detail=detail,
                 timeout=float(timeout),
                 deadline=time.monotonic() + float(timeout),
                 thread_id=threading.get_ident())
    from .. import monitor as _monitor

    if _monitor.enabled():
        _monitor.counter(
            "watchdog_sections_armed_total",
            "watchdog-armed executor sections").labels(
            section=section).inc()
    with _wd_lock:
        _wd_armed[s.token] = s
    _ensure_wd_thread()
    converted = False
    try:
        yield s
    except KeyboardInterrupt:
        if s.expired:
            converted = True
            raise WatchdogTimeout(section, s.timeout, s.detail) from None
        raise
    except _WatchdogInterrupt:
        # the cross-thread delivery path (non-main sections): always ours
        # — nothing else raises this type
        converted = True
        raise WatchdogTimeout(section, s.timeout, s.detail) from None
    finally:
        with _wd_lock:
            _wd_armed.pop(s.token, None)
        if s.expired and not converted:
            # the section finished in the race window between expiry and
            # interrupt delivery: absorb the in-flight interrupt here (it
            # was aimed at this section) instead of letting it detonate in
            # whatever innocent code runs next. The watchdog polls every
            # 0.05s, so a few short sleeps cover the window.
            try:
                for _ in range(4):
                    time.sleep(0.02)
            except (KeyboardInterrupt, _WatchdogInterrupt):
                logger.warning(
                    "watchdog: absorbed a late interrupt for section "
                    "'%s' that completed at its deadline", section)
