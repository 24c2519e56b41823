"""Warm-start AOT executable cache: compiled-executable export/import.

The fleet serving tier (docs/SERVING.md "Fleet tier") starts replicas by
the dozen, and every cold replica used to pay the full compile storm —
one XLA build per (program, shape bucket) before it could flip
``ready()`` true. The executor already builds real AOT executables
(``_ensure_executable``); this module persists them: after a successful
``lowered.compile()`` the executable is serialized to disk
(``jax.experimental.serialize_executable``), and the next process that
needs the same executable loads it instead of compiling — warm-up time
drops from seconds-per-bucket to milliseconds (measured cold-vs-warm in
``ci_fleet_report.json``).

Keying. The in-memory step-cache keys lean on per-process serials
(``program._serial``, ``scope._serial``) — useless across restarts. The
disk key is a content hash of the program that survives restarts
(:func:`program_content_fingerprint`) plus everything else that shapes
the compiled artifact:

* the execution kind (``run`` / ``chained`` + step count) and fetch list,
* the compiler configuration (``FLAGS_xla_options``; nan-checked and
  witness-instrumented steps are never cached),
* the abstract signature of every argument leaf (shape + dtype + tree
  structure): state shapes come from the live scope, so two scopes with
  different-shaped state can never share an executable,
* the platform of the devices the executable runs on, jax version and
  framework version (an upgraded compiler's executables are invisible).

Safety posture: loads NEVER raise — a
missing/corrupt/version-mismatched entry is a miss with one warning, and
the executor compiles as if the cache did not exist. Saves are atomic
(temp sibling + fsync + rename) so a killed replica can never publish a
torn entry. Counters: ``aot_cache_hits_total`` / ``aot_cache_misses_total``
/ ``aot_cache_saves_total`` / ``aot_cache_errors_total{op}``
(docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
from typing import Any, Optional, Tuple

from .monitor.lockwitness import make_lock

__all__ = ["executable_key", "load_executable", "save_executable",
           "cache_dir_flag", "cache_stats", "program_content_fingerprint"]

logger = logging.getLogger("paddle_tpu.aot_cache")

_SCHEMA = 1
_SUFFIX = ".aotx"

# one warning per failure class per process — a broken cache dir must not
# spam a serving replica's log at request rate
_warned = set()
_warned_lock = make_lock("aot_cache._warned_lock")


def _warn_once(kind: str, msg: str, *args) -> None:
    with _warned_lock:
        if kind in _warned:
            return
        _warned.add(kind)
    logger.warning(msg, *args)


def _versions() -> Tuple[str, str]:
    import jax

    from . import __version__

    return str(__version__), str(jax.__version__)


def cache_dir_flag() -> str:
    """``FLAGS_aot_cache_dir`` (empty = cache disabled)."""
    from .flags import flag

    return str(flag("aot_cache_dir")).strip()


def _count(name: str, help_: str, **labels) -> None:
    from . import monitor

    if monitor.enabled():
        c = monitor.counter(name, help_)
        (c.labels(**labels) if labels else c).inc()


_VOLATILE_ATTRS = ("__uid__", "op_callstack", "op_namescope")


def program_content_fingerprint(program) -> str:
    """Stable CONTENT hash of a program — unlike ``program._serial`` (a
    per-process counter) it survives process restarts, which is what makes
    the cache durable. Hashes op types, slot wiring, non-volatile attrs
    and var metadata in deterministic order; memoized per (program,
    version)."""
    cached = getattr(program, "_content_fp", None)
    if cached is not None and cached[0] == getattr(program, "_version", 0):
        return cached[1]
    h = hashlib.sha256()
    for blk in program.blocks:
        for name in sorted(blk.vars):
            v = blk.vars[name]
            h.update(f"v|{blk.idx}|{name}|{v.shape}|{v.dtype}|"
                     f"{v.persistable}|{v.is_data}\n".encode())
        for op in blk.ops:
            attrs = sorted((k, repr(val)) for k, val in op.attrs.items()
                           if k not in _VOLATILE_ATTRS)
            h.update(f"o|{blk.idx}|{op.type}|"
                     f"{sorted((k, tuple(v)) for k, v in op.inputs.items())}|"
                     f"{sorted((k, tuple(v)) for k, v in op.outputs.items())}"
                     f"|{attrs}\n".encode())
    fp = h.hexdigest()[:16]
    try:
        program._content_fp = (getattr(program, "_version", 0), fp)
    except Exception:
        pass
    return fp


def executable_key(parts: tuple, args, devices) -> str:
    """Durable identity of one compiled executable.

    ``parts`` is the executor-stamped tuple
    ``(kind, program, fetch_names, xla_opts, extra...)``; the program
    element is replaced by its content fingerprint. ``args`` are the
    exact call arguments the executable will be lowered with; only their
    abstract signature (tree structure + per-leaf shape/dtype) enters the
    key. ``devices`` are the devices it executes on (their platform
    enters the key).
    """
    import jax

    kind, program, *rest = parts
    fp = program_content_fingerprint(program)
    leaves, treedef = jax.tree_util.tree_flatten(args)
    leaf_sig = "|".join(
        f"{getattr(v, 'shape', None)}:{getattr(v, 'dtype', None)}"
        for v in leaves)
    fw, jx = _versions()
    material = repr((kind, fp, tuple(rest), leaf_sig, str(treedef),
                     devices[0].platform, fw, jx))
    return hashlib.sha256(material.encode()).hexdigest()[:32]


def _path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, key + _SUFFIX)


def load_executable(cache_dir: str, key: str, devices):
    """The executable for ``key`` deserialized and loaded onto ``devices``
    (the step's own — ``deserialize_and_load`` otherwise spreads it over
    every local device), or None. Counts a hit or a miss; never raises
    (corrupt/alien entries degrade to a miss with one warning)."""
    path = _path(cache_dir, key)
    try:
        if not os.path.exists(path):
            _count("aot_cache_misses_total",
                   "AOT executable cache lookups that had to compile")
            return None
        with open(path, "rb") as f:
            blob = pickle.load(f)
        from jax.experimental.serialize_executable import \
            deserialize_and_load

        fw, jx = _versions()
        if (not isinstance(blob, dict) or blob.get("schema") != _SCHEMA
                or blob.get("jax") != jx or blob.get("framework") != fw
                or blob.get("backend") != devices[0].platform):
            # a different compiler's executable is not a corrupt file —
            # it is simply not ours to load (staleness rule)
            _count("aot_cache_misses_total",
                   "AOT executable cache lookups that had to compile")
            _warn_once("stale",
                       "aot cache entry %s was written by a different "
                       "framework/jax/backend — ignoring (recompiling)",
                       path)
            return None
        loaded = deserialize_and_load(blob["payload"], blob["in_tree"],
                                      blob["out_tree"],
                                      backend=devices[0].client,
                                      execution_devices=devices)
        _count("aot_cache_hits_total",
               "compiles skipped by loading a serialized AOT executable")
        return loaded
    except Exception as e:
        _count("aot_cache_errors_total",
               "AOT executable cache operations that failed "
               "(non-fatal; the executor compiles instead)", op="load")
        _warn_once("load",
                   "aot cache load failed for %s (%s: %s) — compiling "
                   "instead", path, type(e).__name__, e)
        return None


def save_executable(cache_dir: str, key: str, compiled, devices) -> bool:
    """Serialize ``compiled`` (built for ``devices``) under ``key`` (atomic
    publish). Returns whether the entry was written; failures warn once
    and return False — a replica that cannot persist executables still
    serves."""
    try:
        from jax.experimental.serialize_executable import (
            deserialize_and_load, serialize)

        payload, in_tree, out_tree = serialize(compiled)
        # validate BEFORE publishing: an executable that itself came out
        # of jax's persistent compilation cache serializes to a blob
        # that cannot load back ("Symbols not found" on XLA:CPU) —
        # publishing it would poison every future warm start.
        # One deserialize costs milliseconds against the seconds the
        # entry saves; an unloadable blob is simply never published.
        try:
            deserialize_and_load(payload, in_tree, out_tree,
                                 backend=devices[0].client,
                                 execution_devices=devices)
        except Exception as e:
            _count("aot_cache_errors_total",
                   "AOT executable cache operations that failed "
                   "(non-fatal; the executor compiles instead)",
                   op="validate")
            _warn_once("validate",
                       "aot cache: freshly serialized executable does "
                       "not load back (%s: %s) — not publishing it "
                       "(typical cause: the compile was served from "
                       "jax's own persistent compilation cache)",
                       type(e).__name__, e)
            return False
        fw, jx = _versions()
        blob = {"schema": _SCHEMA, "framework": fw, "jax": jx,
                "backend": devices[0].platform, "payload": payload,
                "in_tree": in_tree, "out_tree": out_tree}
        os.makedirs(cache_dir, exist_ok=True)
        path = _path(cache_dir, key)
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(blob, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        _count("aot_cache_saves_total",
               "AOT executables serialized into the warm-start cache")
        return True
    except Exception as e:
        _count("aot_cache_errors_total",
               "AOT executable cache operations that failed "
               "(non-fatal; the executor compiles instead)", op="save")
        _warn_once("save",
                   "aot cache save failed under %s (%s: %s) — executable "
                   "stays in-memory only", cache_dir, type(e).__name__, e)
        return False


def cache_stats() -> dict:
    """Monitor-counter snapshot for reports (replica startup lines,
    ci_fleet_report.json)."""
    from . import monitor

    return {
        "hits": monitor.metric_value("aot_cache_hits_total", 0.0),
        "misses": monitor.metric_value("aot_cache_misses_total", 0.0),
        "saves": monitor.metric_value("aot_cache_saves_total", 0.0),
        "errors": sum(
            monitor.metric_value("aot_cache_errors_total", 0.0, op=op)
            for op in ("load", "save", "validate")),
    }
