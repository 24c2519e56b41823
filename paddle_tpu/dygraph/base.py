"""Dygraph (eager) core: VarBase, the tape, guard, to_variable.

Reference: paddle/fluid/imperative/ — `VarBase` eager tensors with grad
twins (layer.h:55), `Tracer::TraceOp` running each kernel immediately while
wiring an autograd graph (tracer.h:39), and `BasicEngine` doing a reverse
dep-counted sweep on backward (engine.h:69).

TPU-native redesign: ops execute eagerly through the SAME registry lowering
rules the compiled path uses (one source of truth for op semantics), and the
tape records (opdef, input uids, attrs, output uids). ``backward()`` replays
the tape as a pure function of the leaf values under ``jax.grad`` — JAX is
the BasicEngine, the replay is the autograd graph, and the whole backward
can be jitted. RNG ops replay bit-identically because each entry's PRNG key
is derived from its tape position.
"""
from __future__ import annotations

import contextlib
import itertools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import registry
from ..lowering import LowerCtx, eager_platform

__all__ = ["VarBase", "guard", "to_variable", "enabled", "in_dygraph_mode",
           "current_tape"]

_uid = itertools.count(1)
_tape: Optional["Tape"] = None


def in_dygraph_mode() -> bool:
    return _tape is not None


enabled = in_dygraph_mode


def current_tape() -> "Tape":
    if _tape is None:
        raise RuntimeError(
            "not in dygraph mode — wrap eager code in fluid.dygraph.guard()")
    return _tape


@contextlib.contextmanager
def guard(place=None, seed: int = 0):
    """reference dygraph/base.py:89 — enables eager execution inside."""
    global _tape
    old, _tape = _tape, Tape(seed=seed)
    try:
        yield
    finally:
        _tape = old


class VarBase:
    """Eager tensor (reference imperative/layer.h:55). Wraps a jax array;
    ``_grad`` is the grad twin, filled by backward()."""

    def __init__(self, value, name: Optional[str] = None,
                 stop_gradient: bool = False, persistable: bool = False):
        self.value = jnp.asarray(value)
        self.uid = next(_uid)
        self.name = name or f"eager_tmp_{self.uid}"
        self.stop_gradient = stop_gradient
        self.persistable = persistable
        self._grad: Optional[jax.Array] = None

    # -- reference VarBase surface ---------------------------------------
    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    def numpy(self) -> np.ndarray:
        return np.asarray(self.value)

    def set_value(self, v) -> None:
        self.value = jnp.asarray(v)

    def detach(self) -> "VarBase":
        return VarBase(self.value, name=self.name + ".detached",
                       stop_gradient=True)

    def backward(self, retain_graph: bool = False) -> None:
        current_tape().backward(self, retain_graph=retain_graph)

    def gradient(self) -> Optional[np.ndarray]:
        return None if self._grad is None else np.asarray(self._grad)

    def clear_gradient(self) -> None:
        self._grad = None

    def astype(self, dtype):
        from . import ops

        return ops.cast(self, in_dtype=str(self.value.dtype),
                        out_dtype=dtype)

    def __repr__(self):
        return f"VarBase(name={self.name}, shape={self.shape}, " \
               f"dtype={self.dtype})"

    # -- arithmetic (reference math_op_patch for VarBase) ----------------
    def _binary(self, other, op, reverse=False):
        from . import ops

        if not isinstance(other, VarBase):
            # keep numpy/jnp promotion semantics (a float scalar promotes an
            # int tensor; forcing self.dtype would truncate it)
            other = VarBase(jnp.asarray(other), stop_gradient=True)
        a, b = (other, self) if reverse else (self, other)
        return getattr(ops, op)(a, b)

    def __add__(self, o):
        return self._binary(o, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "elementwise_sub")

    def __rsub__(self, o):
        return self._binary(o, "elementwise_sub", reverse=True)

    def __mul__(self, o):
        return self._binary(o, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "elementwise_div")

    def __rtruediv__(self, o):
        return self._binary(o, "elementwise_div", reverse=True)

    def __matmul__(self, o):
        return self._binary(o, "matmul")

    def __rmatmul__(self, o):
        return self._binary(o, "matmul", reverse=True)

    def __neg__(self):
        from . import ops

        return ops.scale(self, scale=-1.0)


class _TapeEntry:
    __slots__ = ("opdef", "ins", "attrs", "outs", "pos")

    def __init__(self, opdef, ins, attrs, outs, pos):
        self.opdef = opdef
        self.ins = ins      # {slot: [uid or None]}
        self.attrs = attrs
        self.outs = outs    # {slot: [uid]}
        self.pos = pos


class Tape:
    def __init__(self, seed: int = 0):
        self.entries: List[_TapeEntry] = []
        self.const_values: Dict[int, Any] = {}   # leaf/const uid -> value
        self.leaves: Dict[int, VarBase] = {}     # uid -> VarBase (leaf refs)
        self.produced: set = set()
        self.base_key = jax.random.key(seed)

    # -- tracing ---------------------------------------------------------
    def record(self, op_type: str, ins: Dict[str, List[Optional[VarBase]]],
               attrs: Dict[str, Any]) -> Dict[str, List[VarBase]]:
        """Execute one op eagerly and record it (Tracer::TraceOp)."""
        opdef = registry.get_op_def(op_type)
        if opdef.raw:
            raise RuntimeError(
                f"op '{op_type}' is a graph control-flow op; in dygraph "
                f"mode use ordinary Python control flow instead")
        full_attrs = {name: spec.default for name, spec in opdef.attrs.items()}
        full_attrs.update(attrs)
        pos = len(self.entries)
        in_uids: Dict[str, List[Optional[int]]] = {}
        in_vals: Dict[str, List[Any]] = {}
        for slot, vbs in ins.items():
            uids, vals = [], []
            for vb in vbs:
                if vb is None:
                    uids.append(None)
                    vals.append(None)
                    continue
                uids.append(vb.uid)
                vals.append(vb.value)
                if vb.uid not in self.produced and \
                        vb.uid not in self.const_values:
                    self.const_values[vb.uid] = vb.value
                    self.leaves[vb.uid] = vb
            in_uids[slot] = uids
            in_vals[slot] = vals

        ctx = LowerCtx(base_key=self.base_key, uid=pos,
                       platform=eager_platform())
        outs = opdef.lower(ctx, in_vals, full_attrs) or {}
        out_vbs: Dict[str, List[VarBase]] = {}
        out_uids: Dict[str, List[int]] = {}
        for slot, vals in outs.items():
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            vbs, uids = [], []
            for v in vals:
                vb = VarBase(v) if v is not None else None
                vbs.append(vb)
                uids.append(vb.uid if vb else None)
                if vb:
                    self.produced.add(vb.uid)
            out_vbs[slot] = vbs
            out_uids[slot] = uids
        self.entries.append(
            _TapeEntry(opdef, in_uids, full_attrs, out_uids, pos))
        return out_vbs

    # -- autograd (reference BasicEngine::Execute) -----------------------
    def _replay(self, target_uid: int, leaf_uids: List[int],
                entries: Optional[List["_TapeEntry"]] = None):
        """Build the pure function leaf_values -> scalar(target)."""
        entries = self.entries if entries is None else entries
        const = self.const_values
        base_key = self.base_key
        platform = eager_platform()

        def fn(leaf_vals: List[Any]):
            env = dict(const)
            env.update(zip(leaf_uids, leaf_vals))
            for e in entries:
                ins = {slot: [env.get(u) if u is not None else None
                              for u in uids]
                       for slot, uids in e.ins.items()}
                ctx = LowerCtx(base_key=base_key, uid=e.pos,
                               platform=platform)
                outs = e.opdef.lower(ctx, ins, e.attrs) or {}
                for slot, vals in outs.items():
                    if not isinstance(vals, (list, tuple)):
                        vals = [vals]
                    for u, v in zip(e.outs.get(slot, []), vals):
                        if u is not None and v is not None:
                            env[u] = v
            return jnp.sum(env[target_uid])

        return fn

    def backward(self, loss: VarBase, retain_graph: bool = False) -> None:
        if loss.uid not in self.produced:
            raise RuntimeError(
                f"backward() target {loss.name} was not produced on this "
                f"tape (created outside dygraph ops?)")
        # backward slice: only entries reachable from the loss replay, and
        # only leaves those entries read — unrelated parameters keep
        # gradient()==None instead of silently receiving zeros (and AdamW
        # weight decay never touches them)
        needed = {loss.uid}
        live_entries = []
        for e in reversed(self.entries):
            if any(u in needed for uids in e.outs.values() for u in uids):
                live_entries.append(e)
                needed.update(u for uids in e.ins.values()
                              for u in uids if u is not None)
        live_entries.reverse()
        leaf_uids = [u for u, vb in self.leaves.items()
                     if u in needed and not vb.stop_gradient
                     and jnp.issubdtype(vb.value.dtype, jnp.inexact)]
        if not leaf_uids:
            raise RuntimeError("backward(): no differentiable leaves found")
        fn = self._replay(loss.uid, leaf_uids, live_entries)
        leaf_vals = [self.leaves[u].value for u in leaf_uids]
        grads = jax.grad(fn)(leaf_vals)
        for u, g in zip(leaf_uids, grads):
            vb = self.leaves[u]
            # accumulate like the reference GradientAccumulator
            vb._grad = g if vb._grad is None else vb._grad + g
        if not retain_graph:
            self.reset()

    def reset(self) -> None:
        """Drop everything recorded. Parameters re-register as leaves on
        the next forward; grad accumulation across steps still works
        because grads live on the VarBase objects themselves (_grad)."""
        self.entries.clear()
        self.const_values.clear()
        self.leaves.clear()
        self.produced.clear()


def to_variable(value, name=None, zero_copy=None) -> VarBase:
    """reference dygraph/base.py:151."""
    if isinstance(value, VarBase):
        return value
    arr = np.asarray(value)
    return VarBase(jnp.asarray(arr), name=name, stop_gradient=True)
