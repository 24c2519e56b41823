"""Type system for the TPU-native framework.

Plays the role of the reference's ``framework.proto`` VarType/DataType enums
(reference: paddle/fluid/framework/framework.proto:105-160) but maps directly
onto numpy/jax dtypes instead of a protobuf enum.
"""
from __future__ import annotations

import enum

import numpy as np


class VarType(enum.Enum):
    """Variable kinds (reference framework.proto:105 ``VarType.Type``)."""

    LOD_TENSOR = "lod_tensor"
    SELECTED_ROWS = "selected_rows"
    LOD_TENSOR_ARRAY = "lod_tensor_array"
    STEP_SCOPES = "step_scopes"
    READER = "reader"
    RAW = "raw"


# Canonical dtype strings. We use numpy-style names everywhere; bf16 is
# first-class because it is the native TPU matmul type.
_CANONICAL = {
    "float32": "float32",
    "float64": "float64",
    "float16": "float16",
    "bfloat16": "bfloat16",
    "int8": "int8",
    "uint8": "uint8",
    "int16": "int16",
    "uint16": "uint16",
    "int32": "int32",
    "uint32": "uint32",
    "int64": "int64",
    "uint64": "uint64",
    "bool": "bool",
    # aliases
    "fp32": "float32",
    "fp64": "float64",
    "fp16": "float16",
    "bf16": "bfloat16",
    "float": "float32",
    "double": "float64",
    "int": "int32",
    "long": "int64",
}


def canonical_dtype(dtype) -> str:
    """Normalise a user-provided dtype (str / np.dtype / jnp dtype) to a
    canonical string name."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        name = dtype.lower()
        if name in _CANONICAL:
            return _CANONICAL[name]
        raise ValueError(f"unknown dtype string: {dtype!r}")
    # handle jax / numpy dtype-like objects (incl. ml_dtypes.bfloat16)
    name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    if name in _CANONICAL:
        return _CANONICAL[name]
    name = str(dtype)
    if name in _CANONICAL:
        return _CANONICAL[name]
    raise ValueError(f"unknown dtype: {dtype!r}")


def np_dtype(dtype) -> np.dtype:
    name = canonical_dtype(dtype)
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


# what a 64-bit dtype request degrades to when jax runs with x64 disabled
_X64_FALLBACK = {"int64": "int32", "uint64": "uint32", "float64": "float32"}


def _x64_active() -> bool:
    """Whether jax delivers 64-bit dtypes. Read from the config, never by
    running a computation: this is called while a ``Program`` is being
    BUILT, and touching a backend there would make a parent process that
    only builds programs take the chip from the child that runs them."""
    import jax

    return bool(jax.config.jax_enable_x64)


def jnp_dtype(dtype) -> np.dtype:
    """``np_dtype`` for dtypes handed to jax constructors (jnp.full,
    jax.random.*, jnp.arange, ``Array.astype``...): with ``jax_enable_x64``
    off, explicitly requesting int64/float64 makes every call site emit a
    truncation warning before silently downcasting — spamming bench output
    once per traced op. Canonicalize here instead: request exactly the type
    jax will deliver anyway (``_x64_active``). Host-side numpy arrays
    (feeds, serialized attrs) keep full width via ``np_dtype``."""
    dt = np_dtype(dtype)
    if dt.name in _X64_FALLBACK and not _x64_active():
        return np.dtype(_X64_FALLBACK[dt.name])
    return dt


def is_floating(dtype) -> bool:
    return canonical_dtype(dtype) in ("float16", "float32", "float64", "bfloat16")


def is_integer(dtype) -> bool:
    return canonical_dtype(dtype) in ("int8", "uint8", "int16", "int32", "int64")
