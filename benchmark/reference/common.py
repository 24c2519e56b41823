"""What the plain references share: seeded weights, rounding to a lower
precision for the controls, layer norm, exact gelu.

Nothing here imports the program. Weights are made from the seed on the
device in ONE jitted call, as f32; the runner plants the same arrays in the
program's scope, so program and reference start from equal numbers that
neither made for the other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x3FFFFFFF)
    return jax.random.fold_in(key, (seed >> 30) & 0x3FFFFFFF)


def make_weights(spec: dict, seed: int) -> dict:
    """``spec``: name -> (shape, kind) with kind 'normal:<std>', 'ones' or
    'zeros'. One jitted call; names are folded in by sorted position so a
    weight does not depend on the dict's order."""
    names = sorted(spec)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = spec[name]
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                std = float(kind.split(":", 1)[1])
                z = jax.random.truncated_normal(
                    jax.random.fold_in(key, i), -2.0, 2.0, shape,
                    jnp.float32)
                out[name] = z * std
        return out

    return jax.jit(build)(seed_key(seed))


def rounder(precision: str):
    """The matmul-operand rounding of a stated precision. 'f32' leaves the
    operand alone (with HIGHEST that is a true f32 product); 'bf16' and
    'fp8' round it to that type and back, which is what computing the
    product in that type does to its inputs (accumulation stays f32, as on
    the MXU)."""
    if precision == "f32":
        return lambda a: a
    dt = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    # straight through: the forward value is rounded, the gradient passes
    # as if it were not (a cotangent pushed through fp8 would underflow to
    # zero, which is a broken step and not a lower precision)
    return lambda a: a + jax.lax.stop_gradient(
        a.astype(dt).astype(jnp.float32) - a)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
