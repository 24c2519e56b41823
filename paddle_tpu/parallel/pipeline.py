"""Pipeline parallelism over a `pp` mesh axis — the SectionWorker, TPU-native.

The reference pipelines by cutting the program into sections placed on
different devices and streaming scopes through blocking queues between
section-worker threads (reference: python/paddle/fluid/optimizer.py:2781
PipelineOptimizer, paddle/fluid/framework/trainer.h:110 PipelineTrainer,
device_worker.h:267 SectionWorker). The TPU-native equivalent keeps the
same schedule — GPipe microbatches flowing through stages — but expresses
it as ONE SPMD program: each pp rank holds one stage's parameters (a
[P, ...]-stacked param tree sharded over 'pp'), and the inter-section
queues become `lax.ppermute` of activations to the next rank each tick.
XLA lowers the ppermute to ICI collective-permute; the "queue" is the wire.

Schedule (GPipe, M microbatches, P stages, T = M + P - 1 ticks):

    tick t: rank s works on microbatch (t - s) when 0 <= t - s < M;
    rank 0 injects microbatch t; rank P-1 emits microbatch t - (P - 1).

All ranks execute the stage function every tick (idle ranks chew on
zeros — the SPMD pipelining bubble, cost P-1 of M+P-1 ticks, same as the
reference's warm-up/drain). The loop is a lax.scan, so the whole pipeline
— including backward, which reverses the permutes automatically under
jax.grad — is one compiled step.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_spmd", "pipeline", "stack_stage_params"]


def pipeline_spmd(stage_fn: Callable, stage_params, x_micro,
                  axis_name: str = "pp"):
    """Run the GPipe schedule inside shard_map over ``axis_name``.

    stage_fn: (params_leaf_tree, activation [B_mb, ...]) -> activation of
        the SAME shape/dtype (homogeneous stages — the repeated-block
        architecture every transformer has).
    stage_params: this rank's stage parameters — from a [P, ...]-stacked
        tree sharded over the axis, i.e. leaves arrive [1, ...]; a leading
        singleton dim is squeezed.
    x_micro: [M, B_mb, ...] microbatched input (replicated over the axis).

    Returns [M, B_mb, ...] outputs of the final stage, replicated.
    """
    P_ = jax.lax.axis_size(axis_name)
    s = jax.lax.axis_index(axis_name)
    M = x_micro.shape[0]
    T = M + P_ - 1
    params = jax.tree.map(
        lambda l: l[0] if (hasattr(l, "shape") and l.shape
                           and l.shape[0] == 1) else l, stage_params)

    # non-circular shift s -> s+1: rank 0 receives zeros
    perm = [(i, i + 1) for i in range(P_ - 1)]

    # the carry must be typed as VARYING over the pipeline axis (its value
    # depends on axis_index from tick 1 on), or the scan carry types clash
    carry0 = jax.tree.map(
        lambda t: jax.lax.pcast(t, (axis_name,), to="varying"),
        (jnp.zeros_like(x_micro[0]), jnp.zeros_like(x_micro)))

    def tick(carry, t):
        prev_act, out_buf = carry
        mb = t - s                                   # my microbatch index
        active = (mb >= 0) & (mb < M)
        inj = x_micro[jnp.clip(t, 0, M - 1)]
        inp = jnp.where(s == 0, inj, prev_act)
        y = stage_fn(params, inp)
        # zero inactive ranks' output so garbage never propagates and the
        # backward through idle ticks contributes exact zeros
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its finished microbatch
        emit = (s == P_ - 1) & active
        idx = jnp.clip(mb, 0, M - 1)
        out_buf = jnp.where(
            emit, jax.lax.dynamic_update_index_in_dim(
                out_buf, y.astype(out_buf.dtype), idx, 0), out_buf)
        nxt = jax.lax.ppermute(y, axis_name, perm)
        return (nxt, out_buf), None

    (_, out_buf), _ = jax.lax.scan(tick, carry0, jnp.arange(T))
    # only rank P-1 holds the real outputs; mask-psum replicates them
    return jax.lax.psum(
        jnp.where(s == P_ - 1, out_buf, jnp.zeros_like(out_buf)), axis_name)


def stack_stage_params(per_stage_params):
    """[tree_stage0, tree_stage1, ...] -> one tree with [P, ...] leaves
    (shard the leading dim over 'pp' to place each stage on its rank)."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *per_stage_params)


def pipeline(stage_fn: Callable, stacked_params, x, mesh: Mesh,
             num_microbatches: int, axis_name: str = "pp",
             batch_axis: str = "dp", place_params: bool = True):
    """Whole-array wrapper: shard_map the GPipe schedule over ``mesh``.

    stacked_params: tree with leading [P] dim on every leaf (see
    stack_stage_params); sharded over ``axis_name``.
    x: [B, ...] batch (sharded over ``batch_axis`` when the mesh has it).
    ``place_params=False`` skips the eager device_put (required when called
    from inside a jit trace, where shardings come from the caller).
    Returns [B, ...] final-stage outputs with x's sharding.
    """
    M = int(num_microbatches)
    B = x.shape[0]
    n_stages = {l.shape[0] for l in jax.tree.leaves(stacked_params)}
    if len(n_stages) != 1:
        raise ValueError(
            f"stacked param leaves disagree on stage count: {n_stages}")
    (n_stages,) = n_stages
    if mesh.shape[axis_name] != n_stages:
        raise ValueError(
            f"mesh '{axis_name}' axis has {mesh.shape[axis_name]} ranks "
            f"but the stacked params carry {n_stages} stages — they must "
            f"match (one stage per rank)")
    has_dp = batch_axis is not None and batch_axis in mesh.axis_names
    local_b = B // mesh.shape[batch_axis] if has_dp else B
    if local_b % M:
        raise ValueError(
            f"per-{batch_axis + '-rank ' if has_dp else ''}batch {local_b} "
            f"not divisible by num_microbatches {M}")
    xspec = P(batch_axis if has_dp else None, *([None] * (x.ndim - 1)))
    pspec = jax.tree.map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stacked_params)

    def local(params, xl):
        xm = xl.reshape((M, xl.shape[0] // M) + xl.shape[1:])
        ym = pipeline_spmd(stage_fn, params, xm, axis_name)
        return ym.reshape(xl.shape)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec)
    if place_params and _needs_place(stacked_params, mesh):
        stacked_params = jax.device_put(
            stacked_params,
            jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspec))
    # the GPipe schedule is T = M + P - 1 collective-permutes around the
    # pp ring; a wedged stage rank stalls every other rank's ppermute
    # forever. Armed like the executor step sections: dump + raise under
    # FLAGS_step_timeout_s instead of hanging (a jit-trace caller only
    # wraps host-side tracing and disarms immediately).
    from ..resilience.distributed import (block_until_ready_concrete,
                                          watchdog_section)

    from ..resilience.elastic import device_loss_classification

    # a dead pp-ring rank surfaces here as an untyped runtime error —
    # the shared wrapper classifies it typed so the elastic path can act
    with watchdog_section("collective",
                          detail=f"pipeline over '{axis_name}' "
                                 f"({num_microbatches} microbatches)") \
            as tok, device_loss_classification("collective"):
        out = fn(stacked_params, x)
        if tok is not None:
            # async dispatch: arm through device completion (no-op when
            # called inside a jit trace; real runtime errors propagate)
            block_until_ready_concrete(out)
        return out


def _needs_place(tree, mesh) -> bool:
    """True when leaves are plain (uncommitted) arrays: device_put them
    onto the mesh so shard_map sees the intended stage placement."""
    for leaf in jax.tree.leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if sh is None or getattr(sh, "mesh", None) is not mesh:
            return True
    return False
