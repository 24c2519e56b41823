"""Sharding rules: map program vars onto a device mesh.

TPU-native replacement for the reference's multi-device graph builders
(ir/multi_devices_graph_pass/) and BuildStrategy reduce strategies: instead of
rewriting the graph with per-grad AllReduce handles, we attach a
PartitionSpec to each var and jit once — XLA GSPMD partitions the whole step
and places the collectives (grad all-reduce over 'dp', activation collectives
over 'tp') on ICI.

``ShardingRules`` is name-pattern based so model code stays sharding-agnostic
(the reference reached the same decoupling via transpiler passes).
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Dict[str, int], devices=None) -> Mesh:
    """mesh({'dp': 2, 'tp': 4}) over the first prod(shape) devices.
    Axis order follows dict order; put the fastest-varying (intra-chip ICI
    neighbour) axis last — that is where tp belongs."""
    devices = list(devices if devices is not None else jax.devices())
    n = int(np.prod(list(shape.values())))
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(tuple(shape.values()))
    return Mesh(arr, axis_names=tuple(shape.keys()))


class ShardingRules:
    """Ordered (regex, PartitionSpec) rules for params + batch axis for feeds."""

    def __init__(self, param_rules: Sequence[Tuple[str, P]] = (),
                 feed_spec: P = P("dp"), default: P = P()):
        self.param_rules = [(re.compile(pat), spec) for pat, spec in param_rules]
        self.feed_spec = feed_spec
        self.default = default

    def spec_for_param(self, name: str, shape=None) -> P:
        for pat, spec in self.param_rules:
            if pat.search(name):
                return spec
        return self.default

    def sharding_for_param(self, mesh: Mesh, name: str, shape=None):
        # pipeline-stacked params (layers.PipelineRegion) always place one
        # stage slice per 'pp' rank — their leading dim IS the stage axis.
        # This also covers their optimizer accumulators, whose names embed
        # the param name.
        if ".pp_stacked" in name and "pp" in mesh.axis_names:
            return NamedSharding(mesh, P("pp"))
        return NamedSharding(mesh, self.spec_for_param(name, shape))

    def sharding_for_feed(self, mesh: Mesh):
        return NamedSharding(mesh, self.feed_spec)


# Megatron-style tensor-parallel rules for the BERT/transformer family:
# column-parallel QKV/FFN-in (shard output dim), row-parallel out/FFN-out
# (shard input dim), vocab-sharded embedding. Everything else replicated.
def transformer_tp_rules() -> ShardingRules:
    return ShardingRules(param_rules=[
        (r"_(q|k|v|ffn1)_w$", P(None, "tp")),
        (r"_(q|k|v|ffn1)_b$", P("tp")),
        (r"_(out|ffn2)_w$", P("tp", None)),
        (r"word_embedding$", P("tp", None)),
    ], feed_spec=P("dp"))


def compile_sharded_step(program, mesh: Mesh, feed_names: Sequence[str],
                         fetch_names: Sequence[str],
                         rules: Optional[ShardingRules] = None,
                         donate: bool = True):
    """Jit the program's global block over ``mesh`` with rule-derived
    in/out shardings. Returns (jitted_fn, io) where io describes arg order
    (see executor.analyze_block_io)."""
    from ..executor import analyze_block_io, make_step_fn
    from ..flags import flag

    rules = rules or ShardingRules()
    block = program.global_block
    io = analyze_block_io(block, set(feed_names), fetch_names)
    nan_meta = [] if flag("check_nan_inf") else None
    step_fn = make_step_fn(block, io, fetch_names, mesh=mesh,
                           nan_check_meta=nan_meta)

    def state_shard(name):
        return rules.sharding_for_param(mesh, name)

    feed_shard = rules.sharding_for_feed(mesh)
    in_shardings = (
        [feed_shard] * len(io["feed_order"]),
        [state_shard(n) for n in io["donated"]],
        [state_shard(n) for n in io["ro"]],
        None,
    )
    # outputs: fetches replicated; state keeps its input sharding
    out_shardings = (
        [NamedSharding(mesh, P())] * len(fetch_names),
        [state_shard(n) for n in io["state_out"]],
    )
    if nan_meta is not None:
        out_shardings = out_shardings + (NamedSharding(mesh, P()),)
    jitted = jax.jit(step_fn, in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=(1,) if donate else ())
    io["nan_check_meta"] = nan_meta
    return jitted, io


# ---------------------------------------------------------------------------
# static spec extraction (consumed by analysis.sharding_check and shared
# with CompiledProgram._compile so the static layout IS the runtime layout)
# ---------------------------------------------------------------------------

def zero1_spec_for(v, dp: int, zero1: bool) -> tuple:
    """Pure-metadata twin of CompiledProgram's ``state_sharding`` rule:
    the PartitionSpec-like tuple (one axis name or None per dim) a state
    var gets on a dp mesh. ``()`` = replicated. Sharded embedding tables
    (``is_distributed``) row-shard regardless of the reduce strategy;
    optimizer-state vars row-shard under ZeRO-1
    (``BuildStrategy.ReduceStrategy.Reduce``)."""
    if dp <= 1:
        return ()
    if v is None or not v.shape or len(v.shape) < 1 \
            or v.shape[0] < dp or v.shape[0] % dp:
        return ()
    if getattr(v, "is_distributed", False):
        return ("dp",)
    if zero1 and getattr(v, "is_optimizer_state", False):
        return ("dp",)
    return ()


def extract_param_specs(program, mesh_shape: Dict[str, int],
                        build_strategy=None, zero: bool = False,
                        rules: Optional[ShardingRules] = None
                        ) -> Tuple[Dict[str, tuple], tuple]:
    """Derive the per-param spec assignment a ``BuildStrategy`` implies,
    as plain metadata (no devices touched): the input to
    ``analysis.sharding_check`` and ``Program.memory_plan(mesh=...)``.

    Returns ``(param_specs, feed_spec)`` — ``param_specs`` maps var name
    to a spec tuple (only sharded vars listed), ``feed_spec`` is the
    batch-axis spec for feeds. ``zero=True`` (or a build_strategy with
    ``ReduceStrategy.Reduce``) applies the ZeRO-1 optimizer-state layout;
    ``rules`` layers name-pattern tensor-parallel specs on top (the
    ``ShardingRules`` the tp path uses)."""
    dp = int(mesh_shape.get("dp", 1))
    if build_strategy is not None:
        zero = zero or getattr(build_strategy, "reduce_strategy", 0) == 1
    specs: Dict[str, tuple] = {}
    for blk in program.blocks:
        for v in blk.vars.values():
            if not v.persistable or v.is_data:
                continue
            spec: tuple = ()
            if rules is not None:
                p = rules.spec_for_param(v.name, v.shape)
                spec = tuple(p) if tuple(p) else ()
                if ".pp_stacked" in v.name and "pp" in mesh_shape:
                    spec = ("pp",)
            if not any(a is not None for a in spec):
                spec = zero1_spec_for(v, dp, zero)
            if any(a is not None for a in spec):
                specs[v.name] = spec
    feed_spec = ("dp",) if dp > 1 else ()
    return specs, feed_spec


def place_state(scope_values: Dict[str, "jax.Array"], mesh: Mesh,
                rules: ShardingRules) -> Dict[str, "jax.Array"]:
    """Device_put scope state onto the mesh per rules (param broadcast —
    the reference's BCastParamsToDevices, parallel_executor.cc:503)."""
    placed = {}
    for name, v in scope_values.items():
        placed[name] = jax.device_put(v, rules.sharding_for_param(mesh, name))
    return placed
