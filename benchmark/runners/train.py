"""The ``train`` runner: one training step in the plain loop users write.

Set-up builds ONE object (the program, its executor and scope) and plants
weights made from the seed; drives it through its first ``check.steps``
steps by the window's own call and feed, reading each loss, the first
gradient's norm by leaf (from Adam's first moment after one step) and the
norm of every leaf's change after the steps; then hands the same object to
the window. Each step feeds host numpy from a pool of seeded batches in
rotation and ends in the loss fetch, which is the sync.

After the window the program's state is freed and the plain reference
follows the same steps from the same weights and feeds; the numbers are
compared, each against its own limit.
"""
from __future__ import annotations

import importlib
import math
import statistics
import time

import numpy as np

import harness
from harness import say


def worst_leaf_gap(prog: dict, ref: dict, what: str = "") -> float:
    """Largest gap over the leaves between the program's norm and the
    reference's (the gap between norms, not the norm of a difference),
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: some leaves' norms are all but zero."""
    floor = statistics.median(ref.values())
    gap, leaf = max((abs(prog[k] - ref[k]) / max(ref[k], floor), k)
                    for k in ref)
    if what:
        say(f"{what}: worst leaf {leaf} (program {prog[leaf]:.6g}, "
            f"reference {ref[leaf]:.6g}, median leaf {floor:.6g})")
    return gap


def direction_gap(prog: dict, ref: dict, ref_norm: dict) -> float:
    """Largest ``1 - cosine`` between the program's first gradient and the
    reference's, over the leaves whose reference norm is at least the
    median leaf's (a leaf whose true gradient is all but zero, such as the
    key bias, has no direction to compare). Rounding noise that leaves a
    norm alone turns the direction: this is the number that tells a lower
    precision from the stated one (PERF.md section 2)."""
    import jax
    import jax.numpy as jnp

    floor = statistics.median(ref_norm.values())
    leaves = [k for k in ref_norm if ref_norm[k] >= floor]

    @jax.jit
    def one_minus_cos(a, b):
        out = {}
        for k in leaves:
            x = jnp.asarray(a[k], jnp.float32).reshape(-1)
            y = b[k].reshape(-1)
            out[k] = 1.0 - jnp.vdot(x, y) / (
                jnp.linalg.norm(x) * jnp.linalg.norm(y))
        return out

    got = {k: float(v) for k, v in one_minus_cos(
        {k: prog[k] for k in leaves}, {k: ref[k] for k in leaves}).items()}
    gap, leaf = max((v, k) for k, v in got.items())
    say(f"first gradient direction: worst leaf {leaf} (1 - cos {gap:.3g}) "
        f"over {len(leaves)} leaves at or above the median norm")
    return gap


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers compared for a training cell, each with its limit."""
    gaps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    # every later step has a limit of its own: Adam's second update already
    # divides by a moment, and the loss after it swings from seed to seed
    # ten times as far as the loss before it
    later = limits["later_loss_gap"]
    dir_gap = direction_gap(prog["first_grad"], ref["first_grad"],
                            ref["grad_norm"])
    grad_gap = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"],
                              "first gradient norm")
    delta_gap = worst_leaf_gap(prog["delta_norm"], ref["delta_norm"],
                               "parameter change norm")
    say(f"losses program {prog['losses']} reference {ref['losses']}")

    def held(name, value, limit):
        return {"name": name, "value": value, "limit": limit, "rule": "<=",
                "ok": value <= limit}

    return [held("first_loss_gap", gaps[0], limits["first_loss_gap"])] + [
        held(f"loss_gap_step{k}", gap, lim)
        for k, (gap, lim) in enumerate(zip(gaps[1:], later, strict=True), 2)
    ] + [
        held("first_grad_direction_gap_worst_leaf", dir_gap,
             limits["grad_direction_gap"]),
        held("first_grad_norm_gap_worst_leaf", grad_gap,
             limits["grad_norm_gap"]),
        held("param_change_norm_gap_worst_leaf", delta_gap,
             limits["delta_norm_gap"]),
    ]


class Session:
    """The one object: program, executor, scope, and the step that both the
    check and the window call. Built once; ``load`` plants a seed's weights
    and feeds (and resets the optimizer's state), so a calibration can
    read many seeds in one process."""

    def __init__(self, cell, chips):
        import paddle_tpu as fluid

        self.cell, self.chips = cell, chips
        self.cfg, self.job = cell.config, cell.traffic
        cfg = self.cfg
        self.family = importlib.import_module(f"families.{cfg['family']}")
        self.reference = importlib.import_module(f"reference.{cfg['family']}")
        self.spec = self.reference.param_spec(cfg["model"])
        self.model = self.family.build(cfg)
        place = fluid.CPUPlace() if cell.rehearse else fluid.TPUPlace()
        self.exe, self.scope = fluid.Executor(place), fluid.Scope()
        harness.check_parameter_names(self.model["main"], self.spec)
        dp = int(cfg.get("data_parallel", 1))
        self.program = self.model["main"]
        if dp > 1:
            self.program = fluid.CompiledProgram(
                self.model["main"]).with_data_parallel(
                loss_name=self.model["loss"].name,
                places=chips["devices"][:dp])
        self.tokens = self.family.tokens_per_step(cfg, self.job)
        self.feeds = None

    def weights(self, seed):
        from reference.common import make_weights

        return make_weights(self.spec, seed)

    def plant(self, seed) -> None:
        harness.plant_weights(self.scope, self.weights(seed))

    def load(self, seed) -> None:
        """Fresh optimizer state (the startup program), the seed's weights
        in place of the startup's own, the seed's pool of feeds."""
        self.seed = seed
        self.exe.run(self.model["startup"], scope=self.scope)
        self.plant(seed)
        self.feeds = self.family.make_batches(self.cfg, self.job, seed)

    def step(self, i) -> float:
        (loss,) = self.exe.run(self.program,
                               feed=self.feeds[i % len(self.feeds)],
                               fetch_list=[self.model["loss"]],
                               scope=self.scope)
        return float(np.asarray(loss).reshape(-1)[0])

    def first_steps(self, n) -> dict:
        """Drive the first ``n`` steps through ``step`` and read what the
        reference will be asked for."""
        import jax
        import jax.numpy as jnp

        beta1 = self.reference.ADAM["beta1"]
        norms = jax.jit(lambda d: {k: jnp.linalg.norm(
            v.astype(jnp.float32)) for k, v in d.items()})
        prog = {"losses": []}
        for i in range(n):
            prog["losses"].append(self.step(i))
            if i == 0:
                m1 = {k: self.scope.find_var(f"moment1_{k}_0")
                      for k in self.spec}
                prog["grad_norm"] = {k: float(v) / (1.0 - beta1)
                                     for k, v in norms(m1).items()}
                # the gradient itself waits on the host for the reference,
                # so that the device's memory stays the program's
                prog["first_grad"] = {
                    k: np.asarray(v, np.float32) / (1.0 - beta1)
                    for k, v in m1.items()}
                del m1
        diff = jax.jit(lambda a, b: {k: jnp.linalg.norm(
            jnp.asarray(a[k], jnp.float32).reshape(b[k].shape) - b[k])
            for k in b})
        prog["delta_norm"] = {k: float(v) for k, v in diff(
            {k: self.scope.find_var(k) for k in self.spec},
            self.weights(self.seed)).items()}
        return prog

    def follow(self, n, precision="f32") -> dict:
        """The plain reference over the same first ``n`` steps."""
        chk = self.cfg["check"]
        return self.reference.follow(
            self.weights(self.seed), self.feeds[:n], self.cfg["model"],
            self.cfg["learning_rate"], precision=precision,
            row_block=int(chk["row_block"]))

    def free(self) -> None:
        for name in list(self.scope.vars):
            self.scope.drop_var(name)


def run(cell, chips, args, t_process, broken=None):
    import paddle_tpu as fluid
    from paddle_tpu import trace as program_trace

    trace = harness.TraceWindow(bool(args.trace), args.seconds, cell.name)
    if trace.on:
        fluid.set_flags({"FLAGS_trace_buffer_size": 2_000_000})
    trace.enable_spans()

    # -- set-up: one object, driven through its first steps ----------------
    t0 = time.perf_counter()
    s = Session(cell, chips)
    chk, n_check = s.cfg["check"], int(s.cfg["check"]["steps"])
    t1 = time.perf_counter()
    s.load(args.seed)
    if broken:
        broken(s)
    t2 = time.perf_counter()
    prog = s.first_steps(n_check)
    program_trace.clear()
    before = harness.counters()
    setup_s = time.perf_counter() - t_process
    say(f"set-up {setup_s:.1f} s: imports and chip {t0 - t_process:.1f}, "
        f"program built {t1 - t0:.1f}, startup and seeded weights "
        f"{t2 - t1:.1f}, first {n_check} steps (compile or cache load "
        f"included) {time.perf_counter() - t2:.1f}")

    # -- the window -------------------------------------------------------------
    losses, t_open, out = [], time.perf_counter(), 0.0
    t_end, i = t_open + float(args.seconds), n_check
    while True:
        now = time.perf_counter()
        if now - out >= t_end:          # seconds of work, profiler apart
            break
        out += trace.poll(now - t_open)      # profiler start/stop: not work
        losses.append(s.step(i))
        i += 1
    elapsed = time.perf_counter() - t_open - out
    trace.stop()
    after = harness.counters()
    mem_peak = harness.memory_peak_bytes(chips["devices"])
    e2e = {"setup_s": setup_s,
           "train_tokens_per_s": len(losses) * s.tokens / elapsed}
    say(f"window {elapsed:.3f} s: {len(losses)} steps of {s.tokens} tokens; "
        f"loss first {losses[0]:.4f} last {losses[-1]:.4f}; mean of the "
        f"first 8 {np.mean(losses[:8]):.4f}, of the last 8 "
        f"{np.mean(losses[-8:]):.4f}")

    # -- correct: the reference follows, once the program's state is freed ---------
    delta = harness.counter_delta(before, after)
    recompiles = int(delta["recompiles_total{}"]
                     + harness.sum_matching(delta, "executor_compiles_total"))
    s.free()
    t0 = time.perf_counter()
    ref = s.follow(n_check)
    say(f"reference: {n_check} steps in {time.perf_counter() - t0:.1f} s "
        f"(not in setup_s)")
    checks = compare(prog, ref, chk["limits"])
    finite = all(math.isfinite(v) for v in losses + prog["losses"])
    checks += [
        {"name": "losses_not_finite", "value": 0 if finite else 1,
         "limit": 0, "rule": "==", "ok": finite},
        {"name": "compilations_in_window", "value": recompiles, "limit": 0,
         "rule": "==", "ok": recompiles == 0},
    ]
    result = {"checks": checks, "attempted": len(losses), "failed": 0,
              "memory_peak_bytes": mem_peak, "metrics": dict(e2e)}
    if trace.on:
        harness.finish_traced(cell, chips, trace, result, counters=delta,
                              counters_total=after, series={})
    return result
