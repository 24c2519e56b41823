"""The ``serve_stored`` runner: ``runners.serve`` for a configuration
whose weights do not fit the chip in f32. Window, clients, sampler, burst
close, the reference comparison and the four checks are ``runners.serve``'s
own, by import. Two things differ: a seed's weights are made in the
configuration's storage type, a tensor at a time, by the maker kept with
the configuration's reference (``reference.<family>.make_weights``) and
planted as they come, so that the startup program's tensor is freed before
the next is made; and the model's sizes are read from the file's top level
(the model's published ``config.json`` keys) through
``reference.<family>.model_config``. One check is added: the expert op
dropped no assignment. And one line of free text: how many prefill and
decode dispatches the run made from the end of set-up on, and their mean
wall, so that two runs whose rates differ can be told apart by their own
output (the device's work against the host's gaps).
"""
from __future__ import annotations

import importlib

import harness
import traffic as traffic_mod
from runners import serve as base


class Session(base.Session):
    def load(self, seed, mix) -> None:
        """``runners.serve.Session.load`` with the weights made and
        planted one at a time."""
        self.seed = seed
        self.weights = {}
        for name, w in self.reference.make_weights(self.spec, seed):
            harness.plant_weights(self.scope, {name: w})
            self.weights[name] = w
        self.eng = self.family.engine(self.cfg, self.net, self.scope,
                                      self.exe)
        self.n_exec = self.eng.warm_up()
        self.eng.start()
        warm = traffic_mod.warm_requests(mix, seed, self.vocab)
        futs = [base._submit(self.eng, rec) for rec in warm]
        for rec, fut in zip(warm, futs):
            if fut is None:
                raise harness.BenchmarkError(
                    f"warm request refused: {rec.error}")
            fut.result(timeout=600)
        self.n_warm = len(warm)


def run(cell, chips, args, t_process, broken=None):
    reference = importlib.import_module(
        f"reference.{cell.config['family']}")
    cell.config["model"] = reference.model_config(cell.config)
    at_ready = {}

    def ready(session):             # runs once set-up is over
        at_ready.update(harness.counters())
        if broken:
            broken(session)

    # runners.serve.run builds its session by the module's name for it
    theirs, base.Session = base.Session, Session
    try:
        result = base.run(cell, chips, args, t_process, broken=ready)
    finally:
        base.Session = theirs
    total = harness.counters()
    since = harness.counter_delta(at_ready, total)
    for path, what in (("run", "prefill"), ("chained", "decode")):
        n = harness.sum_matching(since, "executor_step_seconds_count",
                                 path=path)
        wall = harness.sum_matching(since, "executor_step_seconds_sum",
                                    path=path)
        harness.say(f"{what} dispatches since set-up: {n:.0f}, mean "
                    f"{1e3 * wall / max(n, 1):.1f} ms, {wall:.2f} s in all")
    dropped = harness.sum_matching(total, "moe_dropped_assignments_total")
    result["checks"].append(
        {"name": "moe_dropped_assignments", "value": dropped, "limit": 0,
         "rule": "==", "ok": dropped == 0})
    return result
