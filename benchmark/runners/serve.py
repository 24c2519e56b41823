"""The ``serve`` runner: one served decoder under one traffic mix.

Set-up: weights from the seed on the device (one jitted call), the
program's model through its own builder, the engine as an operator starts
it, ``warm_up()`` for its executables, then the mix's warm requests so
that every host path has run once. The window then offers the mix's load
from this one process: an open loop sends on the schedule whatever the
system does, a closed loop keeps ``clients`` callers busy and closes with
the first tokens to reach them at or after ``--seconds``. Every time is
read on the client's side of the engine's public API (``submit``,
``ServingFuture.stream`` / ``result``) with the host's monotonic clock.

Once the window has closed, a seeded sample of the requests it finished,
the longest among them, is checked against the plain reference: one full
f32 forward pass over each prompt with its served tokens, and the widest
gap by which a served token's logit lies below the reference's best.
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

import harness
import traffic as traffic_mod
from harness import say

RESULT_TIMEOUT_S = 120.0


class _Clock:
    """Window bookkeeping shared by the load threads."""

    def __init__(self, seconds):
        self.seconds = seconds
        self.t_open = None

    def open(self):
        self.t_open = time.perf_counter()
        self.t_end = self.t_open + self.seconds

    def elapsed(self):
        return time.perf_counter() - self.t_open


def _watch(rec, fut):
    """One request's client: first token, then the whole answer."""
    try:
        stream = fut.stream(timeout=RESULT_TIMEOUT_S)
        next(stream)
        rec.first_token = time.perf_counter()
        out = fut.result(timeout=RESULT_TIMEOUT_S)[0]
        rec.done = time.perf_counter()
        rec.tokens = np.asarray(out)
    except Exception as e:                     # typed engine errors, timeouts
        rec.done = time.perf_counter()
        rec.error = f"{type(e).__name__}: {e}"


def _submit(eng, rec):
    rec.sent = time.perf_counter()
    try:
        rec.fut = eng.submit(rec.prompt, max_new_tokens=rec.max_new)
    except Exception as e:                     # refused at admission
        rec.done = time.perf_counter()
        rec.error = f"{type(e).__name__}: {e}"
        rec.fut = None
    return rec.fut


def _streamed(sent):
    return sum(len(rec.fut.tokens()) for rec in sent if rec.fut is not None)


def _close(sent):
    """The window closes: how many tokens each request sent so far has
    streamed to its client by now (``ServingFuture.tokens()``)."""
    for rec in sent:
        if rec.fut is not None:
            rec.streamed_in_window = len(rec.fut.tokens())


BURST_QUIET_S = 0.03
BURST_WAIT_S = 5.0


def _close_after_next_burst(sent_now, clock):
    """A closed loop's window closes with the first burst of tokens to
    reach the clients at or after ``seconds``, not in the middle of a
    dispatch: the engine hands tokens over a dispatch at a time (a decode
    chunk is some 256 tokens, 1.5% of a 40 s window), so a window cut at a
    fixed instant counts one dispatch more or fewer by where the cut falls.
    The window's length is then what was measured, and the rate is all the
    tokens streamed in it over all of it. The burst is over once no token
    has arrived for ``BURST_QUIET_S`` (a burst takes a few ms, the next
    dispatch some 100 ms or more)."""
    sent = sent_now()
    base = n = _streamed(sent)
    give_up = clock.t_end + BURST_WAIT_S
    t_last = None
    while True:
        now = time.perf_counter()
        if t_last is None and now >= give_up:
            break                              # nothing is streaming at all
        if t_last is not None and now - t_last >= BURST_QUIET_S:
            break
        time.sleep(0.001)
        sent = sent_now()
        m = _streamed(sent)
        if m != n:
            n, t_last = m, time.perf_counter()
    _close(sent)
    clock.t_end = time.perf_counter()
    clock.seconds = clock.t_end - clock.t_open
    say(f"window closed {clock.seconds:.4f} s after it opened, with a burst "
        f"of {n - base} tokens")
    return sent


def _run_open_loop(eng, reqs, clock):
    watchers = []
    for rec in reqs:
        due = clock.t_open + rec.due
        while True:
            left = due - time.perf_counter()
            if left <= 0:
                break
            time.sleep(min(left, 0.002) if left < 0.01 else left - 0.005)
        fut = _submit(eng, rec)
        if fut is not None:
            t = threading.Thread(target=_watch, args=(rec, fut),
                                 daemon=True)
            t.start()
            watchers.append(t)
    left = clock.t_end - time.perf_counter()
    if left > 0:
        time.sleep(left)
    _close(reqs)
    for t in watchers:                         # drain: every due request
        t.join(RESULT_TIMEOUT_S)               # gets its first token
    return reqs


def _run_closed_loop(eng, pool, clients, clock):
    lock = threading.Lock()
    state = {"next": 0}
    sent = []

    def client():
        while time.perf_counter() < clock.t_end:
            with lock:
                i = state["next"]
                state["next"] += 1
            base = pool[i % len(pool)]
            rec = traffic_mod.Request(index=i, prompt=base.prompt,
                                      max_new=base.max_new)
            with lock:
                sent.append(rec)
            fut = _submit(eng, rec)
            if fut is not None:
                _watch(rec, fut)
            if rec.error is not None:
                time.sleep(0.05)               # a refused caller backs off

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, clock.t_end - time.perf_counter()))

    def sent_now():
        with lock:
            return list(sent)

    sent = _close_after_next_burst(sent_now, clock)
    # the window is over: what is still in flight is cut, not failed
    eng.stop(drain=False, timeout=60.0)
    for t in threads:
        t.join(RESULT_TIMEOUT_S)
    return sent


def _sampler(eng, clock, trace, series, stop):
    from paddle_tpu import monitor

    while not stop.is_set():
        el = clock.elapsed()
        trace.poll(el)
        if 0 <= el <= clock.seconds:
            stats = eng.generation_stats()
            series["slot_occupancy_pct"].append(
                100.0 * len(stats["resident"]) / stats["slots"])
            series["backlog"].append(
                (el, len(stats["resident"])
                 + monitor.metric_value("serving_queue_depth", 0.0)))
        stop.wait(0.1)


def check_sample(finished, seed, n):
    """A seeded sample of the finished requests, the longest in it."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in pick]


def reference_gaps(reference, weights, model_cfg, sample, max_seq,
                   control=""):
    """For each sampled request the widest gap, over its served tokens, by
    which the served token's reference logit lies below the reference's
    best (and the same for the control's own first choices)."""
    import jax.numpy as jnp

    fn = reference.gaps_fn(model_cfg, control)
    served, ctl, n_tokens = [], [], 0
    for r in sample:
        L, n = len(r.prompt), len(r.tokens)
        seq = np.concatenate([r.prompt, r.tokens])
        ids = np.zeros(max_seq, np.int32)
        nxt = np.zeros(max_seq, np.int32)
        ids[:L + n - 1] = seq[:-1]
        nxt[:L + n - 1] = seq[1:]
        g_served, g_ctl = fn(weights, jnp.asarray(ids), jnp.asarray(nxt))
        rows = slice(L - 1, L + n - 1)          # rows that score the answer
        served.append(float(np.max(np.asarray(g_served)[rows])))
        ctl.append(float(np.max(np.asarray(g_ctl)[rows])))
        n_tokens += n
    return served, ctl, n_tokens


class Session:
    """Program, executor, scope and engine for one configuration. Built
    once; ``load`` plants a seed's weights and starts a fresh engine, so a
    calibration or a sweep can make many windows in one process."""

    def __init__(self, cell, chips):
        import paddle_tpu as fluid

        self.cell, self.chips, self.cfg = cell, chips, cell.config
        cfg = self.cfg
        self.model_cfg = cfg["model"]
        self.vocab = self.model_cfg["vocab_size"]
        self.family = importlib.import_module(f"families.{cfg['family']}")
        self.reference = importlib.import_module(f"reference.{cfg['family']}")
        self.spec = self.reference.param_spec(self.model_cfg)
        self.net = self.family.build(cfg)
        place = fluid.CPUPlace() if cell.rehearse else fluid.TPUPlace()
        self.exe, self.scope = fluid.Executor(place), fluid.Scope()
        self.exe.run(self.net["startup"], scope=self.scope)
        harness.check_parameter_names(self.net["decode"]["main"], self.spec)
        self.eng = self.weights = None

    def load(self, seed, mix) -> None:
        """The seed's weights in the scope, a fresh engine warmed up and
        started, the mix's warm requests answered."""
        from reference.common import make_weights

        self.seed = seed
        self.weights = make_weights(self.spec, seed)
        harness.plant_weights(self.scope, self.weights)
        self.eng = self.family.engine(self.cfg, self.net, self.scope,
                                      self.exe)
        self.n_exec = self.eng.warm_up()
        self.eng.start()
        warm = traffic_mod.warm_requests(mix, seed, self.vocab)
        futs = [_submit(self.eng, rec) for rec in warm]
        for rec, fut in zip(warm, futs):
            if fut is None:
                raise harness.BenchmarkError(
                    f"warm request refused: {rec.error}")
            fut.result(timeout=600)
        self.n_warm = len(warm)

    def window(self, mix, seconds, trace) -> dict:
        """Offer the mix's load for ``seconds`` and gather what the clients
        saw, the counters' movement and the benchmark's own samples."""
        eng = self.eng
        stats0 = eng.generation_stats()
        before = harness.counters()
        clock = _Clock(float(seconds))
        if mix["generator"] == "open_loop":
            reqs = traffic_mod.open_loop(mix, self.seed, clock.seconds,
                                         self.vocab)
        elif mix["generator"] == "closed_loop":
            pool = traffic_mod.closed_loop(mix, self.seed, self.vocab)
        else:
            raise harness.BenchmarkError(
                f"traffic: unknown generator {mix['generator']!r}")
        series = {"slot_occupancy_pct": [], "backlog": [], "gen_lag_ms": []}
        stop_sampler = threading.Event()
        self.t_ready = time.perf_counter()
        clock.open()
        sampler = threading.Thread(
            target=_sampler, daemon=True,
            args=(eng, clock, trace, series, stop_sampler))
        sampler.start()
        if mix["generator"] == "open_loop":
            sent = _run_open_loop(eng, reqs, clock)
        else:
            sent = _run_closed_loop(eng, pool, int(mix["clients"]), clock)
        stop_sampler.set()
        sampler.join(10.0)
        trace.stop()
        after = harness.counters()
        stats1 = eng.generation_stats()
        eng.stop(drain=True, timeout=60.0)
        acct = eng.accounting()

        closed = mix["generator"] == "closed_loop"
        judged = [r for r in sent if not closed
                  or (r.done is not None and r.done <= clock.t_end)]
        finished = [r for r in judged
                    if r.error is None and r.tokens is not None]
        failed = [r for r in judged if r.error is not None]
        for r in failed[:5]:
            say(f"failed request {r.index}: {r.error}")
        never_ms = 1e3 * clock.seconds
        base = (lambda r: r.sent) if closed \
            else (lambda r: clock.t_open + r.due)
        ttft = [1e3 * (r.first_token - base(r)) for r in finished]
        tpot = [1e3 * (r.done - r.first_token) / (len(r.tokens) - 1)
                for r in finished if len(r.tokens) > 1]
        ttft += [never_ms] * len(failed)
        tpot += [never_ms] * len(failed)
        # every token streamed to a client inside the window, whether or
        # not its request finished there: all the work of all the window
        out_tokens = sum(r.streamed_in_window for r in sent)
        series["gen_lag_ms"] = [1e3 * (r.sent - base(r)) for r in judged
                                if r.sent is not None]
        series["ttft_ms"] = ttft
        series["tpot_ms"] = tpot
        e2e = {"ttft_p95_ms": harness.percentile(ttft, 95),
               "tpot_p95_ms": harness.percentile(tpot, 95),
               "decode_tokens_per_s": out_tokens / clock.seconds}
        half = [b for t, b in series["backlog"]
                if 0.4 * clock.seconds <= t <= 0.6 * clock.seconds]
        last = [b for t, b in series["backlog"]
                if t >= 0.9 * clock.seconds]
        say(f"window {clock.seconds:g} s: {len(judged)} requests judged, "
            f"{len(finished)} finished, {len(failed)} failed, "
            f"{len(sent) - len(judged)} cut by the window's end; "
            f"{out_tokens} output tokens streamed inside the window")
        say(f"ttft ms p50 {harness.median(ttft):.1f} p95 "
            f"{e2e['ttft_p95_ms']:.1f}; tpot ms p50 "
            f"{harness.median(tpot):.2f} p95 {e2e['tpot_p95_ms']:.2f}; "
            f"generator lag ms p95 "
            f"{harness.percentile(series['gen_lag_ms'], 95):.3f}; backlog "
            f"(queued + resident) mean at the middle "
            f"{np.mean(half) if half else float('nan'):.1f}, over the last "
            f"tenth {np.mean(last) if last else float('nan'):.1f}")
        wrong_len = sum(1 for r in finished if len(r.tokens) != r.max_new)
        delta = harness.counter_delta(before, after)
        recompiles = (stats1["decode_recompiles"]
                      - stats0["decode_recompiles"]) + int(
            delta["recompiles_total{}"]
            + harness.sum_matching(delta, "executor_compiles_total"))
        return {"e2e": e2e, "judged": judged, "finished": finished,
                "failed": failed, "series": series, "counters": delta,
                "counters_total": after, "acct": acct,
                "wrong_len": wrong_len, "recompiles": recompiles,
                "backlog_mid": float(np.mean(half)) if half else None,
                "backlog_end": float(np.mean(last)) if last else None}

    def free_cache(self) -> None:
        """Drop the KV cache so the reference's arrays have room."""
        for name in self.net["state_vars"]:
            self.scope.drop_var(name)

    def gaps(self, finished, control=""):
        sample = check_sample(finished, self.seed,
                              int(self.cfg["check"]["sample"]))
        t0 = time.perf_counter()
        served, ctl, n_tokens = reference_gaps(
            self.reference, self.weights, self.model_cfg, sample,
            self.cfg["serving"]["max_seq"], control)
        say(f"reference: {len(sample)} requests, {n_tokens} served tokens, "
            f"{time.perf_counter() - t0:.1f} s (not in setup_s)")
        return served, ctl


def run(cell, chips, args, t_process, broken=None):
    import paddle_tpu as fluid
    from paddle_tpu import trace as program_trace

    mix = cell.traffic
    trace = harness.TraceWindow(bool(args.trace), args.seconds, cell.name)
    if trace.on:
        fluid.set_flags({"FLAGS_trace_buffer_size": 2_000_000})
    trace.enable_spans()

    t0 = time.perf_counter()
    s = Session(cell, chips)
    t1 = time.perf_counter()
    s.load(args.seed, mix)
    if broken:
        broken(s)
    program_trace.clear()
    say(f"set-up: imports and chip {t0 - t_process:.1f} s, programs built "
        f"and startup {t1 - t0:.1f}, seeded weights, {s.n_exec} executables "
        f"warmed ({s.eng.generation_stats()['compiled_buckets']}) and "
        f"{s.n_warm} warm requests {time.perf_counter() - t1:.1f}")
    w = s.window(mix, args.seconds, trace)
    e2e = dict(w["e2e"], setup_s=s.t_ready - t_process)
    mem_peak = harness.memory_peak_bytes(chips["devices"])

    chk = s.cfg["check"]
    s.free_cache()
    served, _ = s.gaps(w["finished"])
    checks = [
        {"name": "served_logit_gap_max", "value": max(served, default=None),
         "limit": chk["logit_gap_limit"], "rule": "<=",
         "ok": bool(served) and max(served) <= chk["logit_gap_limit"]},
        {"name": "answers_of_wrong_length", "value": w["wrong_len"],
         "limit": 0, "rule": "==", "ok": w["wrong_len"] == 0},
        {"name": "accounting_exact", "value": bool(w["acct"]["exact"]),
         "limit": True, "rule": "==", "ok": bool(w["acct"]["exact"])},
        {"name": "compilations_in_window", "value": w["recompiles"],
         "limit": 0, "rule": "==", "ok": w["recompiles"] == 0},
    ]
    result = {"checks": checks, "attempted": len(w["judged"]),
              "failed": len(w["failed"]), "memory_peak_bytes": mem_peak,
              "metrics": dict(e2e)}
    if trace.on:
        harness.finish_traced(cell, chips, trace, result,
                              counters=w["counters"],
                              counters_total=w["counters_total"],
                              series=w["series"])
    return result
