"""paddle_tpu.serving.fleet: wire schema, HTTP front-end, load-aware
router, warm-start AOT executable cache, and the frozen health()/ready()
wire contract.

Everything here runs IN-process (engines + threaded HTTP servers on
loopback) so the suite stays fast; the multi-PROCESS kill-one-replica
scenario is the CI gate's job (``tools/load_check.py --fleet``)."""
import http.client
import os
import pickle
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, serving, trace
from paddle_tpu.resilience.deadline import DeadlineExceeded
from paddle_tpu.serving.fleet import (FleetRouter, Replica, ReplicaLost,
                                      RouterConfig, ServingFrontend,
                                      WireError, wire)


@pytest.fixture(autouse=True)
def _flags_reset():
    from paddle_tpu import flags as flags_mod

    snap = dict(flags_mod._overrides)
    yield
    flags_mod._overrides.clear()
    flags_mod._overrides.update(snap)
    flags_mod._set_epoch += 1   # trace.enabled() memo must re-read


def _build_infer(hidden=4, in_dim=13):
    import paddle_tpu.unique_name as un

    with un.guard():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[in_dim], dtype="float32")
            pred = fluid.layers.fc(x, hidden, act="softmax")
        infer = main.clone(for_test=True)
    return infer, startup, pred.name


def _engine(**cfg_kw):
    infer, startup, pred = _build_infer()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    cfg = serving.ServingConfig(max_batch=cfg_kw.pop("max_batch", 4),
                                **cfg_kw)
    return serving.ServingEngine(infer, feed_names=["x"],
                                 fetch_list=[pred], scope=scope,
                                 executor=exe, config=cfg)


def _feed(rows=1, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(rows, 13).astype(np.float32)}


# ---------------------------------------------------------------------------
# wire schema
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "float16", "int64", "bool"])
def test_wire_array_roundtrip_bit_exact(dtype):
    rng = np.random.RandomState(0)
    a = (rng.rand(3, 5) * 100).astype(dtype)
    b = wire.decode_array(wire.encode_array(a))
    assert b.dtype == a.dtype and b.shape == a.shape
    assert np.array_equal(a, b)
    b[0] = 0   # decoded arrays must be writable (np.frombuffer is not)


def test_wire_status_distinct_per_typed_outcome():
    """Every typed terminal outcome travels as a DISTINCT HTTP status —
    the router's admitted/unadmitted classification depends on it."""
    cases = [serving.Overloaded("x"), serving.CircuitOpen("x"),
             serving.EngineStopped("x"),
             DeadlineExceeded("x", 1.0, 2.0), serving.BatchFailed("x"),
             WireError("x")]
    statuses = [wire.status_for(e) for e in cases]
    assert len(set(statuses)) == len(statuses)
    assert wire.status_for(serving.Overloaded("x")) == 429
    assert wire.status_for(serving.EngineStopped("x")) == 410
    assert set(wire.UNADMITTED_STATUSES) == {429, 410}


def test_wire_error_body_roundtrips_typed_exceptions():
    e = serving.Overloaded("queue full", reason="queue_age")
    e.trace_id = "abc123"
    back = wire.error_from_body(wire.error_body(e))
    assert isinstance(back, serving.Overloaded)
    assert back.reason == "queue_age" and back.trace_id == "abc123"

    d = DeadlineExceeded("req #7", 0.5, 0.8)
    back = wire.error_from_body(wire.error_body(d))
    assert isinstance(back, DeadlineExceeded)
    assert back.budget_s == 0.5 and back.elapsed_s == 0.8
    assert back.transient is False   # retry must never absorb it

    c = serving.CircuitOpen("open", bucket="b4(x)")
    assert wire.error_from_body(wire.error_body(c)).bucket == "b4(x)"
    # unknown types degrade to the typed base, never a bare RuntimeError
    alien = wire.error_from_body({"error": {"type": "Weird",
                                            "message": "m"}})
    assert isinstance(alien, serving.ServingError)


def test_wire_refuses_newer_schema_and_malformed_bodies():
    with pytest.raises(WireError):
        wire.loads(b'{"schema_version": 99}')
    # a non-integer version is the same typed refusal, never a raw
    # ValueError/TypeError (the router catches WireError only)
    with pytest.raises(WireError):
        wire.loads(b'{"schema_version": "garbage"}')
    with pytest.raises(WireError):
        wire.loads(b'{"schema_version": null}')
    with pytest.raises(WireError):
        wire.loads(b"not json")
    with pytest.raises(WireError):
        wire.loads(b"[1, 2]")
    with pytest.raises(WireError):
        wire.decode_feed("nope")
    with pytest.raises(WireError):
        wire.decode_array({"dtype": "float32", "shape": [2], "b64": "!"})


def test_wire_slo_class_resolution():
    assert wire.resolve_priority({}) == wire.SLO_CLASSES["standard"]
    assert wire.resolve_priority({"slo_class": "interactive"}) \
        == wire.SLO_CLASSES["interactive"]
    # explicit priority wins over the class
    assert wire.resolve_priority({"priority": 7,
                                  "slo_class": "batch"}) == 7
    with pytest.raises(WireError):
        wire.resolve_priority({"slo_class": "platinum"})


def test_wire_admitted_flag_overrides_status_classification():
    """The front-end's explicit ``admitted`` flag is authoritative over
    the status map: an ADMITTED request that settled EngineStopped also
    travels as 410, and the router must never redispatch it (one request
    could reach two outcomes)."""
    stopped = serving.EngineStopped("stopped mid-flight")
    assert wire.response_is_unadmitted(
        410, wire.error_body(stopped, admitted=True)) is False
    assert wire.response_is_unadmitted(
        410, wire.error_body(stopped, admitted=False)) is True
    # bodies without the flag fall back to the status map
    assert wire.response_is_unadmitted(410, {}) is True
    assert wire.response_is_unadmitted(429, None) is True
    assert wire.response_is_unadmitted(500, {}) is False


def test_span_context_wire_roundtrip():
    ctx = trace.SpanContext("tid123", "sid456")
    back = trace.SpanContext.from_wire(ctx.to_wire())
    assert back.trace_id == "tid123" and back.span_id == "sid456"
    assert trace.SpanContext.from_wire(None) is None
    assert trace.SpanContext.from_wire("") is None
    assert trace.SpanContext.from_wire("no-separator") is None


# ---------------------------------------------------------------------------
# the frozen health()/ready() wire contract
# ---------------------------------------------------------------------------

def test_health_schema_frozen():
    """health() is a versioned wire contract since the fleet tier: the
    documented key set (docs/SERVING.md "Health probe schema") must be
    EXACTLY what the payload carries — a missing key breaks deployed
    routers, an undocumented one is schema drift."""
    eng = _engine()
    h = eng.health()
    assert set(h) == set(serving.HEALTH_SCHEMA_KEYS)
    assert h["schema_version"] == serving.HEALTH_SCHEMA_VERSION == 1
    assert isinstance(h["ready"], bool) and isinstance(eng.ready(), bool)
    assert isinstance(h["queue_depth"], int)
    assert isinstance(h["open_buckets"], list)
    # the routing-relevant accounting sub-keys the gate reads
    for k in ("submitted", "completed", "shed", "pending", "exact"):
        assert k in h["accounting"], k


def test_health_schema_same_for_generative_engine():
    """GenerativeEngine inherits the same frozen payload (one schema for
    every replica kind the router polls)."""
    # no model build needed: the schema comes from the base class; use a
    # plain engine pre-start and post-stop to cover both status values
    eng = _engine()
    assert set(eng.health()) == set(serving.HEALTH_SCHEMA_KEYS)
    eng.start()
    try:
        assert eng.health()["ready"] is True
    finally:
        eng.stop()
    h = eng.health()
    assert h["status"] == "stopped" and h["ready"] is False
    assert set(h) == set(serving.HEALTH_SCHEMA_KEYS)


def test_submit_trace_parent_joins_caller_trace():
    """A trace context carried over the wire parents the request root:
    the engine-side outcome and the caller share ONE trace id."""
    fluid.set_flags({"FLAGS_trace": 1})
    eng = _engine()
    eng.warm_up()
    with eng:
        ctx = trace.SpanContext("feedf00d00000001", "feedf00d00000002")
        fut = eng.submit(_feed(), trace_parent=ctx)
        fut.result(timeout=60)
    assert fut.trace_id == "feedf00d00000001"
    ro = eng.accounting()["recent_outcomes"]
    assert ro[-1]["trace_id"] == "feedf00d00000001"


# ---------------------------------------------------------------------------
# front-end over HTTP
# ---------------------------------------------------------------------------

@pytest.fixture()
def frontend():
    eng = _engine(batch_window_s=0.005)
    eng.warm_up()
    eng.start()
    fe = ServingFrontend(eng, replica_id="t0")
    fe.start()
    yield fe
    fe.stop(wait_inflight_s=2.0)
    eng.stop(drain=False)


def _post(port, path, body, headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=wire.dumps(body),
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, wire.loads(resp.read())
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, wire.loads(resp.read())
    finally:
        conn.close()


def test_frontend_submit_roundtrip_bit_exact(frontend):
    feed = _feed(seed=3)
    status, body = _post(frontend.port, "/v1/submit",
                         {"schema_version": wire.WIRE_SCHEMA_VERSION,
                          "feed": wire.encode_feed(feed)})
    assert status == 200
    outs = wire.decode_outputs(body)
    # same engine, same feed, in-process: the wire must not perturb bits
    direct = frontend.engine.submit(_feed(seed=3)).result(timeout=60)
    assert np.array_equal(outs[0], direct[0])


def test_frontend_validation_is_400_not_an_outcome(frontend):
    eng = frontend.engine
    before = eng.accounting()["submitted"]
    status, body = _post(frontend.port, "/v1/submit",
                         {"feed": {"wrong_name":
                                   wire.encode_array(np.zeros((1, 13),
                                                              np.float32))}})
    assert status == 400
    assert body["error"]["type"] == "ValueError"
    # a caller bug never enters the accounting
    assert eng.accounting()["submitted"] == before
    status, _ = _post(frontend.port, "/v1/submit", {"feed": "garbage"})
    assert status == 400


def test_frontend_stopped_engine_maps_to_410(frontend):
    frontend.engine.stop(drain=False)
    status, body = _post(frontend.port, "/v1/submit",
                         {"feed": wire.encode_feed(_feed())})
    assert status == 410
    assert body["error"]["type"] == "EngineStopped"


def test_frontend_unknown_route_404(frontend):
    status, _ = _post(frontend.port, "/v1/nope", {})
    assert status == 404
    status, _ = _get(frontend.port, "/nope")
    assert status == 404


def test_frontend_healthz_readyz(frontend):
    status, h = _get(frontend.port, "/healthz")
    assert status == 200
    assert set(serving.HEALTH_SCHEMA_KEYS) <= set(h)
    assert h["replica_id"] == "t0"
    status, r = _get(frontend.port, "/readyz")
    assert status == 200 and r["ready"] is True
    frontend.engine.stop(drain=True)
    status, r = _get(frontend.port, "/readyz")
    assert status == 503 and r["ready"] is False
    # healthz keeps answering on a drained replica (the router's poll)
    status, h = _get(frontend.port, "/healthz")
    assert status == 200 and h["ready"] is False
    assert h["status"] == "stopped"


def test_frontend_trace_header_propagates(frontend):
    fluid.set_flags({"FLAGS_trace": 1})
    ctx = trace.SpanContext("cafecafe00000001", "cafecafe00000002")
    status, body = _post(frontend.port, "/v1/submit",
                         {"feed": wire.encode_feed(_feed())},
                         headers={wire.TRACE_HEADER: ctx.to_wire()})
    assert status == 200
    assert body["trace_id"] == "cafecafe00000001"
    ro = frontend.engine.accounting()["recent_outcomes"]
    assert ro[-1]["trace_id"] == "cafecafe00000001"


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

@pytest.fixture()
def fleet2():
    """Two in-process replicas behind a router (no poll thread — tests
    drive poll_now() explicitly for determinism)."""
    engines, fronts = [], []
    for i in range(2):
        eng = _engine(batch_window_s=0.005)
        eng.warm_up()
        eng.start()
        fe = ServingFrontend(eng, replica_id=f"r{i}")
        fe.start()
        engines.append(eng)
        fronts.append(fe)
    router = FleetRouter([Replica(f"r{i}", "127.0.0.1", fe.port)
                          for i, fe in enumerate(fronts)])
    router.poll_now()
    yield router, engines, fronts
    router.stop()
    for fe in fronts:
        fe.stop(wait_inflight_s=2.0)
    for eng in engines:
        if not eng._stopped:
            eng.stop(drain=False)


def test_router_submit_completes_with_exact_accounting(fleet2):
    router, engines, _ = fleet2
    for i in range(6):
        outs = router.submit(_feed(seed=i))
        assert outs[0].shape == (1, 4)
    acct = router.accounting()
    assert acct["exact"] and acct["completed"] == 6
    assert acct["submitted"] == 6 and acct["pending"] == 0


def test_router_honors_drain(fleet2):
    """A drained replica stops receiving traffic; everything lands on
    the sibling. Nothing is shed, nothing errors."""
    router, engines, _ = fleet2
    engines[0].stop(drain=True)   # preemption: ready() flips false
    router.poll_now()
    before = engines[1].accounting()["submitted"]
    for i in range(5):
        router.submit(_feed(seed=i))
    assert engines[1].accounting()["submitted"] - before == 5
    acct = router.accounting()
    assert acct["completed"] == 5 and acct["exact"]
    assert acct["stopped"] == 0 and acct["replica_lost"] == 0


def test_router_all_draining_is_typed_overloaded_not_a_hang(fleet2):
    router, engines, _ = fleet2
    for eng in engines:
        eng.stop(drain=True)
    router.poll_now()
    t0 = time.monotonic()
    with pytest.raises(serving.Overloaded) as ei:
        router.submit(_feed())
    assert ei.value.reason == "no_ready_replica"
    assert time.monotonic() - t0 < 5.0
    acct = router.accounting()
    assert acct["shed"] == 1 and acct["exact"]


def test_router_dead_replica_between_poll_and_dispatch_retries(fleet2):
    """The replica dies AFTER the poll said ready: the connection
    refusal is provably unadmitted, so the router retries exactly once
    on the sibling and the request completes."""
    router, engines, fronts = fleet2
    router.poll_now()               # both look ready
    # kill r0 without a poll: its snapshot still says ready
    fronts[0].stop(wait_inflight_s=0.5)
    engines[0].stop(drain=False)
    retries0 = router.accounting()["retries"]
    completed = 0
    for i in range(6):
        router.submit(_feed(seed=i))
        completed += 1
    assert completed == 6
    acct = router.accounting()
    assert acct["completed"] == 6 and acct["exact"]
    assert acct["retries"] - retries0 >= 1     # some dispatches hit r0
    assert acct["replica_lost"] == 0


def test_router_retry_is_exactly_once_then_typed(fleet2):
    """Both replicas dead with stale-ready snapshots: one retry, then a
    typed outcome — never a loop, never a hang."""
    router, engines, fronts = fleet2
    router.poll_now()
    for fe in fronts:
        fe.stop(wait_inflight_s=0.5)
    for eng in engines:
        eng.stop(drain=False)
    retries0 = router.accounting()["retries"]
    t0 = time.monotonic()
    with pytest.raises((ReplicaLost, serving.Overloaded)):
        router.submit(_feed())
    assert time.monotonic() - t0 < 20.0
    acct = router.accounting()
    assert acct["retries"] - retries0 == 1
    assert acct["exact"]


def test_router_load_aware_pick_prefers_lower_pressure(fleet2):
    router, _, _ = fleet2
    r0, r1 = router.replicas
    base = {"ok": True, "ready": True, "degraded": False,
            "open_buckets": 0, "status": "ok", "polled_at": 0.0}
    r0._update({**base, "queue_depth": 9})
    r1._update({**base, "queue_depth": 2})
    assert router._pick() is r1
    # degradation outweighs a small queue edge
    r0._update({**base, "queue_depth": 3, "degraded": True})
    r1._update({**base, "queue_depth": 8})
    assert router._pick() is r1
    # open breakers push a replica down too
    r0._update({**base, "queue_depth": 0, "open_buckets": 2})
    r1._update({**base, "queue_depth": 5})
    assert router._pick() is r1


def test_router_negative_control_ignores_drain(fleet2):
    """The CI gate's negative control wiring: with honor_drain off the
    router keeps dispatching to a stopped replica and requests reach
    typed stopped outcomes (proving the gate detects a drain-blind
    router)."""
    router, engines, fronts = fleet2
    nc = FleetRouter(
        [Replica(f"r{i}", "127.0.0.1", fe.port)
         for i, fe in enumerate(fronts)],
        config=RouterConfig(honor_drain=False, retry_unadmitted=False))
    nc.poll_now()
    engines[0].stop(drain=True)
    nc.poll_now()
    outcomes = {"completed": 0, "stopped": 0}
    for i in range(8):
        try:
            nc.submit(_feed(seed=i))
            outcomes["completed"] += 1
        except serving.EngineStopped:
            outcomes["stopped"] += 1
    assert outcomes["stopped"] >= 1          # kept routing to the corpse
    assert nc.accounting()["exact"]


class _CannedReplica:
    """A fake front-end answering canned responses — for routing-policy
    tests that need wire-level control a real engine cannot give
    deterministically (e.g. a 410 whose body says the request WAS
    admitted)."""

    def __init__(self, responses=()):
        self.requests = 0
        self.responses = list(responses)
        self.health = {"schema_version": 1, "status": "ok", "ready": True,
                       "queue_depth": 0, "degraded": False,
                       "open_buckets": [], "generative": False}
        outer = self

        class _H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, status, obj):
                raw = wire.dumps(obj)
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):
                self._json(200, outer.health)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0) or 0)
                self.rfile.read(n)
                outer.requests += 1
                if outer.responses:
                    status, body = outer.responses.pop(0)
                else:
                    status, body = 500, {"error": {
                        "type": "ServingError",
                        "message": "no canned response left"}}
                self._json(status, body)

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.port = self.server.server_address[1]

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def test_router_never_retries_an_admitted_410():
    """An engine that stops WITHOUT drain settles its admitted requests
    EngineStopped — the front-end ships that as 410 with
    ``admitted: true``. The router must raise it as-is: redispatching
    would run the request a second time on the sibling."""
    stopped = serving.EngineStopped("engine stopped holding the request")
    victim = _CannedReplica(responses=[
        (410, wire.error_body(stopped, admitted=True))])
    sibling = _CannedReplica(responses=[
        (200, wire.encode_outputs([np.zeros((1, 4), np.float32)]))])
    try:
        sibling.health["queue_depth"] = 50   # pin the pick to the victim
        router = FleetRouter([Replica("v", "127.0.0.1", victim.port),
                              Replica("s", "127.0.0.1", sibling.port)])
        router.poll_now()
        with pytest.raises(serving.EngineStopped):
            router.submit(_feed())
        assert victim.requests == 1
        assert sibling.requests == 0         # never redispatched
        acct = router.accounting()
        assert acct["retries"] == 0
        assert acct["stopped"] == 1 and acct["exact"]
    finally:
        victim.close()
        sibling.close()


def test_router_retries_unadmitted_410_on_a_sibling():
    """The same 410 status WITHOUT the admitted claim (a submit-time
    rejection from a draining engine) stays retryable — the request
    completes on the sibling, exactly one outcome."""
    draining = serving.EngineStopped("rejected at admission: draining")
    want = np.ones((1, 4), np.float32)
    victim = _CannedReplica(responses=[
        (410, wire.error_body(draining, admitted=False))])
    sibling = _CannedReplica(responses=[(200, wire.encode_outputs([want]))])
    try:
        sibling.health["queue_depth"] = 50   # victim picked first
        router = FleetRouter([Replica("v", "127.0.0.1", victim.port),
                              Replica("s", "127.0.0.1", sibling.port)])
        router.poll_now()
        outs = router.submit(_feed())
        assert np.array_equal(outs[0], want)
        assert victim.requests == 1 and sibling.requests == 1
        acct = router.accounting()
        assert acct["retries"] == 1
        assert acct["completed"] == 1 and acct["exact"]
    finally:
        victim.close()
        sibling.close()


def test_router_poll_tolerates_future_health_schema():
    """/healthz carries the HEALTH schema version (its own frozen
    contract), not the request wire version — a replica speaking a newer
    health schema must still poll as ready, not be refused through the
    wire-version gate."""
    rep = _CannedReplica()
    try:
        rep.health.update(schema_version=99, queue_depth=3)
        r = Replica("h0", "127.0.0.1", rep.port)
        FleetRouter([r]).poll_now()
        snap = r.snapshot()
        assert snap["ok"] and snap["ready"]
        assert snap["queue_depth"] == 3
    finally:
        rep.close()


def test_router_generate_requires_generative_capability(fleet2):
    """Mixed-fleet routing: request/response replicas advertise
    ``generative: false`` in /healthz, so generate() never dispatches to
    one — a fleet with none ready sheds typed instead of collecting a
    400 from a replica that cannot stream."""
    router, engines, _ = fleet2
    router.poll_now()
    with pytest.raises(serving.Overloaded) as ei:
        router.generate([1, 2, 3], max_new_tokens=2)
    assert ei.value.reason == "no_generative_replica"
    for eng in engines:                      # nothing was submitted
        assert eng.accounting()["submitted"] == 0
    acct = router.accounting()
    assert acct["shed"] == 1 and acct["exact"]


# ---------------------------------------------------------------------------
# streaming through the fleet (GenerativeEngine replica)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_fleet():
    from paddle_tpu.serving.fleet.replica import build_probe

    cfg = serving.ServingConfig(max_batch=4, queue_depth=64)
    eng, _ = build_probe("gpt_tiny", cfg)
    eng.warm_up()
    eng.start()
    fe = ServingFrontend(eng, replica_id="g0")
    fe.start()
    router = FleetRouter([Replica("g0", "127.0.0.1", fe.port)])
    router.poll_now()
    yield router, eng, fe
    router.stop()
    fe.stop(wait_inflight_s=2.0)
    if not eng._stopped:
        eng.stop(drain=False)


def test_router_generate_streams_exact_token_count(gpt_fleet):
    router, eng, _ = gpt_fleet
    toks = list(router.generate([5, 3, 1], max_new_tokens=6))
    assert len(toks) == 6
    assert all(isinstance(t, int) for t in toks)
    acct = router.accounting()
    assert acct["exact"] and acct["completed"] >= 1


def test_router_generate_mid_drain_partials_then_typed(gpt_fleet):
    """The satellite edge case: the streaming request's replica drains
    (stop without drain) mid-stream — partial tokens are delivered,
    then the typed terminal outcome surfaces; accounting stays exact."""
    router, eng, _ = gpt_fleet
    gen = router.generate([2, 2, 2], max_new_tokens=24)
    got = []
    with pytest.raises((serving.EngineStopped, serving.BatchFailed,
                        ReplicaLost)):
        for i, t in enumerate(gen):
            got.append(t)
            if i == 1:
                eng.stop(drain=False)
    assert len(got) >= 2            # partials were delivered first
    assert len(got) < 24            # and the stream really died early
    assert router.accounting()["exact"]


# ---------------------------------------------------------------------------
# warm-start AOT executable cache
# ---------------------------------------------------------------------------

@pytest.fixture()
def _no_jax_persistent_cache():
    """The suite's jax persistent compilation cache (conftest) would
    serve these tests' compiles, and an executable loaded FROM that
    cache serializes to an unloadable blob on XLA:CPU (the validated
    non-publish path). Disable it so the warm-start cache is actually
    exercised; restore after."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _aot_delta(fn):
    """(hits, misses, saves) deltas around fn()."""
    def read():
        return (monitor.metric_value("aot_cache_hits_total", 0.0),
                monitor.metric_value("aot_cache_misses_total", 0.0),
                monitor.metric_value("aot_cache_saves_total", 0.0))
    before = read()
    out = fn()
    after = read()
    return out, tuple(a - b for a, b in zip(after, before))


def test_aot_cache_roundtrip_fresh_executor_bit_exact(
        tmp_path, _no_jax_persistent_cache):
    fluid.set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
    infer, startup, pred = _build_infer()
    scope = fluid.Scope()
    exe1 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe1.run(startup)
    feed = _feed(seed=11)

    out1, d1 = _aot_delta(lambda: exe1.run(infer, feed=feed,
                                           fetch_list=[pred],
                                           scope=scope))
    assert d1[2] >= 1 and d1[0] == 0     # cold: saved, no hit
    assert any(f.endswith(".aotx") for f in os.listdir(tmp_path))

    # a FRESH executor (fresh step cache, same process) must load the
    # serialized executable instead of compiling — and match bit-exactly
    exe2 = fluid.Executor(fluid.CPUPlace())
    out2, d2 = _aot_delta(lambda: exe2.run(infer, feed=feed,
                                           fetch_list=[pred],
                                           scope=scope))
    assert d2[0] >= 1                     # warm: loaded
    assert np.array_equal(out1[0], out2[0])


def test_aot_cache_serves_run_chained(tmp_path,
                                      _no_jax_persistent_cache):
    fluid.set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
    infer, startup, pred = _build_infer()
    scope = fluid.Scope()
    exe1 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe1.run(startup)
    feed = _feed(seed=5)
    out1, d1 = _aot_delta(lambda: exe1.run_chained(
        infer, feed=feed, fetch_list=[pred], steps=3, scope=scope))
    assert d1[2] >= 1
    exe2 = fluid.Executor(fluid.CPUPlace())
    out2, d2 = _aot_delta(lambda: exe2.run_chained(
        infer, feed=feed, fetch_list=[pred], steps=3, scope=scope))
    assert d2[0] >= 1
    assert np.array_equal(np.asarray(out1[0]), np.asarray(out2[0]))


def test_aot_cache_key_changes_with_config_and_shape(tmp_path):
    from paddle_tpu import aot_cache

    infer, _, pred = _build_infer()
    args_a = ([np.zeros((1, 13), np.float32)], [], [], None)
    args_b = ([np.zeros((2, 13), np.float32)], [], [], None)
    parts = ("run", infer, (pred,), ())
    devs = [fluid.CPUPlace().jax_device()]
    k1 = aot_cache.executable_key(parts, args_a, devs)
    assert k1 == aot_cache.executable_key(parts, args_a, devs)   # stable
    assert k1 != aot_cache.executable_key(parts, args_b, devs)   # shape
    parts_opts = ("run", infer, (pred,),
                  (("xla_cpu_enable_fast_min_max", True),))
    assert k1 != aot_cache.executable_key(parts_opts, args_a, devs)
    parts_chained = ("chained", infer, (pred,), (), 3)
    assert k1 != aot_cache.executable_key(parts_chained, args_a, devs)


def test_content_fingerprint_stable_across_builds():
    from paddle_tpu.aot_cache import program_content_fingerprint

    m1, _, _ = _build_infer()
    m2, _, _ = _build_infer()
    m3, _, _ = _build_infer(hidden=8)
    assert program_content_fingerprint(m1) == program_content_fingerprint(m2)
    assert program_content_fingerprint(m1) != program_content_fingerprint(m3)
    assert m1._serial != m2._serial  # serials differ; content hash doesn't


def test_aot_cache_corrupt_and_stale_entries_degrade(
        tmp_path, _no_jax_persistent_cache):
    """A torn/garbage/wrong-version entry is a MISS with one warning,
    never an error: the executor compiles as if uncached."""
    fluid.set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
    infer, startup, pred = _build_infer()
    scope = fluid.Scope()
    exe1 = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe1.run(startup)
    feed = _feed(seed=2)
    out1 = exe1.run(infer, feed=feed, fetch_list=[pred], scope=scope)
    entries = [f for f in os.listdir(tmp_path) if f.endswith(".aotx")]
    assert entries
    # corrupt every entry
    for f in entries:
        with open(os.path.join(tmp_path, f), "wb") as fh:
            fh.write(b"not a pickle")
    exe2 = fluid.Executor(fluid.CPUPlace())
    out2 = exe2.run(infer, feed=feed, fetch_list=[pred], scope=scope)
    assert np.array_equal(out1[0], out2[0])
    # stale version: a well-formed entry from a "different jax" (exe2's
    # recompile re-published SOME entries over the garbage; the startup
    # program's entry stays corrupt — skip what cannot parse)
    for f in os.listdir(tmp_path):
        if not f.endswith(".aotx"):
            continue
        p = os.path.join(tmp_path, f)
        try:
            with open(p, "rb") as fh:
                blob = pickle.load(fh)
        except Exception:
            continue
        blob["jax"] = "0.0.1-alien"
        with open(p, "wb") as fh:
            pickle.dump(blob, fh)
    hits0 = monitor.metric_value("aot_cache_hits_total", 0.0)
    exe3 = fluid.Executor(fluid.CPUPlace())
    out3 = exe3.run(infer, feed=feed, fetch_list=[pred], scope=scope)
    assert np.array_equal(out1[0], out3[0])
    assert monitor.metric_value("aot_cache_hits_total", 0.0) == hits0


def test_aot_cache_off_by_default(tmp_path):
    """Without FLAGS_aot_cache_dir nothing is written anywhere."""
    infer, startup, pred = _build_infer()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    _, d = _aot_delta(lambda: exe.run(infer, feed=_feed(),
                                      fetch_list=[pred], scope=scope))
    assert d == (0.0, 0.0, 0.0)
