"""Serving-fleet resilience tests (serve/router.py + serve/fleet.py).

Three tiers, all CPU and tier-1:

- pure state-machine tests (circuit breaker, fault-spec parsing, fault
  routing) with injected clocks — no sockets, no sleeps;
- stub-replica tests: the router against tiny in-test HTTP servers whose
  failure behavior is a switch (refused, pre-stream reset, mid-stream
  death, slow first byte, unhealthy healthz) — every routing policy is
  exercised without paying a subprocess boot;
- subprocess chaos drills: REAL replica processes (cli/serve_lm.py,
  gpt2-tiny, random weights) under the fleet supervisor, with
  ``PDT_TPU_FAULT=replica_crash`` killing one mid-load and SIGTERM
  driving the drain/exit-75 contract end-to-end.

The acceptance bar throughout: every submitted request either streams to
completion or fails with an EXPLICIT retryable error — zero hung waiters.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_training_tpu.serve.router import (
    CircuitBreaker,
    Router,
    RouterConfig,
)
from pytorch_distributed_training_tpu.serve.server import wait_until

pytestmark = [pytest.mark.serve, pytest.mark.chaos]


class ListSink:
    """In-memory telemetry sink (same contract as JsonlSink.emit)."""

    def __init__(self):
        self.records = []
        self._lock = threading.Lock()

    def emit(self, record):
        rec = dict(record)
        rec.setdefault("ts", time.time())
        with self._lock:
            self.records.append(rec)

    def flush(self, **kw):
        pass

    def of(self, kind):
        with self._lock:
            return [r for r in self.records if r.get("record") == kind]


def _registry():
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return reg, sink


# =====================================================================
# state machines (no sockets)
# =====================================================================


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_circuit_breaker_opens_half_opens_closes():
    clock = FakeClock()
    transitions = []
    br = CircuitBreaker(
        threshold=3, cooldown_s=2.0, now_fn=clock,
        on_transition=lambda a, b: transitions.append((a, b)),
    )
    assert br.state == br.CLOSED and br.allow_probe()
    br.record_failure()
    br.record_failure()
    assert br.state == br.CLOSED    # under threshold: still closed
    br.record_failure()
    assert br.state == br.OPEN      # 3 consecutive failures -> open
    assert not br.allow_probe()     # cooldown not yet over
    assert br.reopen_in() == pytest.approx(2.0)
    clock.t += 2.5
    assert br.allow_probe()         # cooldown over -> half-open probe
    assert br.state == br.HALF_OPEN
    br.record_success()
    assert br.state == br.CLOSED and br.failures == 0
    assert transitions == [
        ("closed", "open"), ("open", "half_open"), ("half_open", "closed"),
    ]


def test_circuit_breaker_half_open_failure_reopens():
    clock = FakeClock()
    br = CircuitBreaker(threshold=2, cooldown_s=1.0, now_fn=clock)
    br.record_failure()
    br.record_failure()
    assert br.state == br.OPEN
    clock.t += 1.1
    assert br.allow_probe() and br.state == br.HALF_OPEN
    br.record_failure()             # probe failed -> straight back to open
    assert br.state == br.OPEN
    assert not br.allow_probe()     # and the cooldown restarted
    clock.t += 1.1
    assert br.allow_probe()
    # a success after an intervening failure history still closes cleanly
    br.record_success()
    assert br.state == br.CLOSED


def test_serve_fault_spec_parsing():
    from pytorch_distributed_training_tpu.faults.inject import FaultPlan

    plan = FaultPlan.parse(
        "replica_crash:5,replica_hang:3:0.5,replica_slow:2:4x"
    )
    kinds = [(s.kind, s.step, s.factor) for s in plan.specs]
    assert kinds == [
        ("replica_crash", 5, 1.0),
        ("replica_hang", 3, 0.5),
        ("replica_slow", 2, 4.0),
    ]
    # hang duration defaults when omitted
    assert FaultPlan.parse("replica_hang:3").specs[0].factor == 2.0
    for bad in (
        "replica_crash:0",          # non-positive tick
        "replica_crash:2:9",        # crash takes a bare tick
        "replica_slow:2",           # slow needs a factor
        "replica_slow:2:0.5x",      # factor < 1
        "replica_hang:1:2:3",       # too many parts
    ):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_split_fault_specs_routes_by_rank():
    from pytorch_distributed_training_tpu.serve.fleet import split_fault_specs

    routed = split_fault_specs(
        "replica_crash:5@1,replica_slow:2:4x,crash_at_step:3,"
        "replica_hang:1:0.2@1"
    )
    # serve-scoped specs land on their @rank replica (suffix stripped);
    # train-scoped specs never reach a replica env
    assert routed == {
        1: "replica_crash:5,replica_hang:1:0.2",
        0: "replica_slow:2:4x",
    }
    assert split_fault_specs(None) == {}
    assert split_fault_specs("crash_at_step:3") == {}


# =====================================================================
# stub replicas: routing policy without subprocess boots
# =====================================================================


class StubReplica:
    """A minimal replica-shaped HTTP server whose behavior is a switch.

    ``mode``: "ok" (stream ``tokens`` then done), "reset" (close before
    any byte), "mid_stream" (stream 2 tokens then close, no done),
    "busy" (429 + Retry-After), "slow" (sleep ``ttfb_s`` then stream).
    ``health``: "ready" | "draining" | "unhealthy" | "dead" (refuse).
    """

    def __init__(self, *, mode="ok", health="ready", tokens=3,
                 ttfb_s=0.0, queue_depth=0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        stub = self
        self.mode = mode
        self.health = health
        self.tokens = tokens
        self.ttfb_s = ttfb_s
        self.queue_depth = queue_depth
        self.generate_hits = 0
        self.health_hits = 0

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.0"

            def log_message(self, fmt, *args):
                pass

            def _json(self, code, obj, headers=None):
                body = (json.dumps(obj) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                stub.health_hits += 1
                state = stub.health
                payload = {
                    "state": state,
                    "queue_depth": stub.queue_depth,
                    "slot_occupancy": 0.0,
                    "num_slots": 1,
                }
                self._json(200 if state == "ready" else 503, payload)

            def do_POST(self):
                stub.generate_hits += 1
                rid = self.headers.get("X-Request-Id", "?")
                if stub.mode == "reset":
                    self.wfile.close()      # die before any byte
                    return
                if stub.mode == "busy":
                    self._json(429, {"error": "full"},
                               headers={"Retry-After": 1})
                    return
                if stub.mode == "slow":
                    time.sleep(stub.ttfb_s)
                self.send_response(200)
                self.end_headers()
                n = 2 if stub.mode == "mid_stream" else stub.tokens
                for i in range(n):
                    self.wfile.write((json.dumps({
                        "id": rid, "event": "token", "token_id": i,
                    }) + "\n").encode())
                    self.wfile.flush()
                if stub.mode == "mid_stream":
                    self.wfile.close()      # EOF with no done event
                    return
                self.wfile.write((json.dumps({
                    "id": rid, "event": "done", "status": "done",
                    "new_tokens": n,
                }) + "\n").encode())

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        if health != "dead":
            self._thread.start()

    def close(self):
        if self._thread.is_alive():
            self.httpd.shutdown()


def _make_router(stubs, registry=None, **cfg_kw):
    cfg = RouterConfig(**{
        "health_interval_s": 0.03,
        "health_timeout_s": 0.5,
        "breaker_threshold": 3,
        "breaker_cooldown_s": 0.25,
        "retry_backoff_s": 0.01,
        "retry_backoff_max_s": 0.05,
        "ttfb_timeout_s": 5.0,
        **cfg_kw,
    })
    router = Router(
        [(f"s{i}", "127.0.0.1", s.port) for i, s in enumerate(stubs)],
        cfg, registry=registry,
    )
    return router


def _collect_lines():
    lines = []

    def write(b):
        lines.append(json.loads(b))

    return lines, write


def _wait_in_rotation(router, n, timeout=5.0):
    assert wait_until(
        lambda: router.available_count() >= n, timeout=timeout
    ), router.stats()


def test_router_all_replicas_down_returns_503_retry_after():
    """Nothing listening on either endpoint: breakers open fast and a
    request fails FAST with 503 + Retry-After — never a hang."""
    from pytorch_distributed_training_tpu.serve.fleet import find_free_port
    from pytorch_distributed_training_tpu.serve.router import (
        make_router_http_server,
    )

    reg, sink = _registry()
    router = Router(
        [("a", "127.0.0.1", find_free_port()),
         ("b", "127.0.0.1", find_free_port())],
        RouterConfig(health_interval_s=0.03, health_timeout_s=0.3,
                     breaker_threshold=2, breaker_cooldown_s=30.0),
        registry=reg,
    ).start()
    httpd = make_router_http_server(router)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        assert wait_until(
            lambda: all(
                r.breaker.state == "open" for r in router.replicas
            ),
            timeout=10,
        )
        t0 = time.monotonic()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": "hi"}))
        resp = conn.getresponse()
        elapsed = time.monotonic() - t0
        assert resp.status == 503
        assert int(resp.getheader("Retry-After")) >= 1
        assert resp.getheader("X-Request-Id")
        assert elapsed < 5.0        # fail-fast, not fail-by-timeout
        conn.close()
        # the router's own healthz advertises the dead pool the same way
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        assert resp.status == 503
        assert resp.getheader("Retry-After")
        conn.close()
        assert sink.of("router_request")[-1]["status"] == "rejected"
    finally:
        httpd.shutdown()
        router.close()


def test_router_failover_before_first_byte():
    """Pre-stream replica death is idempotent: the router retries the SAME
    request on the other replica and the client sees one clean stream."""
    reg, sink = _registry()
    a = StubReplica(mode="reset", queue_depth=0)     # dies pre-byte, low load
    b = StubReplica(mode="ok", tokens=3, queue_depth=5)  # healthy, loaded
    router = _make_router([a, b], registry=reg).start()
    try:
        _wait_in_rotation(router, 2)
        lines, write = _collect_lines()
        out = router.route_generate(
            json.dumps({"prompt": "x"}).encode(), "req-1", write
        )
        assert out["status"] == "ok"
        assert out["replica"] == "s1"
        assert out["attempts"] == 2         # s0 (least loaded) died first
        assert lines[-1]["event"] == "done"
        assert len([l for l in lines if l["event"] == "token"]) == 3
        assert router.failovers == 1
        fo = sink.of("router_failover")
        assert len(fo) == 1 and fo[0]["to"] == "s1"
        req = sink.of("router_request")[-1]
        assert req["status"] == "ok" and req["attempts"] == 2
    finally:
        router.close()
        a.close()
        b.close()


def test_router_mid_stream_failure_is_explicit_retryable_error():
    """Once bytes streamed, no silent retry and no hang: the client gets
    its partial tokens plus a terminal error event marked retryable."""
    reg, sink = _registry()
    a = StubReplica(mode="mid_stream", queue_depth=0)
    b = StubReplica(mode="ok", queue_depth=5)
    router = _make_router([a, b], registry=reg).start()
    try:
        _wait_in_rotation(router, 2)
        lines, write = _collect_lines()
        out = router.route_generate(
            json.dumps({"prompt": "x"}).encode(), "req-2", write
        )
        assert out["status"] == "error_midstream"
        assert out["attempts"] == 1         # never duplicated downstream
        assert lines[-1]["event"] == "error"
        assert lines[-1]["retryable"] is True
        assert [l["event"] for l in lines[:-1]] == ["token", "token"]
        assert b.generate_hits == 0         # the stream was NOT re-sent
        assert sink.of("router_request")[-1]["status"] == "error_midstream"
    finally:
        router.close()
        a.close()
        b.close()


def test_router_retries_busy_replica_without_breaker_harm():
    """429 from a loaded replica reroutes the request but does NOT count
    against the breaker — busy is healthy."""
    reg, _sink = _registry()
    a = StubReplica(mode="busy", queue_depth=0)
    b = StubReplica(mode="ok", queue_depth=5)
    router = _make_router([a, b], registry=reg).start()
    try:
        _wait_in_rotation(router, 2)
        lines, write = _collect_lines()
        out = router.route_generate(
            json.dumps({"prompt": "x"}).encode(), "req-3", write
        )
        assert out["status"] == "ok" and out["replica"] == "s1"
        assert lines[-1]["event"] == "done"
        assert router.replicas[0].breaker.state == "closed"
    finally:
        router.close()
        a.close()
        b.close()


def test_router_hedges_slow_ttfb():
    """No first byte within hedge_s: a second replica races the first and
    the client streams from whichever answers first."""
    reg, sink = _registry()
    a = StubReplica(mode="slow", ttfb_s=3.0, queue_depth=0)
    b = StubReplica(mode="ok", tokens=2, queue_depth=5)
    router = _make_router([a, b], registry=reg, hedge_s=0.1).start()
    try:
        _wait_in_rotation(router, 2)
        lines, write = _collect_lines()
        t0 = time.monotonic()
        out = router.route_generate(
            json.dumps({"prompt": "x"}).encode(), "req-4", write
        )
        elapsed = time.monotonic() - t0
        assert out["status"] == "ok"
        assert out["hedged"] is True
        assert out["replica"] == "s1"       # the hedge won
        assert lines[-1]["event"] == "done"
        assert elapsed < 2.5                # did not wait out the slow TTFB
        assert router.hedges == 1
        hedge = sink.of("router_hedge")
        assert len(hedge) == 1
        assert hedge[0]["primary"] == "s0" and hedge[0]["hedge"] == "s1"
    finally:
        router.close()
        a.close()
        b.close()


def test_breaker_trips_on_unhealthy_and_recovers_via_half_open():
    """An unhealthy replica leaves rotation after `threshold` consecutive
    bad polls; when it turns healthy again, the half-open probe puts it
    back — the full trip/recover cycle through REAL health polling."""
    reg, sink = _registry()
    a = StubReplica(mode="ok", health="ready")
    router = _make_router([a], breaker_cooldown_s=0.2, registry=reg).start()
    try:
        _wait_in_rotation(router, 1)
        a.health = "unhealthy"
        assert wait_until(
            lambda: router.replicas[0].breaker.state == "open", timeout=10
        )
        assert router.pick() is None        # out of rotation
        a.health = "ready"
        assert wait_until(
            lambda: router.replicas[0].breaker.state == "closed", timeout=10
        )
        assert router.pick() is not None    # recovered
        def seq():
            return [(r["from"], r["to"]) for r in sink.of("router_breaker")]

        # the breaker's state flips a moment before its record is written
        assert wait_until(lambda: ("half_open", "closed") in seq(), timeout=5)
        assert ("closed", "open") in seq()
        assert ("open", "half_open") in seq()
    finally:
        router.close()
        a.close()


def test_router_drains_draining_replica_out_of_rotation():
    """A replica advertising 'draining' leaves rotation at the next poll
    without tripping its breaker — it is healthy, just leaving."""
    reg, sink = _registry()
    a = StubReplica(mode="ok", health="ready")
    router = _make_router([a], registry=reg).start()
    try:
        _wait_in_rotation(router, 1)
        a.health = "draining"
        assert wait_until(lambda: router.replicas[0].draining, timeout=10)
        assert router.pick() is None
        assert router.replicas[0].breaker.state == "closed"
        states = sink.of("router_replica_state")
        assert states and states[-1]["draining"] is True
    finally:
        router.close()
        a.close()


def test_pick_least_loaded_with_round_robin_ties():
    a = StubReplica(queue_depth=0)
    b = StubReplica(queue_depth=4)
    c = StubReplica(queue_depth=0)
    router = _make_router([a, b, c])
    for i, r in enumerate(router.replicas):     # hand-feed health samples
        r.health = {"queue_depth": [0, 4, 0][i], "slot_occupancy": 0.0,
                    "num_slots": 1}
        r.last_ready_t = time.monotonic()
    picks = {router.pick().name for _ in range(8)}
    assert picks == {"s0", "s2"}        # never the loaded replica...
    assert router.pick(exclude=frozenset({"s0", "s2"})).name == "s1"  # ...unless excluded
    for s in (a, b, c):
        s.close()


# =====================================================================
# replica-side health states (in-process InferenceServer)
# =====================================================================


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel
    from pytorch_distributed_training_tpu.utils.config import model_preset

    cfg = model_preset(
        "gpt2-tiny", compute_dtype="float32", attention_impl="reference",
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = GPT2LMModel(cfg)
    params = model.init(jax.random.key(0), jnp.ones((2, 16), jnp.int32))[
        "params"
    ]
    return model, params


def _server(lm, reg=None, **kw):
    from pytorch_distributed_training_tpu.serve import (
        EngineConfig,
        InferenceServer,
    )

    model, params = lm
    kw.setdefault("queue_depth", 4)
    return InferenceServer(
        model, params,
        EngineConfig(num_slots=1, prompt_buckets=(8,), max_new_tokens=64),
        registry=reg, **kw,
    )


def _prompt(model, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(1, model.config.vocab_size, n).astype(np.int32)


def test_healthz_reports_states_and_load(lm):
    """/healthz: ready with load fields; 503 'draining' once shutdown
    begins; 503 'unhealthy' when the serve loop dies."""
    from pytorch_distributed_training_tpu.data.bpe import ByteTokenizer
    from pytorch_distributed_training_tpu.serve import make_http_server

    server = _server(lm)
    httpd = make_http_server(server, ByteTokenizer())
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    def healthz():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/healthz")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        retry = resp.getheader("Retry-After")
        conn.close()
        return resp.status, payload, retry

    try:
        status, payload, _ = healthz()
        assert status == 200 and payload["state"] == "ready"
        for key in ("queue_depth", "slot_occupancy", "num_slots"):
            assert key in payload

        # draining: visible on /healthz as a 503 the moment the queue
        # refuses admissions — external LBs act on the status code
        server.queue.close()
        status, payload, retry = healthz()
        assert status == 503 and payload["state"] == "draining"
        assert retry is not None
    finally:
        httpd.shutdown()
        server.close(drain=False)

    # a dead serve loop is 'unhealthy', not 'draining' (different fix:
    # replace the replica, don't wait for it)
    server2 = _server(lm)

    def boom():
        raise RuntimeError("injected tick failure")

    server2.engine.tick = boom
    server2.start()
    assert wait_until(lambda: server2.queue.closed, timeout=30)
    assert server2.health()["state"] == "unhealthy"
    server2.close(drain=False)


def test_http_request_id_propagates_to_telemetry_and_events(lm):
    """X-Request-Id flows header -> queue -> engine -> telemetry record ->
    response header + every streamed event; 429 carries Retry-After."""
    from pytorch_distributed_training_tpu.data.bpe import ByteTokenizer
    from pytorch_distributed_training_tpu.serve import make_http_server

    reg, sink = _registry()
    server = _server(lm, reg=reg).start()
    httpd = make_http_server(server, ByteTokenizer())
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": "hello", "max_new_tokens": 3}),
            headers={"X-Request-Id": "trace-me-123"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Request-Id") == "trace-me-123"
        events = [json.loads(l) for l in resp.read().decode().splitlines()]
        assert all(e["id"] == "trace-me-123" for e in events)
        assert events[-1]["event"] == "done"
        conn.close()
        recs = sink.of("serve_request")
        assert len(recs) == 1 and recs[0]["id"] == "trace-me-123"

        # without the header (or a body id) the server generates one
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": "hi", "max_new_tokens": 2}),
        )
        resp = conn.getresponse()
        rid = resp.getheader("X-Request-Id")
        assert rid
        events = [json.loads(l) for l in resp.read().decode().splitlines()]
        assert all(e["id"] == rid for e in events)
        conn.close()
    finally:
        httpd.shutdown()
        server.close(drain=False)

    # backpressure: 429 + Retry-After (loop stopped so fullness is stable)
    server3 = _server(lm, queue_depth=1)
    httpd3 = make_http_server(server3, ByteTokenizer())
    port3 = httpd3.server_address[1]
    threading.Thread(target=httpd3.serve_forever, daemon=True).start()
    try:
        model, _params = lm
        server3.submit(_prompt(model), max_new_tokens=2)    # fills depth 1
        conn = http.client.HTTPConnection("127.0.0.1", port3, timeout=10)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": "hi"}))
        resp = conn.getresponse()
        assert resp.status == 429
        assert resp.getheader("Retry-After")
        assert resp.getheader("X-Request-Id")
        conn.close()
    finally:
        httpd3.shutdown()
        server3.close(drain=False)


def test_expired_telemetry_split_queued_vs_running(lm):
    """Deadline expiries are split by phase: queued (overload) vs running
    (stuck/slow replica) — counters and per-request records."""
    model, _params = lm
    reg, sink = _registry()
    server = _server(lm, reg=reg)

    # queued expiry: deadline passes before any tick admits it
    q = server.submit(_prompt(model, seed=1), max_new_tokens=4,
                      deadline_s=0.01)
    time.sleep(0.05)
    server.engine.tick()
    assert q.done.is_set() and q.status == "expired"

    # running expiry: admit with a generous deadline, then shrink it
    r = server.submit(_prompt(model, seed=2), max_new_tokens=64,
                      deadline_s=60.0)
    while r.admit_t is None:
        server.engine.tick()
    r.deadline_s = 1e-4
    while not r.done.is_set():
        server.engine.tick()
    assert r.status == "expired" and len(r.tokens) > 0

    recs = sink.of("serve_expired")
    assert [x["phase"] for x in recs] == ["queued", "running"]
    assert recs[0]["id"] == q.id and recs[1]["id"] == r.id
    counters = reg.snapshot()["counters"]
    assert counters["serve/expired_queued"] == 1
    assert counters["serve/expired_running"] == 1
    assert counters["serve/expired"] == 2
    server.close(drain=False)


def test_replica_hang_injection_goes_unhealthy_then_recovers(lm):
    """PDT_TPU_FAULT=replica_hang freezes the serve loop at an exact busy
    tick: /healthz flips to 'unhealthy' while the heartbeat is stale and
    back to 'ready' when the loop resumes — the signal a router's breaker
    trips on and recovers from."""
    from pytorch_distributed_training_tpu.faults.inject import (
        FaultPlan,
        set_plan,
    )

    model, _params = lm
    server = _server(lm, stall_timeout_s=0.25).start()
    try:
        # warm: compile prefill+decode OUTSIDE the injected window so the
        # hang tick is the only slow tick (busy ticks 1..3)
        warm = server.submit(_prompt(model, seed=3), max_new_tokens=3)
        assert wait_until(warm.done.is_set, timeout=120)
        assert server.health()["state"] == "ready"

        prev = set_plan(FaultPlan.parse("replica_hang:5:1.0"))
        try:
            req = server.submit(_prompt(model, seed=4), max_new_tokens=8)
            saw_unhealthy = wait_until(
                lambda: server.health()["state"] == "unhealthy", timeout=10
            )
            assert saw_unhealthy    # stale heartbeat detected mid-hang
            assert wait_until(req.done.is_set, timeout=120)
            assert req.status == "done"
            assert wait_until(
                lambda: server.health()["state"] == "ready", timeout=10
            )
        finally:
            set_plan(prev)
    finally:
        server.close(drain=False)


# =====================================================================
# subprocess chaos drills: REAL replicas under the fleet supervisor
# =====================================================================

REPLICA_ARGS = (
    "--model", "gpt2-tiny", "--num-slots", "2",
    "--prompt-buckets", "16,32", "--max-new-tokens-cap", "64",
    "--queue-depth", "16", "--stall-timeout-s", "10",
)


def _fleet(num_replicas, fault_env=None, registry=None, **router_kw):
    from pytorch_distributed_training_tpu.serve.fleet import (
        FleetConfig,
        ServeFleet,
    )

    return ServeFleet(
        FleetConfig(
            num_replicas=num_replicas,
            replica_args=REPLICA_ARGS,
            fault_env=fault_env or {},
            max_restarts=1,
            backoff_s=0.2,
            drain_timeout_s=20.0,
        ),
        RouterConfig(**{
            "health_interval_s": 0.05,
            "health_timeout_s": 1.0,
            "breaker_threshold": 3,
            "breaker_cooldown_s": 0.5,
            "retry_backoff_s": 0.02,
            "retry_backoff_max_s": 0.1,
            "ttfb_timeout_s": 60.0,
            **router_kw,
        }),
        registry=registry,
    )


def _post_generate(port, prompt, max_new, rid, timeout=120):
    """One closed-loop client request through the router; returns a dict
    classifying the outcome (never raises, never hangs past timeout)."""
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": prompt, "max_new_tokens": max_new}),
            headers={"X-Request-Id": rid},
        )
        resp = conn.getresponse()
        if resp.status != 200:
            retry_after = resp.getheader("Retry-After")
            resp.read()
            conn.close()
            return {"outcome": "rejected", "status": resp.status,
                    "retry_after": retry_after}
        events = [json.loads(l) for l in resp.read().decode().splitlines()]
        conn.close()
        last = events[-1] if events else {}
        if last.get("event") == "done":
            return {"outcome": "done", "events": events}
        if last.get("event") == "error" and last.get("retryable"):
            return {"outcome": "retryable_error", "events": events}
        return {"outcome": "bad", "events": events}
    except Exception as e:          # pragma: no cover - drill diagnostics
        return {"outcome": "exception", "error": repr(e)}


def test_fleet_replica_crash_mid_load_fails_over(tmp_path):
    """THE acceptance drill: 2 replicas, PDT_TPU_FAULT=replica_crash kills
    one mid-load. Every request streams to completion or fails with an
    explicit retryable error (zero hung waiters); the router records the
    failover; the supervisor respawns the dead replica (burning a
    restart) and the pool recovers."""
    from pytorch_distributed_training_tpu.serve.router import (
        make_router_http_server,
    )

    reg, sink = _registry()
    fleet = _fleet(
        2, fault_env={0: "replica_crash:6"}, registry=reg
    ).start()
    httpd = None
    try:
        assert fleet.wait_ready(timeout=120), fleet.stats()
        httpd = make_router_http_server(fleet.router)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

        results = [None] * 8
        threads = []
        for i in range(8):
            def run(i=i):
                results[i] = _post_generate(
                    port, f"request number {i}", 8, f"drill-{i}"
                )
            t = threading.Thread(target=run, daemon=True)
            threads.append(t)
            t.start()
        for t in threads:
            t.join(180)
        # ZERO hung waiters: every client thread finished and every
        # outcome is terminal-and-explicit
        assert all(t.is_alive() is False for t in threads)
        outcomes = [r["outcome"] for r in results]
        assert all(
            o in ("done", "retryable_error", "rejected") for o in outcomes
        ), results
        assert outcomes.count("done") >= 1      # the survivor kept serving

        # the crash really happened and was recorded as a CRASH (rc != 75)
        def crashed():
            return [r for r in sink.of("replica_exit") if not r["graceful"]]

        # the supervisor writes the record when it reaps the process, which
        # under load can be a moment after the clients have their answers
        assert wait_until(crashed, timeout=30)
        crashes = crashed()
        assert crashes[0]["replica"] == "r0"
        assert crashes[0]["rc"] == 23       # REPLICA_CRASH_EXIT_CODE

        # the router recorded the failover path it took
        counters = reg.snapshot()["counters"]
        failovers = counters.get("router/failovers", 0)
        midstream = counters.get("router/midstream_errors", 0)
        assert failovers + midstream >= 1, counters

        # supervision: r0 respawned, burning a restart from the budget
        assert wait_until(
            lambda: fleet.replica(0).describe()["restarts_used"] >= 1,
            timeout=60,
        )
        assert fleet.wait_ready(timeout=120, min_replicas=2)
        post = _post_generate(port, "after recovery", 4, "drill-post")
        assert post["outcome"] == "done", post
    finally:
        if httpd is not None:
            httpd.shutdown()
        fleet.stop(drain=False)

    # the drill's stream folds into the summarize_metrics fleet section
    import subprocess
    import sys

    stream = str(tmp_path / "metrics.jsonl")
    with open(stream, "w") as f:
        for r in sink.records:
            f.write(json.dumps(r) + "\n")
    proc = subprocess.run(
        [sys.executable, "scripts/summarize_metrics.py", stream, "--json"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    fleet_summary = json.loads(proc.stdout)["fleet"]
    assert fleet_summary["routed"] >= 8
    assert fleet_summary["failovers"] + fleet_summary["midstream_errors"] >= 1
    assert "r0" in fleet_summary["replicas"]
    assert fleet_summary["replicas"]["r0"]["crashes"] >= 1


def test_fleet_sigterm_drains_in_flight_and_exits_75():
    """The preemption contract, serve-side: SIGTERM to a replica streaming
    a request -> it advertises draining (router pulls it from rotation),
    FINISHES the in-flight stream, exits 75, and the supervisor respawns
    it without counting a crash."""
    from pytorch_distributed_training_tpu.serve.router import (
        make_router_http_server,
    )

    reg, sink = _registry()
    fleet = _fleet(1, registry=reg).start()
    httpd = None
    try:
        assert fleet.wait_ready(timeout=120), fleet.stats()
        httpd = make_router_http_server(fleet.router)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

        # incremental client: events append as lines arrive, so the test
        # can SIGTERM the replica while the stream is provably mid-flight
        events = []
        client_done = threading.Event()

        def client():
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=120
                )
                conn.request(
                    "POST", "/generate",
                    body=json.dumps({
                        "prompt": "a long drain drill request",
                        "max_new_tokens": 64,
                    }),
                    headers={"X-Request-Id": "drain-1"},
                )
                resp = conn.getresponse()
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    events.append(json.loads(line))
                conn.close()
            finally:
                client_done.set()

        t = threading.Thread(target=client, daemon=True)
        t.start()
        replica = fleet.replica(0)
        first_pid = replica.proc.pid
        # wait until tokens are genuinely streaming, then preempt
        assert wait_until(lambda: len(events) >= 2, timeout=60), events
        replica.sigterm()

        # the in-flight stream completes (drain finishes, not cancels)
        assert client_done.wait(120)
        done = events[-1]
        assert done["event"] == "done", events[-3:]
        assert done["new_tokens"] == 64 and done["status"] == "done"

        # exit 75, recorded as graceful with a measured drain duration
        assert wait_until(lambda: len(sink.of("replica_exit")) >= 1,
                          timeout=30)
        exits = sink.of("replica_exit")
        assert exits[0]["graceful"] is True and exits[0]["rc"] == 75
        drains = sink.of("replica_drain")
        assert drains and drains[0]["drain_s"] > 0

        # the router saw 'draining' BEFORE the process died
        states = sink.of("router_replica_state")
        assert any(s["draining"] for s in states), states

        # no restart burned; the replica respawns as fresh capacity
        assert wait_until(
            lambda: fleet.replica(0).describe()["alive"]
            and fleet.replica(0).proc.pid != first_pid,
            timeout=90,
        )
        d = fleet.replica(0).describe()
        assert d["restarts_used"] == 0 and d["graceful_exits"] == 1
        assert fleet.wait_ready(timeout=120)
    finally:
        if httpd is not None:
            httpd.shutdown()
        fleet.stop(drain=False)
