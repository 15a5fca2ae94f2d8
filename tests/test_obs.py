"""Observability-plane tests (telemetry/spans.py, telemetry/flight.py,
telemetry/slo.py + their serve-stack instrumentation and the
trace_view/summarize renderers). CPU, tier-1.

Four layers:

- pure unit tests with injected clocks (burn-rate window math, flight-ring
  bounds, span/trace structural analysis) — no sleeps, no sockets;
- in-process engine tests (gpt2-tiny): the span tree a served request
  emits TILES its lifetime, mixed greedy/speculative; a PDT_TPU_FAULT
  replica_hang under an installed watchdog dumps the flight ring with the
  stalled tick as the last entry;
- stub-replica router tests: hedged/retried attempts stay in ONE trace,
  and the X-Parent-Span header the router sends names the attempt/hedge
  span the replica should parent under;
- one subprocess drill: a REAL replica (cli/serve_lm.py) writes its span
  stream to disk, the merged coordinator+replica streams reconstruct the
  request end-to-end across the process boundary, and SIGTERM drain dumps
  the replica's flight ring.
"""

import http.client
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.serve.router import (
    Router,
    RouterConfig,
)
from pytorch_distributed_training_tpu.serve.server import wait_until
from pytorch_distributed_training_tpu.telemetry.flight import (
    FlightRecorder,
)
from pytorch_distributed_training_tpu.telemetry import flight as flight_mod
from pytorch_distributed_training_tpu.telemetry.slo import (
    BurnRateMonitor,
    SloConfig,
    burn_rate,
)
from pytorch_distributed_training_tpu.telemetry.spans import (
    REQUEST_PHASES,
    Tracer,
    spans_by_trace,
    trace_coverage,
    trace_summary,
)

pytestmark = [pytest.mark.serve, pytest.mark.obs]


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


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _registry():
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    reg = MetricsRegistry()
    sink = ListSink()
    reg.attach_sink(sink)
    return reg, sink


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


@pytest.fixture(autouse=True)
def _clean_fault_state():
    from pytorch_distributed_training_tpu.faults.inject import set_plan
    from pytorch_distributed_training_tpu.faults.watchdog import set_watchdog

    yield
    set_plan(None)
    set_watchdog(None)


def _prompts(model, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, model.config.vocab_size, n).astype(np.int32)
        for n in lengths
    ]


def _load_script(name):
    """Import a scripts/*.py module by path (scripts/ is not a package)."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", f"{name}.py"
    )
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# =====================================================================
# span plane: the tree a served request emits
# =====================================================================


def test_span_tree_tiles_request_mixed_greedy_spec(lm):
    """Every accepted request — greedy and speculative in the same batch —
    yields ONE complete span tree whose queue/prefill/decode phases tile
    submit→finish exactly (the bench's 5% reconciliation gate is met by
    construction, asserted here with zero tolerance on the stamps)."""
    model, params = lm
    reg, sink = _registry()
    prompts = _prompts(model, [4, 6, 5, 7], seed=3)
    T = 6
    server = InferenceServer(
        model, params,
        EngineConfig(
            num_slots=2, prompt_buckets=(8,), max_new_tokens=T,
            kv_layout="paged", sampling="device", spec_k=3,
        ),
        queue_depth=16, registry=reg,
    ).start()
    spec_flags = [False, True, False, True]
    tiers = ["interactive", "batch", "interactive", "batch"]
    try:
        reqs = [
            server.submit(p, max_new_tokens=T, spec=s, tier=t)
            for p, s, t in zip(prompts, spec_flags, tiers)
        ]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]

    cov = trace_coverage(sink.records, accepted_ids=[r.id for r in reqs])
    assert cov["traces"] == 4
    assert cov["coverage"] == 1.0, cov
    assert cov["orphan_spans"] == 0 and cov["incomplete"] == []
    assert cov["phase_sum_bad"] == []

    traces = spans_by_trace(sink.records)
    for req, spec, tier in zip(reqs, spec_flags, tiers):
        spans = {s["name"]: s for s in traces[req.id]}
        assert {"serve", "queue", "prefill", "decode"} <= set(spans)
        serve = spans["serve"]
        # in-process submit: no router above us, serve IS the root
        assert serve["parent"] is None
        assert serve["attrs"]["tier"] == tier
        assert serve["attrs"]["status"] == "done"
        assert "weights_step" in serve["attrs"]
        # exact tiling: each phase starts where the previous one ended
        assert spans["queue"]["t0_s"] == serve["t0_s"]
        assert spans["queue"]["t1_s"] == spans["prefill"]["t0_s"]
        assert spans["prefill"]["t1_s"] == spans["decode"]["t0_s"]
        assert spans["decode"]["t1_s"] == serve["t1_s"]
        assert trace_summary(traces[req.id])["phase_sum_ok"] is True
        # page-reservation span nests under prefill, not the root
        assert spans["admission"]["parent"] == spans["prefill"]["span"]
        assert spans["decode"]["attrs"]["tokens"] == T
        if spec:
            assert spans["decode"]["attrs"]["drafted"] > 0
            assert spans["decode"]["attrs"]["accepted"] >= 0


# =====================================================================
# router side: hedges/retries stay in ONE trace
# =====================================================================


class StubReplica:
    """Replica-shaped HTTP stub that captures the trace headers it gets.

    ``mode``: "ok" (stream then done) or "slow" (sleep ``ttfb_s`` first —
    the hedge trigger). Every POST records ``(X-Request-Id,
    X-Parent-Span)`` into ``seen`` before any behavior kicks in."""

    def __init__(self, *, mode="ok", tokens=3, ttfb_s=0.0, queue_depth=0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        stub = self
        self.mode = mode
        self.tokens = tokens
        self.ttfb_s = ttfb_s
        self.queue_depth = queue_depth
        self.seen = []
        self._seen_lock = threading.Lock()

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.0"

            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                body = (json.dumps({
                    "state": "ready", "queue_depth": stub.queue_depth,
                    "slot_occupancy": 0.0, "num_slots": 1,
                }) + "\n").encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                rid = self.headers.get("X-Request-Id", "?")
                with stub._seen_lock:
                    stub.seen.append(
                        (rid, self.headers.get("X-Parent-Span"))
                    )
                if stub.mode == "slow":
                    time.sleep(stub.ttfb_s)
                self.send_response(200)
                self.end_headers()
                for i in range(stub.tokens):
                    self.wfile.write((json.dumps({
                        "id": rid, "event": "token", "token_id": i,
                    }) + "\n").encode())
                    self.wfile.flush()
                self.wfile.write((json.dumps({
                    "id": rid, "event": "done", "status": "done",
                    "new_tokens": stub.tokens,
                }) + "\n").encode())

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        ).start()

    def close(self):
        self.httpd.shutdown()


def test_hedged_attempts_share_one_trace(lm=None):
    """A hedged request emits request→attempt→hedge spans under ONE trace
    id (the X-Request-Id), and the X-Parent-Span header each replica saw
    names exactly the router span it should parent its serve span under —
    the cross-process causality link, asserted at the wire."""
    reg, sink = _registry()
    # the empty-queue slow stub is picked first; the loaded fast one is
    # the hedge target after hedge_s with no first byte
    slow = StubReplica(mode="slow", ttfb_s=3.0, queue_depth=0)
    fast = StubReplica(mode="ok", tokens=2, queue_depth=5)
    router = Router(
        [("s0", "127.0.0.1", slow.port), ("s1", "127.0.0.1", fast.port)],
        RouterConfig(
            health_interval_s=0.03, health_timeout_s=0.5,
            breaker_threshold=3, breaker_cooldown_s=0.25,
            retry_backoff_s=0.01, retry_backoff_max_s=0.05,
            ttfb_timeout_s=5.0, hedge_s=0.1,
        ),
        registry=reg,
    ).start()
    try:
        assert wait_until(
            lambda: router.available_count() >= 2, timeout=5
        ), router.stats()
        lines = []
        out = router.route_generate(
            json.dumps({"prompt": "x"}).encode(), "obs-hedge-1",
            lambda b: lines.append(json.loads(b)),
        )
        assert out["status"] == "ok" and out["hedged"] is True
    finally:
        router.close()
        slow.close()
        fast.close()

    traces = spans_by_trace(sink.records)
    assert list(traces) == ["obs-hedge-1"]   # hedge did NOT fork a trace
    spans = {s["name"]: s for s in traces["obs-hedge-1"]}
    assert {"request", "attempt", "hedge"} <= set(spans)
    summary = trace_summary(traces["obs-hedge-1"])
    assert summary["complete"] is True and summary["roots"] == 1
    assert spans["request"]["parent"] is None
    assert spans["attempt"]["parent"] == spans["request"]["span"]
    assert spans["hedge"]["parent"] == spans["attempt"]["span"]
    assert spans["request"]["attrs"]["hedged"] is True

    # the wire contract: the primary carried the attempt span id, the
    # hedge carried the hedge span id, both under the same request id
    assert slow.seen == [("obs-hedge-1", spans["attempt"]["span"])]
    assert fast.seen == [("obs-hedge-1", spans["hedge"]["span"])]


# =====================================================================
# flight recorder: ring bounds + post-mortem dumps
# =====================================================================


def test_flight_recorder_ring_dump_and_registry():
    reg, sink = _registry()
    fr = FlightRecorder(4, component="unit", registry=reg)
    for i in range(10):
        fr.record(tick=i, payload=i * 2)
    snap = fr.snapshot()
    assert [e["seq"] for e in snap] == [7, 8, 9, 10]   # bounded, newest
    assert snap[-1] == {"seq": 10, "tick": 9, "payload": 18}

    rec = fr.dump("unit_test", attrs={"extra": 1})
    assert rec["record"] == "flight_dump" and rec["reason"] == "unit_test"
    assert rec["depth"] == 4 and rec["dropped"] == 6 and rec["extra"] == 1
    assert rec["entries"][-1]["tick"] == 9
    assert sink.of("flight_dump")[-1]["reason"] == "unit_test"
    assert fr.stats()["flight_dumps"] == 1
    assert fr.stats()["flight_last_dump"] == "unit_test"

    # process-wide hookup: registered rings answer dump_all, unregistered
    # rings are left alone (a closed server must not keep dumping)
    flight_mod.register(fr)
    try:
        assert flight_mod.dump_all("drill") >= 1
        assert fr.dumps == 2
    finally:
        flight_mod.unregister(fr)
    flight_mod.dump_all("after_unregister")
    assert fr.dumps == 2

    with pytest.raises(ValueError):
        FlightRecorder(0)


def test_watchdog_stall_dumps_flight_with_stalled_tick(lm):
    """An injected replica_hang under an installed watchdog produces a
    ``flight_dump`` whose LAST entry is the stalled tick itself — the
    acceptance criterion for the black-box: the run-up to the wedge is on
    the record, ending at the wedge."""
    from pytorch_distributed_training_tpu.faults.inject import (
        FaultPlan,
        set_plan,
    )
    from pytorch_distributed_training_tpu.faults.watchdog import (
        Watchdog,
        set_watchdog,
    )
    from pytorch_distributed_training_tpu.telemetry.registry import (
        set_registry,
    )

    model, params = lm
    reg, sink = _registry()
    prev_reg = set_registry(reg)
    server = InferenceServer(
        model, params,
        EngineConfig(num_slots=1, prompt_buckets=(8,), max_new_tokens=12),
        queue_depth=8, registry=reg,
    ).start()
    wd = None
    prev_plan = prev_wd = None
    try:
        # warm OUTSIDE the watchdog: compile ticks are slow and would
        # poison the stall threshold's history
        warm = server.submit(
            _prompts(model, [4], seed=1)[0], max_new_tokens=12
        )
        assert wait_until(warm.done.is_set, timeout=120)

        hang_tick = server.engine.busy_ticks + 3
        wd = Watchdog(stall_factor=5.0, min_stall_s=0.1, hard_timeout_s=0)
        prev_wd = set_watchdog(wd)
        prev_plan = set_plan(
            FaultPlan.parse(f"replica_hang:{hang_tick}:1.0")
        )
        req = server.submit(
            _prompts(model, [4], seed=2)[0], max_new_tokens=8
        )
        assert wait_until(req.done.is_set, timeout=120)
        assert req.status == "done"
        assert wait_until(
            lambda: sink.of("flight_dump"), timeout=10
        ), "watchdog never dumped the flight ring"
    finally:
        set_plan(prev_plan)
        if wd is not None:
            wd.close()
            set_watchdog(prev_wd)
        server.close(drain=False)
        set_registry(prev_reg)

    stalls = sink.of("watchdog_stall")
    assert stalls and stalls[0]["section"] == "serve_tick"
    dumps = [
        r for r in sink.of("flight_dump")
        if r["reason"] == "watchdog_stall"
    ]
    assert dumps, sink.of("flight_dump")
    entries = dumps[0]["entries"]
    assert entries, "dump carried an empty ring"
    # the hang fires at the END of busy tick `hang_tick`, whose flight
    # entry was recorded just before the chaos hook — so the ring's last
    # entry IS the stalled tick
    assert entries[-1]["busy_tick"] == hang_tick
    assert dumps[0]["component"] == "engine"
    # the injected fault itself is on the record too
    faults = sink.of("fault_injected")
    assert any(r.get("fault") == "replica_hang" for r in faults)


def test_debug_flight_endpoint(lm):
    """GET /debug/flight on a live replica returns the ring AND leaves a
    flight_dump record on the metrics stream (on-demand post-mortem)."""
    from pytorch_distributed_training_tpu.data.bpe import ByteTokenizer
    from pytorch_distributed_training_tpu.serve import make_http_server

    model, params = lm
    reg, sink = _registry()
    server = InferenceServer(
        model, params,
        EngineConfig(num_slots=1, prompt_buckets=(8,), max_new_tokens=4),
        queue_depth=4, registry=reg,
    ).start()
    httpd = make_http_server(server, ByteTokenizer())
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        req = server.submit(
            _prompts(model, [4], seed=5)[0], max_new_tokens=4
        )
        assert wait_until(req.done.is_set, timeout=120)
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        c.request("GET", "/debug/flight")
        resp = c.getresponse()
        assert resp.status == 200
        body = json.loads(resp.read())
        c.close()
    finally:
        httpd.shutdown()
        server.close(drain=False)
    assert body["entries"], "live engine had an empty flight ring"
    assert body["flight_dumps"] >= 1
    dumps = sink.of("flight_dump")
    assert any(r["reason"] == "debug_endpoint" for r in dumps)


# =====================================================================
# SLO burn rates: window math under an injected clock
# =====================================================================


def test_burn_rate_formula():
    assert burn_rate(100, 100, 0.99) == 0.0
    assert burn_rate(99, 100, 0.99) == pytest.approx(1.0)
    assert burn_rate(90, 100, 0.99) == pytest.approx(10.0)
    # an empty window burns nothing (no traffic, no budget consumed)
    assert burn_rate(0, 0, 0.99) == 0.0


def test_burn_rate_monitor_windows_prune_and_throttle():
    clock = FakeClock(1000.0)
    reg, sink = _registry()
    mon = BurnRateMonitor(
        SloConfig(windows_s=(60.0, 600.0), deadline_objective=0.99,
                  availability_objective=0.999, emit_interval_s=5.0),
        tiers=("interactive", "batch"), registry=reg, now_fn=clock,
    )
    for _ in range(10):
        mon.observe("interactive", available=True, deadline_met=True)
    # one availability failure with NO deadline: it must not touch the
    # deadline ratio
    mon.observe("interactive", available=False, deadline_met=None)

    rates = mon.burn_rates()["interactive"]
    fast = rates["60s"]
    assert fast["requests"] == 11 and fast["deadline_requests"] == 10
    assert fast["deadline_met"] == 1.0 and fast["deadline_burn"] == 0.0
    assert fast["availability"] == pytest.approx(10 / 11)
    assert fast["availability_burn"] == pytest.approx(
        (1 / 11) / 0.001
    )
    assert mon.max_burn() == pytest.approx((1 / 11) / 0.001)
    # the untouched tier reads zero, not missing
    assert mon.burn_rates()["batch"]["60s"]["requests"] == 0
    assert mon.burn_rates()["batch"]["60s"]["availability_burn"] == 0.0

    # past the fast window: the failure ages out of 60s but still burns
    # the 600s budget
    clock.t += 120.0
    rates = mon.burn_rates()["interactive"]
    assert rates["60s"]["requests"] == 0
    assert rates["60s"]["availability_burn"] == 0.0
    assert rates["600s"]["requests"] == 11
    assert rates["600s"]["availability_burn"] > 0.0

    # past the longest window: everything pruned, all burns zero
    clock.t += 600.0
    rates = mon.burn_rates()["interactive"]
    assert rates["600s"]["requests"] == 0
    assert rates["600s"]["availability"] is None
    assert mon.max_burn() == 0.0

    # emission throttle: the 11 rapid observes above emitted exactly once
    # (queries never emit); the first observe past emit_interval_s does
    assert len(sink.of("slo_burn")) == 1
    mon.observe("interactive", available=True)
    burns = sink.of("slo_burn")
    assert len(burns) == 2
    assert burns[-1]["windows_s"] == [60.0, 600.0]
    assert "interactive" in burns[-1]["tiers"]
    assert reg.snapshot()["gauges"]["slo/max_burn"] == burns[-1]["max_burn"]

    # unknown tiers fold into the first tier instead of KeyError-ing the
    # serve path
    mon.observe("mystery", available=True)
    assert mon.stats()["slo_observed"] == 13


def test_slo_coupling_is_default_off_and_opt_in():
    """The burn-rate monitor is PLUMBED into the brownout ladder and the
    autoscaler but acts only when slo_burn_high > 0 — default-off keeps
    pre-obs policy (and the storm bench) byte-identical."""
    import types

    from pytorch_distributed_training_tpu.serve.autoscale import (
        Autoscaler,
        AutoscaleConfig,
    )
    from pytorch_distributed_training_tpu.serve.queue import (
        BrownoutController,
    )

    burning = types.SimpleNamespace(max_burn=lambda now=None: 50.0)

    # ---- brownout: default off -> burning monitor moves nothing
    clock = FakeClock()
    reg, _sink = _registry()
    br = BrownoutController(
        high_watermark=0.8, low_watermark=0.3,
        escalate_hold_s=0.5, deescalate_hold_s=0.5,
        now_fn=clock, registry=reg, slo_monitor=burning,
    )
    for _ in range(5):
        br.observe(0.0)
        clock.t += 1.0
    assert br.level == 0

    # ---- brownout: opted in -> burn escalates despite an empty queue
    br2 = BrownoutController(
        high_watermark=0.8, low_watermark=0.3,
        escalate_hold_s=0.5, deescalate_hold_s=0.5,
        now_fn=clock, registry=reg, slo_monitor=burning, slo_burn_high=2.0,
    )
    br2.observe(0.0)
    clock.t += 0.6
    br2.observe(0.0)
    assert br2.level == 1
    # burn subsides -> the ladder comes back down on queue pressure alone
    burning.max_burn = lambda now=None: 0.0
    br2.observe(0.0)
    clock.t += 0.6
    br2.observe(0.0)
    assert br2.level == 0

    # ---- autoscaler: the burn signal is visible either way, acted on
    # only when opted in
    class View:
        def __init__(self, name):
            self.name = name
            self.breaker = types.SimpleNamespace(state="closed")
            self.health = {"queue_depth": 0.0, "page_occupancy": 0.0}

        def available(self):
            return True

    class FakeFleet:
        def __init__(self):
            self.router = types.SimpleNamespace(replicas=[View("r0")])
            self.replicas = [types.SimpleNamespace(name="r0", state="up")]
            self.ups = 0

        def scale_up(self):
            self.ups += 1
            v = View(f"r{self.ups}")
            self.router.replicas.append(v)
            proc = types.SimpleNamespace(name=v.name, state="up")
            self.replicas.append(proc)
            return proc

        def retire_replica(self):
            return None

    hot = types.SimpleNamespace(max_burn=lambda now=None: 10.0)
    clock2 = FakeClock()

    off = Autoscaler(
        FakeFleet(), AutoscaleConfig(up_hold_s=1.0, up_cooldown_s=5.0),
        now_fn=clock2, registry=reg, slo_monitor=hot,
    )
    assert off.signals()["slo_burn"] == 10.0   # visible in telemetry
    for _ in range(5):
        assert off.step() is None              # ...but never acted on
        clock2.t += 1.0

    on_fleet = FakeFleet()
    on = Autoscaler(
        on_fleet,
        AutoscaleConfig(up_hold_s=1.0, up_cooldown_s=5.0,
                        slo_burn_high=3.0),
        now_fn=clock2, registry=reg, slo_monitor=hot,
    )
    assert on.step() is None                   # onset: hold starts
    clock2.t += 1.1
    assert on.step() == "up"                   # burn alone scaled the pool
    assert on_fleet.ups == 1


# =====================================================================
# renderers: trace_view waterfall golden + summarize sections
# =====================================================================


def _synthetic_stream():
    """One complete trace, one orphan trace, one slo_burn, one
    flight_dump — deterministic via injected tracer clocks."""
    reg, sink = _registry()
    tr = Tracer(registry=reg, component="engine",
                now_fn=lambda: 100.0, wall_fn=lambda: 1000.0)
    serve = tr.begin("req-g", "serve", t0=0.0,
                     attrs={"tier": "interactive"})
    q = tr.begin("req-g", "queue", parent=serve.span, t0=0.0)
    tr.end(q, t1=0.2, attrs={"tier": "interactive"})
    p = tr.begin("req-g", "prefill", parent=serve.span, t0=0.2)
    tr.end(p, t1=0.5, attrs={"bucket": 16})
    d = tr.begin("req-g", "decode", parent=serve.span, t0=0.5)
    tr.end(d, t1=1.0, attrs={"tokens": 8})
    tr.end(serve, t1=1.0)
    # an orphan: its parent span id never appears in the stream (an
    # unmerged replica file, or a dropped root)
    lost = tr.begin("req-lost", "serve", parent="router-gone-1", t0=0.0)
    tr.end(lost, t1=0.3)

    clock = FakeClock(1000.0)
    mon = BurnRateMonitor(
        SloConfig(windows_s=(60.0, 600.0)), tiers=("interactive",),
        registry=reg, now_fn=clock,
    )
    for ok in (True, True, True, False):
        mon.observe("interactive", available=ok, deadline_met=ok)
    mon.emit_now()   # the throttled observes above emitted only once

    fr = FlightRecorder(8, component="engine", registry=reg)
    for i in range(3):
        fr.record(tick=i, busy_tick=i)
    fr.dump("unit_test")
    return sink.records


GOLDEN_WATERFALL = """\
trace req-g: 4 span(s), complete, phases ok (1000.0ms of 1000.0ms serve)
  serve                    engine       +     0.0ms    1000.0ms  tier=interactive
    queue                  engine       +     0.0ms     200.0ms  tier=interactive
    prefill                engine       +   200.0ms     300.0ms  bucket=16
    decode                 engine       +   500.0ms     500.0ms  tokens=8"""


def test_trace_view_waterfall_golden():
    tv = _load_script("trace_view")
    records = _synthetic_stream()
    assert tv.render_waterfall(records, "req-g") == GOLDEN_WATERFALL

    # the orphan trace renders its spans under the orphans heading and is
    # flagged INCOMPLETE instead of silently vanishing
    lost = tv.render_waterfall(records, "req-lost")
    assert "INCOMPLETE" in lost.splitlines()[0]
    assert "orphans (parent span not in merged streams):" in lost
    assert "parent=router-gone-1" in lost

    assert "no spans found" in tv.render_waterfall(records, "nope")

    listing = tv.render_trace_list(records)
    assert "req-g" in listing and "req-lost" in listing
    assert "complete" in listing and "INCOMPLETE" in listing


def test_trace_view_timeline_orders_fleet_events():
    tv = _load_script("trace_view")
    records = _synthetic_stream() + [
        {"record": "fleet_scale", "ts": 10.0, "action": "up",
         "replica": "r1", "size": 2},
        {"record": "brownout_transition", "ts": 12.5, "from": 0, "to": 1,
         "level": 1},
    ]
    out = tv.render_timeline(records)
    lines = out.splitlines()
    assert lines[0] == "fleet timeline:"
    # sink-timestamp order, relative offsets from the first event
    scale = next(l for l in lines if "fleet_scale" in l)
    brown = next(l for l in lines if "brownout_transition" in l)
    assert "action=up replica=r1 size=2" in scale
    assert "from=0 to=1 level=1" in brown
    assert lines.index(scale) < lines.index(brown)
    # slo_burn + flight_dump from the synthetic stream are events too
    assert any("slo_burn" in l for l in lines)
    assert any("flight_dump" in l for l in lines)
    assert any(l.startswith("traces: 2 (1 complete)") for l in lines)


def test_trace_view_load_dir_merges_and_skips_torn_lines(tmp_path):
    tv = _load_script("trace_view")
    records = _synthetic_stream()
    split = len(records) // 2
    (tmp_path / "replica-0").mkdir()
    with open(tmp_path / "metrics.jsonl", "w") as f:
        for r in records[:split]:
            f.write(json.dumps(r) + "\n")
        f.write('{"record": "span", "torn')   # crashed writer's last line
    with open(tmp_path / "replica-0" / "metrics.jsonl", "w") as f:
        for r in records[split:]:
            f.write(json.dumps(r) + "\n")
    merged = tv.load_dir(str(tmp_path))
    assert len(merged) == len(records)        # torn line skipped, rest kept
    assert trace_summary(
        spans_by_trace(merged)["req-g"]
    )["complete"] is True
    with pytest.raises(FileNotFoundError):
        tv.load_dir(str(tmp_path / "replica-0" / "nothing-here"))


def test_summarize_metrics_obs_sections(tmp_path):
    stream = tmp_path / "metrics.jsonl"
    with open(stream, "w") as f:
        for r in _synthetic_stream():
            f.write(json.dumps(r) + "\n")

    proc = subprocess.run(
        [sys.executable, "scripts/summarize_metrics.py", str(stream),
         "--json"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(proc.stdout)

    spans = data["spans"]
    assert spans["traces"] == 2 and spans["complete_traces"] == 1
    assert spans["incomplete_traces"] == 1 and spans["orphan_spans"] == 1
    assert spans["coverage"] == 0.5
    assert spans["components"] == ["engine"]
    tiers = spans["tiers"]["interactive"]
    assert set(tiers) == set(REQUEST_PHASES)
    assert tiers["queue"]["p50"] == pytest.approx(0.2)
    assert tiers["decode"]["p95"] == pytest.approx(0.5)

    slo = data["slo"]
    assert slo["emissions"] >= 1
    assert slo["deadline_objective"] == 0.99
    assert slo["max_burn"] == slo["peak_burn"] > 1.0
    fast = slo["tiers"]["interactive"]["60s"]
    assert fast["requests"] == 4 and fast["deadline_met"] == 0.75

    flight = data["flight"]
    assert flight["dumps"] == 1
    assert flight["by_reason"] == {"unit_test": 1}
    assert flight["detail"][0]["last_tick"] == 2

    # the text table carries all three sections
    proc = subprocess.run(
        [sys.executable, "scripts/summarize_metrics.py", str(stream)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "spans:" in proc.stdout and "[INCOMPLETE]" in proc.stdout
    assert "slo:" in proc.stdout and "[BURNING]" in proc.stdout
    assert "flight-dumps: 1 (unit_test=1)" in proc.stdout


# =====================================================================
# subprocess drill: trace context across the process boundary + SIGTERM
# =====================================================================

REPLICA_ARGS = (
    "--model", "gpt2-tiny", "--num-slots", "2",
    "--prompt-buckets", "16,32", "--max-new-tokens-cap", "64",
    "--queue-depth", "16", "--stall-timeout-s", "10",
)


def test_fleet_trace_merges_across_processes_and_sigterm_dumps(tmp_path):
    """End-to-end X-Request-Id contract with a REAL replica: the router's
    request/attempt spans (coordinator stream) and the replica's
    serve/queue/prefill/decode spans (its own metrics dir) merge into ONE
    complete tree keyed by the client's request id, with the serve span
    parented under the router's attempt via the X-Parent-Span header.
    Then SIGTERM: the drain path dumps the replica's flight ring to the
    same on-disk stream."""
    from pytorch_distributed_training_tpu.serve.fleet import (
        FleetConfig,
        ServeFleet,
    )
    from pytorch_distributed_training_tpu.serve.router import (
        make_router_http_server,
    )

    tv = _load_script("trace_view")
    reg, sink = _registry()
    fleet = ServeFleet(
        FleetConfig(
            num_replicas=1,
            replica_args=REPLICA_ARGS,
            replica_extra_args={0: (
                "--metrics-dir", str(tmp_path / "replica-0"),
                "--replica-name", "replica-0",
            )},
            max_restarts=1,
            backoff_s=0.2,
            drain_timeout_s=20.0,
        ),
        RouterConfig(
            health_interval_s=0.05, health_timeout_s=1.0,
            breaker_threshold=3, breaker_cooldown_s=0.5,
            retry_backoff_s=0.02, retry_backoff_max_s=0.1,
            ttfb_timeout_s=60.0,
        ),
        registry=reg,
    ).start()
    httpd = None
    rid = "obs-e2e-1"
    try:
        assert fleet.wait_ready(timeout=120), fleet.stats()
        httpd = make_router_http_server(fleet.router)
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever, daemon=True).start()

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": "trace me", "max_new_tokens": 6}),
            headers={"X-Request-Id": rid},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        events = [json.loads(l) for l in resp.read().decode().splitlines()]
        conn.close()
        assert events[-1]["event"] == "done", events[-3:]

        fleet.replica(0).sigterm()
        assert wait_until(
            lambda: len(sink.of("replica_exit")) >= 1, timeout=60
        )
        exits = sink.of("replica_exit")
        assert exits[0]["graceful"] is True and exits[0]["rc"] == 75
    finally:
        if httpd is not None:
            httpd.shutdown()
        fleet.stop(drain=False)

    # merge the coordinator's in-memory stream with the replica's on-disk
    # one — exactly what trace_view does for a fleet metrics dir
    with open(tmp_path / "metrics.jsonl", "w") as f:
        for r in sink.records:
            f.write(json.dumps(r) + "\n")
    merged = tv.load_dir(str(tmp_path))

    traces = spans_by_trace(merged)
    assert rid in traces, sorted(traces)
    spans = {s["name"]: s for s in traces[rid]}
    assert {"request", "attempt", "serve", "queue", "prefill",
            "decode"} <= set(spans)
    summary = trace_summary(traces[rid])
    assert summary["complete"] is True, summary
    assert summary["phase_sum_ok"] is True, summary
    # the cross-process link: the replica's serve span hangs under the
    # router-generated attempt span id it got over HTTP
    assert spans["serve"]["parent"] == spans["attempt"]["span"]
    assert spans["serve"]["component"] == "replica-0"
    assert spans["request"]["component"] == "router"

    waterfall = tv.render_waterfall(merged, rid)
    assert "complete" in waterfall.splitlines()[0]

    # the preemption black box: SIGTERM drain dumped the replica's ring
    # into its own stream before exit 75
    replica_records = tv.load_file(
        str(tmp_path / "replica-0" / "metrics.jsonl")
    )
    dumps = [
        r for r in replica_records
        if r.get("record") == "flight_dump"
        and r.get("reason") == "sigterm_drain"
    ]
    assert dumps, [r.get("record") for r in replica_records][-20:]
    assert dumps[0]["entries"], "drain dump carried an empty ring"


# =====================================================================
# phases: the engine tick, the set-up path, the profiler's clock
# =====================================================================


def _paged_server(lm, reg, **cfg):
    model, params = lm
    cfg.setdefault("num_slots", 2)
    return InferenceServer(
        model, params,
        EngineConfig(
            prompt_buckets=(8, 16), max_new_tokens=6, kv_layout="paged",
            sampling="device", **cfg,
        ),
        queue_depth=16, registry=reg,
    ).start()


def _serve_all(server, prompts, new_tokens=6):
    """``new_tokens``: one count for all, or one a prompt."""
    if isinstance(new_tokens, int):
        new_tokens = [new_tokens] * len(prompts)
    try:
        reqs = [server.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, new_tokens, strict=True)]
        assert wait_until(
            lambda: all(r.done.is_set() for r in reqs), timeout=120
        ), [r.status for r in reqs]
    finally:
        server.close()
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    return reqs


def _children(phases):
    """A tick's own children: the phases no other phase encloses."""
    return [
        p for p in phases
        if not any(q is not p and q[1] <= p[1] and p[2] <= q[2]
                   for q in phases)
    ]


def test_tick_phases_tile_every_busy_tick_and_name_their_requests(lm):
    """Every busy tick is ONE ``serve_tick`` record whose children are in
    order, inside the tick and tile it: what they leave uncovered is the
    phase boundaries' own cost (1% of a tick at the chip's 138 ms; on the
    CPU's few milliseconds, 30 us a boundary). A request's ``prefill``
    span names the tick whose ``prefill`` phase names the request."""
    model, _ = lm
    reg, sink = _registry()
    server = _paged_server(lm, reg)
    # answers of unequal length: a slot comes free, and is filled again,
    # while the other's step is in flight
    reqs = _serve_all(server, _prompts(model, [4, 6, 5, 12, 7], seed=4),
                      new_tokens=[6, 4, 5, 6, 3])
    ticks = sink.of("serve_tick")
    assert len(ticks) == server.engine.busy_ticks > 5
    assert [t["tick"] for t in ticks] == sorted({t["tick"] for t in ticks})
    order = ["expire", "admit", "prefill", "chunks", "operands", "dispatch",
             "publish"]
    tiled = []
    for t in ticks:
        kids = _children(t["phases"])
        assert [p[1] for p in kids] == sorted(p[1] for p in kids)
        names = [p[0] for p in kids]
        assert names[0] == "expire" and names[-1] == "publish"
        # in order, but for admit/prefill, which alternate per admission,
        # and for the retirement of the step in flight (``decode_wait``,
        # ``emit``): after the dispatch where the tick overlapped, before
        # the prefill that would block it (or alone) where it did not
        ranks = [order.index(n) for n in names
                 if n not in ("admit", "prefill", "decode_wait", "emit")]
        assert ranks == sorted(ranks), names
        assert names.count("decode_wait") == names.count("emit") <= 1
        if "decode_wait" in names:
            at = names.index("decode_wait")
            assert names[at + 1] == "emit"
            if t["overlapped"]:
                assert names[at - 1] == "dispatch"
            else:
                assert "dispatch" not in names[:at]
                assert names[at + 2] in ("prefill", "chunks", "publish")
        else:
            assert not t["overlapped"]
        cursor = t["t0_s"]
        for name, t0, t1, *_ in kids:
            assert cursor <= t0 <= t1 <= t["t1_s"], (name, t)
            cursor = t1
        dur = t["t1_s"] - t["t0_s"]
        uncovered = dur - sum(p[2] - p[1] for p in kids)
        tiled.append(
            uncovered <= max(0.01 * dur, 30e-6 * (len(kids) + 1)))
        if t["decode_active"]:
            assert {"operands", "dispatch"} <= set(names)
        assert t["admitted"] == names.count("prefill")
    # all but a tick or two: another thread may take the interpreter for a
    # moment between two phases, and that moment is nobody's
    assert sum(tiled) >= 0.75 * len(ticks), tiled
    assert sum(t["admitted"] for t in ticks) == len(reqs)
    assert sum(t["prefill_tokens"] for t in ticks) == sum(
        r.prompt_len for r in reqs)

    # both directions: request span -> tick, tick phase -> request
    traces = spans_by_trace(sink.records)
    by_tick = {t["tick"]: t for t in ticks}
    for r in reqs:
        span = next(s for s in traces[r.id] if s["name"] == "prefill")
        tick = by_tick[span["attrs"]["tick"]]
        mine = [p for p in tick["phases"] if p[0] == "prefill" and p[3] == r.id]
        assert len(mine) == 1
        assert mine[0][4] == {"bucket": r.bucket, "prompt_len": r.prompt_len}
        waits = [p for p in tick["phases"]
                 if p[0] == "prefill_wait" and p[3] == r.id]
        assert len(waits) == 1
        assert mine[0][1] <= waits[0][1] <= waits[0][2] <= mine[0][2]
        # the request's own prefill span (admit -> first token) encloses it
        assert span["t0_s"] <= mine[0][1] and waits[0][2] <= span["t1_s"]

    # the flight entries carry the same phases, in milliseconds by name:
    # every entry, so also each tick that retired the step in flight FIRST
    # (before an admission's prefill, or alone at the end of the run) or
    # had none to retire, where ``_retire`` could leave time in no phase
    overlapped = {t["tick"]: t["overlapped"] for t in ticks}
    retired_first = []
    for e in server.engine.flight.snapshot():
        assert e["phases"]["publish"] >= 0
        own = sum(v for k, v in e["phases"].items() if k != "prefill_wait")
        assert own <= e["dur_ms"] + 0.01
        assert own >= 0.9 * e["dur_ms"] - 0.3, (e, overlapped[e["tick"]])
        if not overlapped[e["tick"]] and "decode_wait" in e["phases"]:
            retired_first.append(set(e["phases"]))
    assert any("prefill" in names for names in retired_first)
    assert any("dispatch" not in names for names in retired_first)


def test_no_sink_builds_no_tick_record_and_the_ring_stays_bounded(lm):
    from pytorch_distributed_training_tpu.telemetry.registry import (
        MetricsRegistry,
    )

    model, _ = lm
    reg = MetricsRegistry()
    seen = []
    emit = reg.emit
    reg.emit = lambda rec: (seen.append(rec["record"]), emit(rec))
    server = _paged_server(lm, reg, flight_capacity=4)
    _serve_all(server, _prompts(model, [4, 6, 5], seed=6))
    assert server.engine.busy_ticks > 4
    assert "serve_request" in seen and "serve_tick" not in seen
    ring = server.engine.flight.snapshot()
    assert len(ring) == 4 and server.engine.flight.recorded > 4
    assert all("decode_wait" in e["phases"] or e["decode_active"] == 0
               for e in ring)


def test_phases_are_on_the_profilers_host_line_and_agree_with_records(
        lm, tmp_path):
    """One primitive writes both: under ``jax.profiler`` every
    ``serve_tick*`` and ``serve_setup*`` phase is an event on a line of a
    host plane, and its duration there agrees with its record's to 1 ms."""
    import glob

    import jax

    model, _ = lm
    reg, sink = _registry()
    jax.profiler.start_trace(str(tmp_path))
    try:
        server = _paged_server(lm, reg, warmup=True)
        _serve_all(server, _prompts(model, [4, 6], seed=8), new_tokens=3)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}   # name -> [(start_s, dur_s)]
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve_tick", "serve_setup")):
                    assert plane.name.startswith("/host:"), plane.name
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns / 1e9, ev.duration_ns / 1e9))
    setup = [r for r in sink.of("span") if r["component"] == "setup"]
    assert {r["name"] for r in setup} == {
        "serve_setup.engine", "serve_setup.warmup",
        "serve_setup.warmup.prefill_8", "serve_setup.warmup.prefill_16",
        "serve_setup.warmup.decode", "serve_setup.warmup.drain"}
    warmup = next(r for r in setup if r["name"] == "serve_setup.warmup")
    assert warmup["parent"] is None
    assert all(r["parent"] == warmup["span"] and r["trace"] == warmup["span"]
               for r in setup if r["name"].startswith("serve_setup.warmup."))
    # the two clocks differ by a constant: take it from the one warm-up
    [(w_start, _)] = events["serve_setup.warmup"]
    shift = w_start - warmup["t0_s"]

    def agrees(name, t0, t1):
        return any(abs(s - (t0 + shift)) < 1e-3 and abs(d - (t1 - t0)) < 1e-3
                   for s, d in events.get(name, ()))

    for r in setup:
        assert agrees(r["name"], r["t0_s"], r["t1_s"]), r["name"]
    ticks = sink.of("serve_tick")
    assert ticks
    for t in ticks:
        assert agrees("serve_tick", t["t0_s"], t["t1_s"]), t["tick"]
        for name, t0, t1, *_ in t["phases"]:
            assert agrees("serve_tick." + name, t0, t1), (t["tick"], name)


def test_spans_module_stays_jax_free():
    code = (
        "import sys\n"
        "from pytorch_distributed_training_tpu.telemetry import spans\n"
        "got = []\n"
        "with spans.Phase('outer', got.append):\n"
        "    with spans.setup_phase('inner'):\n"
        "        pass\n"
        "assert got[0].t1 >= got[0].t0 and spans.SETUP_SPANS\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_warm_start_record_splits_lowering_from_compiling(tmp_path):
    """``aot_warm_start`` times ``.lower()`` apart from ``.compile()``:
    the ``compile`` record's ``*_lower_s`` are parts of its
    ``*_compile_s`` (which keep their meaning: lower plus compile), and
    the four ``warm_start.*`` spans are the same stamps."""
    from pytorch_distributed_training_tpu.cli import train_dp

    mdir = tmp_path / "metrics"
    train_dp.main([
        "--model", "tiny", "--task", "synthetic", "--num-epochs", "1",
        "--train-size", "64", "--eval-size", "32", "--max-seq-length", "32",
        "--native-loader", "off", "--metrics-dir", str(mdir)])
    records = [json.loads(ln) for ln in open(mdir / "metrics.jsonl")]
    [c] = [r for r in records if r["record"] == "compile"]
    spans = {r["name"]: r for r in records
             if r["record"] == "span" and r["component"] == "setup"}
    assert set(spans) == {
        "warm_start", "warm_start.train.lower", "warm_start.train.compile",
        "warm_start.eval.lower", "warm_start.eval.compile"}
    for step in ("train", "eval"):
        lower = spans[f"warm_start.{step}.lower"]
        compile_ = spans[f"warm_start.{step}.compile"]
        assert 0 < c[f"{step}_lower_s"] < c[f"{step}_compile_s"]
        assert c[f"{step}_lower_s"] == pytest.approx(lower["dur_s"])
        assert c[f"{step}_compile_s"] == pytest.approx(
            lower["dur_s"] + compile_["dur_s"])
        assert lower["parent"] == compile_["parent"] == spans["warm_start"]["span"]
    assert c["compile_s"] == pytest.approx(
        c["train_compile_s"] + c["eval_compile_s"])
    assert trace_summary(list(spans.values()))["complete"] is True
