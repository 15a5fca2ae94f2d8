"""One decode step in flight across the tick boundary (``DecodeEngine.tick``):
tick k dispatches step k, fed its tokens on the device, and only then
fetches, emits and retires step k-1.

Every test drives ``engine.tick()`` by hand on the CPU over the tiny preset
of each of the three families, in the engine modes the family admits, and
holds the served streams to the SERIAL order: the same engine, every step
retired before the next tick's host work starts (``drive(..., lagged=False)``
empties ``_inflight`` after each tick, so every slot is ``fresh`` and every
token goes host -> device, as in an engine that keeps nothing in flight).
"""

import importlib
import os
import sys

import jax
import numpy as np
import pytest

from pytorch_distributed_training_tpu.serve import (
    EngineConfig,
    InferenceServer,
)
from pytorch_distributed_training_tpu.telemetry.registry import MetricsRegistry
from pytorch_distributed_training_tpu.utils.config import model_preset

pytestmark = [pytest.mark.serve]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, BUCKET, NEW = 4, 24, 9
#: engine modes by family: the hybrid refuses the prefix cache
MODES = {
    "bucket": dict(),
    "chunked": dict(prefill_chunk=8),
    "prefix": dict(prefix_cache=True),
}
CASES = [
    (preset, mode)
    for preset in ("gpt2-tiny", "latent-moe-tiny", "sambay-tiny")
    for mode in MODES
    if not (preset == "sambay-tiny" and mode == "prefix")
]
IDS = [f"{preset}-{mode}" for preset, mode in CASES]


@pytest.fixture(scope="module", autouse=True)
def dispatch_counts_left_as_found():
    """The process-wide dispatch counts are other files' evidence (a
    lowering test reads that no call took the formula): this file's CPU
    engines leave them as they were."""
    from pytorch_distributed_training_tpu.ops import dispatch

    before = dict(dispatch.DISPATCH_PATHS)
    yield
    dispatch.DISPATCH_PATHS.clear()
    dispatch.DISPATCH_PATHS.update(before)


@pytest.fixture(scope="module")
def families():
    """(model, params) of each tiny preset, float32, random weights."""
    from pytorch_distributed_training_tpu.models import latent_moe, sambay
    from pytorch_distributed_training_tpu.models.gpt2 import GPT2LMModel

    own = {latent_moe.LatentMoEConfig: latent_moe.LatentMoELM,
           sambay.SambaYConfig: sambay.SambaYLM}
    out = {}
    for preset in ("gpt2-tiny", "latent-moe-tiny", "sambay-tiny"):
        mcfg = model_preset(preset)
        model = own.get(type(mcfg), GPT2LMModel)(mcfg)
        init = jax.jit(lambda key, model=model: model.init(
            key, np.ones((1, 8), np.int32))["params"])
        out[preset] = (model, init(jax.random.key(3)))
    return out


class Records:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(dict(record))

    def flush(self, **kw):
        pass

    def ticks(self):
        return [r for r in self.records if r.get("record") == "serve_tick"]


def make(families, preset, mode, *, slots=3, **kw):
    model, params = families[preset]
    sink = Records()
    registry = MetricsRegistry()
    registry.attach_sink(sink)
    config = EngineConfig(
        num_slots=slots, prompt_buckets=(BUCKET,), max_new_tokens=16,
        page_size=PAGE, **MODES[mode], **kw)
    server = InferenceServer(model, params, config, registry=registry,
                             queue_depth=32)
    return server, sink


def prompts(n=5, seed=11):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, 500, 12).astype(np.int32)
    out = []
    for k, size in enumerate((19, 7, 23, 12, 16)[:n]):
        p = rng.integers(1, 500, size).astype(np.int32)
        if k % 2 == 0:
            # a common run of three pages: the prefix engines hit on it
            p[:12] = shared[:min(12, size)]
        out.append(p)
    return out


def drive(server, requests=(), *, lagged=True, limit=4000):
    """Tick until the engine has no work. ``lagged=False`` is the serial
    order: what a tick dispatched is retired before the next tick."""
    engine = server.engine
    n = 0
    while engine.has_work() or not all(r.done.is_set() for r in requests):
        engine.tick()
        if not lagged:
            engine._retire({"discarded": 0})
        n += 1
        assert n < limit, "the engine never ran out of work"
    return n


def serve_all(families, preset, mode, sampling, *, lagged):
    server, sink = make(families, preset, mode)
    try:
        reqs = [
            server.submit(p, max_new_tokens=NEW - k, seed=40 + k, **sampling)
            for k, p in enumerate(prompts())
        ]
        drive(server, reqs, lagged=lagged)
        stats = server.stats()
    finally:
        server.close(drain=False)
    assert all(r.status == "done" for r in reqs), [r.status for r in reqs]
    return [list(r.tokens) for r in reqs], stats, sink


@pytest.mark.parametrize("sampling", [
    dict(), dict(temperature=0.9, top_k=12)], ids=["greedy", "seeded"])
@pytest.mark.parametrize("preset,mode", CASES, ids=IDS)
def test_streams_equal_the_serial_orders_token_for_token(
        families, preset, mode, sampling):
    """Five requests of unequal length on three slots (slots are reused,
    the batch thins out at the end): the lagged order serves what the
    serial order serves, and counts what it did."""
    want, _, _ = serve_all(families, preset, mode, sampling, lagged=False)
    got, stats, sink = serve_all(families, preset, mode, sampling, lagged=True)
    assert got == want
    assert [len(s) for s in got] == [NEW - k for k in range(5)]
    # stops by length waste nothing
    assert stats["discarded_slot_steps"] == 0
    ticks = [t for t in sink.ticks() if t["decode_active"]]
    assert stats["decode_overlap_share"] == pytest.approx(
        sum(t["overlapped"] for t in ticks) / len(ticks))
    # steady ticks (a step in flight before them, no prefill that fetches a
    # first token: a chunk before a prompt's last is only queued) dispatch
    # first; a tick that waited for a prefill retired first
    for before, t in zip(ticks, ticks[1:]):
        if t["tick"] != before["tick"] + 1:
            continue
        waited = any(p[0] == "prefill_wait" for p in t["phases"])
        assert t["overlapped"] == int(not waited), t
    if mode == "chunked":
        assert any(t["overlapped"] and t["chunks"] for t in ticks)
    assert sum(t["overlapped"] for t in ticks) >= len(ticks) // 4
    # every dispatched slot-step was emitted: prefills gave 5 first tokens
    assert sum(t["decode_active"] for t in ticks) == sum(map(len, got)) - 5
    # the last step of the run is retired by a tick with no active slot
    last = sink.ticks()[-1]
    assert last["decode_active"] == 0 and last["overlapped"] == 0
    assert [p[0] for p in last["phases"]][-3:] == [
        "decode_wait", "emit", "publish"]


@pytest.mark.parametrize("preset,mode", CASES, ids=IDS)
def test_end_of_text_costs_one_discarded_step_and_frees_the_slot(
        families, preset, mode):
    """A request ending on ``eot_id`` emits nothing after it and frees its
    slot; the step dispatched before the end was seen is discarded; the
    request admitted into that slot serves its own reference stream."""
    a, b = prompts(2)
    (ref_a, ref_b), _, _ = serve_all_two(families, preset, mode, a, b)
    # an id A's reference stream (sampled: greedy on random weights repeats
    # itself) holds in its middle and not before ends it
    cut = next(k for k in range(2, len(ref_a) - 1)
               if ref_a[k] not in ref_a[:k])
    server, sink = make(families, preset, mode, slots=1)
    try:
        ra = server.submit(a, max_new_tokens=NEW, eot_id=ref_a[cut], **SAMPLED)
        rb = server.submit(b, max_new_tokens=NEW, **SAMPLED)
        drive(server, [ra, rb])
        stats = server.stats()
    finally:
        server.close(drain=False)
    assert ra.finish_reason == "eot" and list(ra.tokens) == ref_a[:cut + 1]
    assert rb.status == "done" and list(rb.tokens) == ref_b
    assert stats["discarded_slot_steps"] == 1
    assert sum(t["discarded"] for t in sink.ticks()) == 1
    ticks = sink.ticks()
    assert sum(t["decode_active"] for t in ticks) == (
        len(ra.tokens) - 1 + len(rb.tokens) - 1 + 1)


SAMPLED = dict(temperature=1.0, top_k=40, seed=5)


def serve_all_two(families, preset, mode, a, b):
    server, sink = make(families, preset, mode, slots=1)
    try:
        reqs = [server.submit(p, max_new_tokens=NEW, **SAMPLED)
                for p in (a, b)]
        drive(server, reqs, lagged=False)
        stats = server.stats()
    finally:
        server.close(drain=False)
    return [list(r.tokens) for r in reqs], stats, sink


def accounted(server, sink, requests):
    """No id unaccounted: every dispatched slot-step was emitted or
    counted as discarded, nothing is in flight, no waiter hangs."""
    engine = server.engine
    assert engine._inflight is None and not engine.has_work()
    assert all(r.done.is_set() for r in requests)
    dispatched = sum(t["decode_active"] for t in sink.ticks())
    emitted = sum(r.decode_ticks for r in requests)
    assert dispatched == emitted + engine.discarded_slot_steps
    assert engine.stats()["discarded_slot_steps"] == engine.discarded_slot_steps


@pytest.mark.parametrize("preset", ["gpt2-tiny", "latent-moe-tiny",
                                    "sambay-tiny"])
def test_deadline_eviction_with_a_step_in_flight(families, preset):
    server, sink = make(families, preset, "chunked")
    engine = server.engine
    try:
        a, b = prompts(2)
        doomed = server.submit(a, max_new_tokens=NEW, deadline_s=3600.0)
        kept = server.submit(b, max_new_tokens=NEW)
        while len(doomed.tokens) < 3:
            engine.tick()
        assert engine._inflight is not None
        doomed.deadline_s = 0.0     # overdue at the next tick's expire
        doomed.submit_t -= 1.0
        n = len(doomed.tokens)
        drive(server, [doomed, kept])
        assert doomed.status == "expired" and len(doomed.tokens) == n
        assert kept.status == "done" and len(kept.tokens) == NEW
        assert engine.discarded_slot_steps == 1
        accounted(server, sink, [doomed, kept])
    finally:
        server.close(drain=False)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "latent-moe-tiny",
                                    "sambay-tiny"])
def test_cancel_all_with_a_step_in_flight(families, preset):
    server, sink = make(families, preset, "bucket")
    engine = server.engine
    try:
        reqs = [server.submit(p, max_new_tokens=NEW) for p in prompts(3)]
        while min(len(r.tokens) for r in reqs) < 3:
            engine.tick()
        assert engine._inflight is not None
        emitted = [len(r.tokens) for r in reqs]
        engine.cancel_all()
        assert [r.status for r in reqs] == ["cancelled"] * 3
        assert [len(r.tokens) for r in reqs] == emitted
        assert engine.discarded_slot_steps == 3
        assert engine.tick() is False       # nothing left to retire
        accounted(server, sink, reqs)
    finally:
        server.close(drain=False)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "latent-moe-tiny",
                                    "sambay-tiny"])
def test_swap_retires_the_step_in_flight_before_the_trial_opens(
        families, preset):
    """A swap to the SAME weights with a step in flight: the streams are
    the serial order's, the swap tick retired first (old weights) and saw its own ids
    before it committed, and went back to one step in flight after."""
    model, params = families[preset]
    want, _, _ = serve_all_two(families, preset, "bucket", *prompts(2))
    server, sink = make(families, preset, "bucket", slots=2)
    engine = server.engine
    try:
        reqs = [server.submit(p, max_new_tokens=NEW, **SAMPLED)
                for p in prompts(2)]
        while min(len(r.tokens) for r in reqs) < 3:
            engine.tick()
        assert engine._inflight is not None
        ticket = engine.request_swap(params, 7)
        before = len(sink.ticks())
        engine.tick()
        assert ticket.done.is_set() and ticket.ok and engine.swaps == 1
        swap_tick = sink.ticks()[before]
        assert swap_tick["overlapped"] == 0
        names = [p[0] for p in swap_tick["phases"]]
        # retired (old weights), dispatched (new), retired its own step
        assert names.index("decode_wait") < names.index("dispatch")
        assert names.count("decode_wait") == 2 and names.count("emit") == 2
        assert engine._inflight is None
        engine.tick()
        assert sink.ticks()[before + 1]["overlapped"] == 0
        engine.tick()
        assert sink.ticks()[before + 2]["overlapped"] == 1
        drive(server, reqs)
        assert [list(r.tokens) for r in reqs] == want
        assert engine.discarded_slot_steps == 0
        accounted(server, sink, reqs)
    finally:
        server.close(drain=False)


def test_a_failing_retirement_before_a_swap_is_not_the_new_versions(families):
    """The step in flight ran on the old weights: where its fetch fails,
    the tick raises as any tick does and the swap is neither applied nor
    rolled back."""
    model, params = families["gpt2-tiny"]
    server, sink = make(families, "gpt2-tiny", "bucket")
    engine = server.engine
    try:
        req = server.submit(prompts(1)[0], max_new_tokens=NEW)
        while len(req.tokens) < 3:
            engine.tick()
        engine.request_swap(params, 9)
        engine._inflight.out.delete()       # device_get raises on it
        with pytest.raises(Exception):
            engine.tick()
        assert engine.swap_rollbacks == 0 and engine.swaps == 0
        assert engine._trial is None and engine._pending_swap is not None
        # the slot-step is given back: the next tick installs the swap and
        # dispatches the slot again from its last emitted token
        assert engine._inflight is None and engine.discarded_slot_steps == 1
        drive(server, [req])
        assert req.status == "done" and len(req.tokens) == NEW
        assert engine.swaps == 1 and engine.discarded_slot_steps == 1
    finally:
        server.close(drain=False)


@pytest.mark.parametrize("preset", ["gpt2-tiny", "latent-moe-tiny",
                                    "sambay-tiny"])
def test_a_failed_fetch_on_the_trial_tick_hangs_no_waiter(
        families, preset, monkeypatch):
    """The trial tick dispatches on the new weights and fetches its own ids
    before it commits. Where that fetch fails the swap is rolled back, the
    slot-steps dispatched are given back (counted as discarded, the slots
    fresh again), and the next tick serves them on the old weights from
    their last emitted token: every request finishes."""
    model, params = families[preset]
    want, _, _ = serve_all_two(families, preset, "bucket", *prompts(2))
    server, sink = make(families, preset, "bucket", slots=2)
    engine = server.engine
    try:
        reqs = [server.submit(p, max_new_tokens=NEW, **SAMPLED)
                for p in prompts(2)]
        while min(len(r.tokens) for r in reqs) < 3:
            engine.tick()
        assert engine._inflight is not None
        ticket = engine.request_swap(params, 7)
        fetch = jax.device_get

        def failing(tree):
            if engine._trial is not None:
                raise RuntimeError("the new weights' step failed")
            return fetch(tree)

        emitted = [len(r.tokens) for r in reqs]
        with monkeypatch.context() as patched:
            patched.setattr(jax, "device_get", failing)
            assert engine.tick() is True
        assert ticket.done.is_set() and not ticket.ok
        assert engine.swap_rollbacks == 1 and engine.swaps == 0
        # the old weights' step was retired before the trial opened; the
        # trial's own step is given back
        assert [len(r.tokens) for r in reqs] == [n + 1 for n in emitted]
        assert engine._inflight is None and engine.discarded_slot_steps == 2
        assert all(s.steps_done == s.steps_retired
                   for s in engine._slots if s is not None)
        drive(server, reqs)
        assert [r.status for r in reqs] == ["done"] * 2
        assert [len(r.tokens) for r in reqs] == [NEW] * 2
        if preset != "sambay-tiny":
            # pages are written again at the same positions; a recurrent
            # state the failed step advanced stays advanced, as it does in
            # an engine that dispatches and fetches within one tick
            assert [list(r.tokens) for r in reqs] == want
        assert engine.discarded_slot_steps == 2 and not engine.has_work()
    finally:
        server.close(drain=False)


def test_speculation_keeps_its_own_synchronous_path(families):
    model, params = families["gpt2-tiny"]
    sink = Records()
    registry = MetricsRegistry()
    registry.attach_sink(sink)
    server = InferenceServer(model, params, EngineConfig(
        num_slots=2, prompt_buckets=(BUCKET,), max_new_tokens=16,
        page_size=PAGE, spec_k=2), registry=registry)
    try:
        reqs = [server.submit(p, max_new_tokens=NEW, **SAMPLED)
                for p in prompts(2)]
        engine = server.engine
        while not all(r.done.is_set() for r in reqs):
            engine.tick()
            assert engine._inflight is None
        stats = server.stats()
    finally:
        server.close(drain=False)
    assert stats["decode_overlap_share"] is None
    assert stats["discarded_slot_steps"] == 0
    assert all(t["overlapped"] == 0 for t in sink.ticks())
    want, _, _ = serve_all_two(families, "gpt2-tiny", "bucket", *prompts(2))
    assert [list(r.tokens) for r in reqs] == want


def test_no_knob_for_the_order():
    """The plain decode path has one order: no configuration field, no
    command-line option and no environment variable names it."""
    import dataclasses

    from pytorch_distributed_training_tpu.cli import serve_lm

    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    assert not {f for f in fields if "overlap" in f or "lag" in f
                or "flight" in f.replace("flight_capacity", "")}
    options = {a.dest for a in serve_lm.build_parser()._actions}
    assert not {o for o in options if "overlap" in o or "lagged" in o}
    with open(os.path.join(
            REPO, "pytorch_distributed_training_tpu/serve/engine.py")) as f:
        assert "os.environ" not in f.read()


# ------------------------------------ the benchmark's readers on the records

def _spans():
    """`benchmarks/harness/spans.py`, imported as the benchmark does."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        return importlib.import_module("harness.spans")
    finally:
        sys.path.remove(os.path.join(REPO, "benchmarks"))


def _parents_record():
    """A plain decode tick as the parent of this order wrote it: the wait
    follows ITS OWN dispatch, no ``overlapped``, no ``discarded``."""
    t = 100.0
    phases = [["expire", t, t + 1e-4, None], ["admit", t + 1e-4, t + 2e-4, None],
              ["chunks", t + 2e-4, t + 3e-4, None],
              ["operands", t + 3e-4, t + 1.3e-3, None],
              ["dispatch", t + 1.3e-3, t + 1.8e-3, None],
              ["decode_wait", t + 1.8e-3, t + 5.8e-3, None],
              ["emit", t + 5.8e-3, t + 6.0e-3, None],
              ["publish", t + 6.0e-3, t + 6.4e-3, None]]
    return {"record": "serve_tick", "tick": 7, "busy_tick": 7, "t0_s": t,
            "t1_s": t + 6.4e-3, "decode_active": 2, "admitted": 0,
            "prefill_tokens": 0, "cached_tokens": 0, "chunks": 0,
            "live_tokens": 90, "phases": phases}


@pytest.mark.parametrize("mode", ["bucket", "chunked", "prefix"])
def test_the_benchmarks_span_readers_take_both_orders_records(families, mode):
    """`benchmarks/harness/spans.py` on `serve_tick` records of the new
    order (overlapped ticks, retire-first ticks, a tick that only retires)
    and on one the parent wrote: a number each, never an exception."""
    spans = _spans()
    _, _, sink = serve_all(families, "gpt2-tiny", mode, {}, lagged=True)
    new = sink.ticks()
    kinds = {(bool(t["overlapped"]), bool(t["decode_active"])) for t in new}
    assert kinds == {(True, True), (False, True), (False, False)}
    for record in new + [_parents_record()]:
        intervals = spans.decode_intervals(record["phases"])
        host = spans.tick_host_s(record)
        assert isinstance(host, float) and host == host
        assert 0.0 <= host <= record["t1_s"] - record["t0_s"] + 1e-9
        assert len(intervals) == sum(
            1 for p in record["phases"] if p[0] == "dispatch")
        assert all(b >= a for a, b in intervals)
    # an overlapped tick pairs its dispatch with the wait after it (what is
    # left of the step before), as the parent's record pairs its own
    for record in [t for t in new if t["overlapped"]] + [_parents_record()]:
        (a, b), = [iv for iv, p in zip(
            spans.decode_intervals(record["phases"]),
            [p for p in record["phases"] if p[0] == "dispatch"])
            if p[3] is None]
        wait, = [p for p in record["phases"] if p[0] == "decode_wait"]
        assert b == wait[2] and a < wait[1]
    picked = spans.tick_records({"records": new + [_parents_record()]})
    assert len(picked) == sum(1 for t in new if t["decode_active"]) + 1
    assert spans.median_ms(spans.tick_host_s(r) for r in picked) > 0.0
