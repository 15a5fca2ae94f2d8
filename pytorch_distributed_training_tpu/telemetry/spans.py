"""Request-span tracing: one causal tree per request over the JSONL sink.

The serving stack's telemetry was flat per-record events (``serve_request``,
``router_request``, ...) — enough for rates and percentiles, useless for the
question "where did THIS slow request spend its time?". Spans answer it:

- **trace id** = the existing ``X-Request-Id``. The router, every replica a
  hedged/retried attempt lands on, and the engine all emit spans keyed by
  the same id, so ``scripts/trace_view.py`` can merge a fleet's metrics
  streams into one waterfall per request.
- **span** = one named phase with a parent span id, ``time.monotonic()``
  start/end stamps (durations are exact within a process) and wall-clock
  stamps derived at emit time (cross-process alignment is approximate —
  good enough for a waterfall, never used for arithmetic).
- **phase taxonomy** (replica side): ``serve`` is the replica root
  (child of the router's ``attempt`` span when the request came through a
  router), and its children ``queue`` / ``prefill`` / ``decode`` TILE the
  request's lifetime exactly — queue is submit→admit, prefill is
  admit→first-token, decode is first-token→finish — so the per-phase sums
  reconcile against the request's measured total (the bench gate).
  ``admission`` (page reservation) nests under prefill; ``swap_overlap``
  and ``brownout_clamp`` annotate requests a weight swap or overload clamp
  touched. Router side: ``request`` (root) → ``attempt`` → ``hedge``.

``Tracer`` is thread-safe (front-end threads begin what the engine thread
ends); its one mutable counter sits behind the PR-8 named-lock registry
(``concurrency.lock``), never a raw ``threading.Lock``. The module is
deliberately jax-free: routers and fleet coordinators import it in
processes that never touch an accelerator.

``Phase`` is the one context manager every timed region of the program
goes through (``utils.profiling.annotate``, the engine tick's
``serve_tick*`` phases, the set-up path's ``setup_phase``): for the span
of its body it holds a ``jax.profiler.TraceAnnotation`` of its name — so a
profiler trace carries the phase on the clock the device's operations are
on — and stamps both ends with ``time.monotonic()``, the clock requests
and ``Tracer`` stamp; closed, it goes to its collector. The annotation
class is looked up in ``sys.modules``: a process that never imported jax
(a router) has no profiler to write to and gets the stamps alone.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import sys
import threading
import time
import uuid
from typing import Callable, Optional

from pytorch_distributed_training_tpu.analysis import concurrency

#: replica-side phases that tile a request's submit->finish interval; the
#: summarize/bench reconciliation sums exactly these against the root span
REQUEST_PHASES = ("queue", "prefill", "decode")

#: every request-span name any instrumentation site emits
SPAN_NAMES = (
    "request", "attempt", "hedge",              # router side
    "serve", "queue", "admission", "prefill",   # replica side
    "decode", "swap_overlap", "brownout_clamp",
)


@dataclasses.dataclass
class Span:
    """One live (or retroactively constructed) span; ``Tracer.end`` emits
    it as a ``span`` record and returns it closed."""

    trace: str
    span: str
    name: str
    parent: Optional[str] = None
    t0: float = 0.0                 # time.monotonic()
    t1: Optional[float] = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_s(self) -> Optional[float]:
        return None if self.t1 is None else max(0.0, self.t1 - self.t0)


class Tracer:
    """Span factory + emitter bound to one MetricsRegistry.

    ``begin``/``end`` take explicit ``t0``/``t1`` overrides so loop-
    structured phases (the engine's tick loop stamps phase boundaries on
    the request as it goes) can emit their spans retroactively with exact
    monotonic bounds; linear code takes a ``Phase``. Span ids are unique
    across processes (random
    per-tracer prefix + a counter), which is what lets a replica parent
    its ``serve`` span under a router-generated ``attempt`` span id
    carried over HTTP.
    """

    def __init__(self, *, registry=None, component: str = "",
                 now_fn=None, wall_fn=None):
        if registry is None:
            from pytorch_distributed_training_tpu.telemetry.registry import (
                get_registry,
            )

            registry = get_registry()
        self._registry = registry
        self.component = component
        self._now = now_fn if now_fn is not None else time.monotonic
        self._wall = wall_fn if wall_fn is not None else time.time
        # begin() is called from front-end threads while end() runs on the
        # engine thread: the id counter is the shared state (named lock —
        # the concurrency linter's thread-shared rule)
        self._lock = concurrency.lock("telemetry.spans")
        self._prefix = uuid.uuid4().hex[:6]
        self._seq = 0
        self.emitted = 0

    def _span_id(self) -> str:
        with self._lock:
            self._seq += 1
            n = self._seq
        head = self.component or "span"
        return f"{head}-{self._prefix}-{n}"

    def begin(self, trace: str, name: str, *, parent: Optional[str] = None,
              t0: Optional[float] = None, attrs: Optional[dict] = None,
              ) -> Span:
        return Span(
            trace=str(trace), span=self._span_id(), name=name,
            parent=parent, t0=self._now() if t0 is None else float(t0),
            attrs=dict(attrs or {}),
        )

    def end(self, span: Span, *, t1: Optional[float] = None,
            attrs: Optional[dict] = None) -> Span:
        span.t1 = self._now() if t1 is None else float(t1)
        if attrs:
            span.attrs.update(attrs)
        with self._lock:
            self.emitted += 1
        self._registry.emit(_span_record(
            trace=span.trace, span=span.span, parent=span.parent,
            name=span.name, component=self.component or None,
            t0=span.t0, t1=span.t1, attrs=span.attrs,
            mono=self._now(), wall=self._wall()))
        return span

    def event(self, trace: str, name: str, *, parent: Optional[str] = None,
              t: Optional[float] = None, attrs: Optional[dict] = None,
              ) -> Span:
        """A zero-duration marker span (e.g. a brownout clamp applied at
        admission)."""
        s = self.begin(trace, name, parent=parent, t0=t, attrs=attrs)
        return self.end(s, t1=s.t0)


# ------------------------------------------------------------------ phases

_ANNOTATION = None          # jax.profiler.TraceAnnotation, once jax is loaded
_open = threading.local()   # .stack: this thread's open phases, outermost first


def _annotation(name: str):
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        _ANNOTATION = getattr(
            getattr(jax, "profiler", None), "TraceAnnotation", None)
        if _ANNOTATION is None:
            return None
    return _ANNOTATION(name)


class Phase:
    """``with Phase(name, collector):`` — one timed region of linear code.

    ``parent`` is the phase that was open on this thread when this one
    was entered (None for a root), ``ident`` and ``attrs`` are the
    caller's; ``collector(phase)`` runs once, after ``t1`` is stamped, on
    the thread that ran the body. No lock, no I/O: what a collector does
    with a closed phase is its own cost."""

    __slots__ = ("name", "ident", "attrs", "parent", "t0", "t1",
                 "_collector", "_annotation")

    def __init__(self, name: str, collector: Optional[Callable] = None, *,
                 ident=None, attrs: Optional[dict] = None):
        self.name = name
        self.ident = ident
        self.attrs = attrs
        self.parent = None
        self.t0 = self.t1 = None
        self._collector = collector

    def __enter__(self) -> "Phase":
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        if stack:
            self.parent = stack[-1]
        stack.append(self)
        self._annotation = ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        _open.stack.pop()
        # let go of the collector: one that keeps the phase (a list's
        # ``append``) would otherwise close a reference cycle a tick, left
        # to the cyclic collector
        collector, self._collector = self._collector, None
        if collector is not None:
            collector(self)
        return False

    @property
    def dur_s(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0


#: the set-up path's closed spans (``span`` records, component ``setup``),
#: oldest first: what a reader in the same process has where no sink is
#: attached. Bounded: a process sets up once, a test suite many times.
SETUP_SPANS: collections.deque = collections.deque(maxlen=256)

_SETUP_PREFIX = f"setup-{uuid.uuid4().hex[:6]}"
_setup_seq = itertools.count(1)


class _SetupPhase(Phase):
    __slots__ = ("registry",)

    def __init__(self, name: str, registry, attrs: Optional[dict]):
        super().__init__(
            name, _collect_setup,
            ident=f"{_SETUP_PREFIX}-{next(_setup_seq)}", attrs=attrs)
        self.registry = registry


def setup_phase(name: str, *, registry=None,
                attrs: Optional[dict] = None) -> Phase:
    """A phase of a linear set-up path (``serve_setup*``, ``warm_start*``):
    closed, it is kept in ``SETUP_SPANS`` and emitted through ``registry``
    (the process default when None) as a ``span`` record. Set-up phases
    opened inside one another on a thread form one trace, named after
    its root."""
    return _SetupPhase(name, registry, attrs)


def _collect_setup(p: _SetupPhase) -> None:
    parent = p.parent if isinstance(p.parent, _SetupPhase) else None
    root = p
    while isinstance(root.parent, _SetupPhase):
        root = root.parent
    rec = _span_record(
        trace=root.ident, span=p.ident,
        parent=parent.ident if parent is not None else None,
        name=p.name, component="setup", t0=p.t0, t1=p.t1,
        attrs=p.attrs or {}, mono=time.monotonic(), wall=time.time())
    SETUP_SPANS.append(rec)
    registry = p.registry
    if registry is None:
        from pytorch_distributed_training_tpu.telemetry.registry import (
            get_registry,
        )

        registry = get_registry()
    registry.emit(rec)


def _span_record(*, trace, span, parent, name, component, t0, t1, attrs,
                 mono, wall) -> dict:
    """The ``span`` record. Wall-clock bounds are derived from the
    monotonic offsets at emit time (``mono``/``wall`` read together):
    cross-process waterfall alignment, never duration math."""
    return {
        "record": "span",
        "trace": trace,
        "span": span,
        "parent": parent,
        "name": name,
        "component": component,
        "t0_s": t0,
        "t1_s": t1,
        "dur_s": max(0.0, t1 - t0),
        "wall_t0": wall - (mono - t0),
        "wall_t1": wall - (mono - t1),
        "attrs": attrs,
    }


# --------------------------------------------------------- trace analysis


def spans_by_trace(records) -> dict:
    """Group ``span`` records (any iterable of record dicts) by trace id,
    preserving emission order — the merge step for fleet-side analysis."""
    out: dict[str, list] = {}
    for rec in records:
        if rec.get("record") == "span" and rec.get("trace"):
            out.setdefault(str(rec["trace"]), []).append(rec)
    return out


def trace_summary(spans: list) -> dict:
    """Structural verdict for ONE trace's span list.

    A trace is **complete** when it has exactly one root (a span with no
    parent), the root is closed, every span is closed, and every parent id
    resolves to a span within the trace (unresolved parents are orphans —
    the signature of a replica stream that wasn't merged, or a dropped
    root). ``phase_sum_s``/``root_dur_s`` carry the tiling reconciliation
    for the replica phases (summed across replicas for hedged traces;
    compared per-serve-span by callers that need the 5% gate)."""
    roots = [s for s in spans if not s.get("parent")]
    ids = {s.get("span") for s in spans}
    orphans = [
        s for s in spans
        if s.get("parent") and s.get("parent") not in ids
    ]
    open_spans = [s for s in spans if s.get("t1_s") is None]
    serve = [s for s in spans if s.get("name") == "serve"]
    phase_sum = sum(
        s.get("dur_s") or 0.0 for s in spans
        if s.get("name") in REQUEST_PHASES
    )
    serve_dur = sum(s.get("dur_s") or 0.0 for s in serve)
    return {
        "spans": len(spans),
        "roots": len(roots),
        "orphans": len(orphans),
        "open": len(open_spans),
        "complete": (
            len(roots) == 1 and not orphans and not open_spans
        ),
        "root_name": roots[0].get("name") if len(roots) == 1 else None,
        "root_dur_s": roots[0].get("dur_s") if len(roots) == 1 else None,
        "serve_spans": len(serve),
        "serve_dur_s": serve_dur or None,
        "phase_sum_s": phase_sum or None,
        "phase_sum_ok": (
            abs(phase_sum - serve_dur) <= 0.05 * serve_dur
            if serve_dur else None
        ),
    }


def trace_coverage(records, *, accepted_ids=None) -> dict:
    """Fleet-level span coverage over an iterable of records.

    ``accepted_ids`` (when given) restricts the verdict to those trace ids
    — the bench gate: every ACCEPTED request must yield a complete,
    root-closed tree with zero orphans and phase sums reconciling within
    5% of the serve span total. Returns counts plus the offending trace
    ids so a failing gate names its evidence."""
    traces = spans_by_trace(records)
    if accepted_ids is not None:
        wanted = {str(i) for i in accepted_ids}
        traces = {t: s for t, s in traces.items() if t in wanted}
        missing = sorted(wanted - set(traces))
    else:
        missing = []
    complete = 0
    orphan_spans = 0
    incomplete: list[str] = []
    phase_sum_bad: list[str] = []
    for trace, spans in sorted(traces.items()):
        v = trace_summary(spans)
        orphan_spans += v["orphans"]
        if v["complete"]:
            complete += 1
        else:
            incomplete.append(trace)
        if v["phase_sum_ok"] is False:
            phase_sum_bad.append(trace)
    total = len(traces) + len(missing)
    return {
        "traces": total,
        "complete": complete,
        "incomplete": incomplete + missing,
        "orphan_spans": orphan_spans,
        "phase_sum_bad": phase_sum_bad,
        "coverage": (complete / total) if total else 1.0,
    }
