"""Low-overhead request/span tracing with cross-thread propagation.

A **span** is a named ``[t0, t1)`` interval tagged with a ``trace_id``;
every span of one serving request (or one training run) shares the id,
so a bounded ring buffer of spans can be re-assembled into a per-request
timeline: ``submit → queue → prefix lookup/copy → chunked prefill
cycles → decode steps → complete`` for serving,
``loop.step → trainer.step → checkpoint.commit`` for training.

Design rules (same contract as :mod:`mxnet_tpu.resilience.faults`):

- **zero-cost when disabled** — every instrumentation site does ONE
  module-global load plus a ``None`` check and nothing else.  The
  serving engine's decode medians must stay within trial noise with
  tracing off (the ``obs``-marked tests in tests/test_observability.py).
- **propagation crosses threads by value**: the caller thread stamps
  ``trace_id`` on the request at ``submit``; the scheduler thread reads
  it — no thread-locals to lose across the queue boundary.  Batched
  device calls (one prefill/decode program serving many requests)
  record ONE span carrying ``trace_ids`` of every rider, so a request's
  timeline includes the shared steps it rode.
- **bounded memory**: spans land in a ``deque(maxlen=capacity)`` ring;
  a forgotten-enabled tracer can never OOM a serving host.
- **parents are explicit**: a span names the span that caused it with
  ``parent=`` at the call site (no thread-local stack), so a span's
  self time — its duration less what its children cover — can be
  computed from the ring (:meth:`Tracer.self_seconds`).
- **device-trace bridge**: :func:`host_range` is the ONE way a host
  phase reaches a ``jax.profiler`` capture.  It always opens a
  ``TraceAnnotation`` (all but free while no profiler session is live)
  and, when a tracer is active, records the span as well.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..analysis.lockwitness import named_lock as _named_lock
from . import stalls as _stalls

__all__ = ["Span", "Tracer", "enable", "disable", "active", "host_range"]

# process-unique span ids, shared by every tracer so that an id handed
# out before a tracer was replaced can never collide with a later one
_SPAN_IDS = itertools.count(1)


def _id_of(parent) -> Optional[int]:
    """``parent=`` takes a span (live or recorded) or a bare span id."""
    if parent is None or isinstance(parent, int):
        return parent
    return parent.span_id


class Span:
    """One recorded interval (or instant, when ``t1 == t0``)."""

    __slots__ = ("name", "trace_id", "trace_ids", "t0", "t1", "attrs",
                 "span_id", "parent_id")

    def __init__(self, name: str, trace_id: Optional[int], t0: float,
                 t1: float, trace_ids: Optional[tuple] = None,
                 attrs: Optional[dict] = None,
                 span_id: Optional[int] = None,
                 parent_id: Optional[int] = None):
        self.name = name
        self.trace_id = trace_id
        self.trace_ids = trace_ids or ()
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs or {}
        self.span_id = next(_SPAN_IDS) if span_id is None else span_id
        self.parent_id = parent_id      # the span that caused this one

    def in_trace(self, trace_id: int) -> bool:
        return self.trace_id == trace_id or trace_id in self.trace_ids

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    def as_dict(self) -> dict:
        return {"name": self.name, "trace_id": self.trace_id,
                "trace_ids": list(self.trace_ids),
                "span_id": self.span_id, "parent_id": self.parent_id,
                "t0": self.t0, "t1": self.t1,
                "duration_ms": round(1e3 * (self.t1 - self.t0), 4),
                "attrs": dict(self.attrs)}

    def __repr__(self):
        tid = self.trace_id if self.trace_id is not None else \
            list(self.trace_ids)
        return (f"Span({self.name!r}, trace={tid}, "
                f"{1e3 * (self.t1 - self.t0):.3f}ms)")


class _LiveSpan:
    """A started-but-unfinished span; also usable as a context manager.
    Recording happens at ``finish()`` so a span abandoned by a crashed
    step simply never lands in the ring (no torn half-spans)."""

    __slots__ = ("_tracer", "name", "trace_id", "trace_ids", "t0",
                 "attrs", "span_id", "parent_id")

    def __init__(self, tracer: "Tracer", name: str,
                 trace_id: Optional[int], trace_ids: Optional[tuple],
                 attrs: dict, parent=None):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.trace_ids = trace_ids
        self.attrs = attrs
        # the id exists from the start, so children that finish first
        # can already name this span as their parent
        self.span_id = next(_SPAN_IDS)
        self.parent_id = _id_of(parent)
        self.t0 = time.monotonic()

    def finish(self, **attrs):
        t1 = time.monotonic()
        if attrs:
            self.attrs.update(attrs)
        self._tracer._record(Span(self.name, self.trace_id, self.t0, t1,
                                  self.trace_ids, self.attrs,
                                  self.span_id, self.parent_id))

    def __enter__(self):
        return self

    def __exit__(self, etype, exc, tb):
        if etype is not None:
            self.attrs["error"] = etype.__name__
        self.finish()


class Tracer:
    """Ring-buffered span recorder.  Thread-safe throughout."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._lock = _named_lock("obs.trace_ring",
                                 "tracer span ring buffer")
        self._ring: deque = deque(maxlen=self.capacity)
        self._ids = itertools.count(1)
        self.dropped = 0          # spans evicted by the ring bound

    # ---------------------------------------------------------------- ids
    def new_trace_id(self) -> int:
        """A fresh process-unique trace id (itertools.count is GIL-atomic)."""
        return next(self._ids)

    # ------------------------------------------------------------- recording
    def _record(self, span: Span):
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(span)

    @staticmethod
    def new_span_id() -> int:
        """A span id reserved ahead of its span: retrospective children
        recorded first name it as ``parent=``, the parent then takes it
        with ``record_span(..., span_id=)``."""
        return next(_SPAN_IDS)

    def span(self, name: str, trace_id: Optional[int] = None,
             trace_ids: Optional[tuple] = None, parent=None,
             **attrs) -> _LiveSpan:
        """Start a span; finish via ``with`` or ``.finish()``.
        ``parent`` is the span (live, recorded, or its id) that caused
        this one."""
        return _LiveSpan(self, name, trace_id,
                         tuple(trace_ids) if trace_ids else None, attrs,
                         parent)

    def record_span(self, name: str, t0: float, t1: float,
                    trace_id: Optional[int] = None,
                    trace_ids: Optional[tuple] = None, parent=None,
                    span_id: Optional[int] = None, **attrs):
        """Record a RETROSPECTIVE span from timestamps the caller
        already holds (e.g. the queue phase, measured by request
        timestamps) — no live bookkeeping on the hot path."""
        self._record(Span(name, trace_id, t0, t1,
                          tuple(trace_ids) if trace_ids else None, attrs,
                          span_id, _id_of(parent)))

    def event(self, name: str, trace_id: Optional[int] = None,
              parent=None, **attrs):
        """Instant (zero-duration) span."""
        now = time.monotonic()
        self._record(Span(name, trace_id, now, now, None, attrs, None,
                          _id_of(parent)))

    # --------------------------------------------------------------- queries
    def spans(self, trace_id: Optional[int] = None,
              name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._ring)
        if trace_id is not None:
            out = [s for s in out if s.in_trace(trace_id)]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def timeline(self, trace_id: int) -> List[dict]:
        """Every span of one trace, oldest-first, offsets relative to
        the trace's first span — the per-request timeline dump.
        ``None`` (a future whose request predates tracing) is NOT a
        wildcard here: it returns an empty timeline, never the whole
        ring dressed up as one request."""
        if trace_id is None:
            return []
        ring = self.spans()
        spans = sorted((s for s in ring if s.in_trace(trace_id)),
                       key=lambda s: (s.t0, s.t1))
        if not spans:
            return []
        base = spans[0].t0
        own = _self_seconds(ring, spans)
        out = []
        for s in spans:
            d = s.as_dict()
            d["offset_ms"] = round(1e3 * (s.t0 - base), 4)
            d["self_ms"] = round(1e3 * own[s.span_id], 4)
            out.append(d)
        return out

    def self_seconds(self) -> Dict[int, float]:
        """``{span_id: seconds}`` for every span in the ring: the span's
        duration less the union of what its recorded children cover
        (clipped to the span, overlapping children counted once).  A
        span with no children keeps its whole duration; a parent whose
        children were evicted by the ring bound reads too high, which
        ``dropped`` gives away."""
        ring = self.spans()
        return _self_seconds(ring, ring)

    def trace_ids(self) -> List[int]:
        seen: Dict[int, None] = {}
        with self._lock:
            for s in self._ring:
                if s.trace_id is not None:
                    seen.setdefault(s.trace_id, None)
                for t in s.trace_ids:
                    seen.setdefault(t, None)
        return list(seen)

    def clear(self):
        with self._lock:
            self._ring.clear()
            self.dropped = 0

    def __len__(self):
        with self._lock:
            return len(self._ring)


def _self_seconds(ring: List[Span], wanted: List[Span]) -> Dict[int, float]:
    """Self time of each span of ``wanted``, its children looked up in
    ``ring`` (one snapshot serves both)."""
    kids: Dict[int, list] = {}
    for s in ring:
        if s.parent_id is not None:
            kids.setdefault(s.parent_id, []).append(s)
    out = {}
    for s in wanted:
        covered, at = 0.0, s.t0
        for k in sorted(kids.get(s.span_id, ()), key=lambda k: k.t0):
            lo, hi = max(k.t0, at), min(k.t1, s.t1)
            if hi > lo:
                covered += hi - lo
                at = hi
        out[s.span_id] = (s.t1 - s.t0) - covered
    return out


# The one active tracer.  Written under _LOCK; read lock-free on hot
# paths (a torn read of a single reference is impossible in CPython).
_ACTIVE: Optional[Tracer] = None
_LOCK = _named_lock("obs.trace_global", "active-tracer swaps")


def _ring_samples():
    """Registry collector: the span ring's occupancy and evictions as
    scrapeable series (docs/observability.md).  ``tracer.dropped``
    used to be visible only on the tracer object — a scraper could
    not tell a quiet host from a ring silently thrashing (every
    dropped span is a hole in some request's timeline).  No samples
    while tracing is disabled: there is no ring to report on, and an
    absent series is distinguishable from a zero one."""
    tr = _ACTIVE
    if tr is None:
        return []
    with tr._lock:
        dropped, size = tr.dropped, len(tr._ring)
    return [
        {"name": "mxtpu_trace_spans_dropped_total", "kind": "counter",
         "labels": {}, "value": dropped,
         "help": "spans evicted by the ring bound — each is a hole in "
                 "some request's timeline (resets when the tracer is "
                 "replaced)"},
        {"name": "mxtpu_trace_ring_spans", "kind": "gauge",
         "labels": {}, "value": size,
         "help": "spans currently in the ring"},
        {"name": "mxtpu_trace_ring_capacity", "kind": "gauge",
         "labels": {}, "value": tr.capacity,
         "help": "ring bound — ring_spans pinned here plus a climbing "
                 "dropped_total means the ring is thrashing"},
    ]


def _register_ring_collector():
    from .registry import default_registry
    default_registry().register_collector("trace", _ring_samples)


_register_ring_collector()


def enable(capacity: int = 4096) -> Tracer:
    """Install (or replace) the process-global tracer and return it.
    Replacing drops the previous ring — tracing config is a process
    decision, not a nesting scope like FaultPlan."""
    global _ACTIVE
    tracer = Tracer(capacity=capacity)
    with _LOCK:
        _ACTIVE = tracer
    # re-register on every enable: a test that reset() the registry
    # (dropping all collectors) still gets ring telemetry back the
    # moment tracing turns on
    _register_ring_collector()
    return tracer


def disable() -> None:
    global _ACTIVE
    with _LOCK:
        _ACTIVE = None


def active() -> Optional[Tracer]:
    """The hot-path hook: one global load.  Instrumentation sites do
    ``tr = active()`` / ``if tr is not None: ...`` and NOTHING else on
    the disabled path."""
    return _ACTIVE


class host_range:
    """The one bridge from a host phase to both timelines.

    Entering ALWAYS opens a ``jax.profiler.TraceAnnotation`` — all but
    free while no profiler session is live — so the phase shows on the
    host thread of any device capture, tracer or no tracer.  When a
    tracer is active (and ``span`` is left on) it also records the span
    ``<layer>.<phase>`` with ``parent`` as the span that caused it.

    Entering and leaving also tell ``observability.stalls`` which phase
    this thread is in (a slot write each, always on): what a stall
    record later says the thread was doing.

    The annotation's prefix follows one rule, because a trace reader
    attributes a device program to the last ``marker:`` range opened
    before the program started: a phase that **launches** device
    programs is ``marker:<layer>:<phase>``; one that only waits or does
    host bookkeeping is ``span:<layer>.<phase>``.

    ``span=False`` is for a caller that records its own spans (the
    serving engine: one retrospective span per batched call, carrying
    every rider's trace id)."""

    __slots__ = ("_ann", "_live", "_name", "_slot")

    def __init__(self, layer: str, phase: str, *, launches: bool,
                 parent=None, span: bool = True, **attrs):
        name = self._name = f"{layer}.{phase}"
        self._ann = _TraceAnnotation(
            f"marker:{layer}:{phase}" if launches else "span:" + name)
        tr = _ACTIVE if span else None
        self._live = None if tr is None else \
            tr.span(name, parent=parent, **attrs)

    @property
    def span(self):
        """The live span this range records, for a child's ``parent=``
        (None with no tracer on, which ``parent=`` takes as well)."""
        return self._live

    def __enter__(self):
        self._ann.__enter__()
        self._slot = _stalls.enter(self._name)
        return self

    def __exit__(self, etype, exc, tb):
        _stalls.leave(self._slot, self._live)
        self._ann.__exit__(etype, exc, tb)
        if self._live is not None:
            self._live.__exit__(etype, exc, tb)
