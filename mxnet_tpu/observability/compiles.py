"""What ``jax.monitoring`` tells this process about getting programs:
counted, and kept with its instant.

JAX reports, on the thread whose call needed the executable, how long
it took to trace a jitted function to a jaxpr, to lower the jaxpr to an
MLIR module, and to get the executable from the backend — a cold compile
and a persistent-cache load alike, never an in-memory hit — and, where
the persistent cache was asked, whether it served the program and how
long reading it took.  This module listens once per process and keeps:

- registry counters, what a scraper alerts on once warm-up is over:
  ``mxtpu_xla_compiles_total`` (backend requests),
  ``mxtpu_jax_trace_seconds_total``, ``mxtpu_jax_lower_seconds_total``,
  ``mxtpu_xla_compile_seconds_total``, ``mxtpu_compile_cache_hits_total``
  and ``mxtpu_compile_cache_misses_total``;
- a per-thread count of backend requests, so a caller can tell what ITS
  OWN calls compiled (``on_this_thread()`` before and after) whatever
  other threads do meanwhile.  ``ShardedTrainer.step`` records
  ``trainer.compile`` from it and the serving engine feeds
  ``mxtpu_serving_compiles`` and ``warmup()``'s return from it, in place
  of guessing from the first call per shape bucket (which a
  committed-vs-uncommitted argument defeats: the program compiles again
  and the guess stays put);
- a bounded log, one record an event: ``(kind, end, seconds, thread,
  name)`` with ``end`` on ``time.monotonic()`` (the clock of ``Span``, the
  flight recorder and a benchmark's window) and ``name`` the function or
  module as JAX calls it (``jit(trainer_step)``; empty for the cache's
  events, which carry none).  Kinds: ``trace``, ``lower``,
  ``compile`` (every backend request, however it was served),
  ``cache_load`` (the read of a persistent-cache entry, inside its
  ``compile``), and the instants ``cache_hit`` and ``cache_miss``
  (seconds 0).  ``cache_miss`` is JAX's own event: a program that was
  compiled and WRITTEN to the cache.  A program that compiled in less
  than ``jax_persistent_cache_min_compile_time_secs`` is neither: it is
  compiled afresh in every process.
- one record a ``ShardedTrainer`` built (``note_build`` / ``builds``).

Intervals overlap: a ``cache_load`` lies inside its ``compile``, threads
compile side by side, and a jit traced inside another reports both, the
inner first.  Time is therefore never the seconds added up but the
length of the UNION of the intervals ``[end - seconds, end]``.  Nested
traces the listener leaves out itself (a model's step reports thousands
of ``add`` and ``reshape`` traced inside it): JAX announces the start of
each timed section too, so a trace that ends while another of its thread
is open is the inner one, and neither the log nor the counter takes it.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import jax.monitoring

from ..analysis.lockwitness import named_lock as _named_lock
from .registry import default_registry

__all__ = ["on_this_thread", "log", "dropped", "of_last", "note_build",
           "builds"]

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_DURATIONS = {
    _TRACE: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_INSTANTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# registry name and help by kind; a cache_load is inside its compile and
# has no counter of its own
_COUNTERS = {
    "trace": ("mxtpu_jax_trace_seconds_total",
              "seconds this process spent tracing jitted functions to "
              "jaxprs (a trace inside another counted once)"),
    "lower": ("mxtpu_jax_lower_seconds_total",
              "seconds this process spent lowering jaxprs to MLIR modules"),
    "compile": ("mxtpu_xla_compile_seconds_total",
                "seconds this process waited for the backend to hand over "
                "executables, compiled or loaded from the persistent cache"),
    "cache_hit": ("mxtpu_compile_cache_hits_total",
                  "programs the persistent compilation cache served"),
    "cache_miss": ("mxtpu_compile_cache_misses_total",
                   "programs compiled and written to the persistent "
                   "compilation cache"),
}
# of_last()'s key by kind (a cache_load is inside its compile)
_OF_LAST = {"trace": "trace_s", "lower": "lower_s", "compile": "compile_s",
            "cache_hit": "cache_hits", "cache_miss": "cache_misses"}
# a whole benchmark run of a hybrid cell leaves 5,400-7,000 records, nine
# in ten of them eager per-leaf work tracing trivial functions for 50 us
_CAPACITY = 16384
_tls = threading.local()


class _Log:
    """The records, the count of those that fell off, the builds."""

    def __init__(self):
        self._lock = _named_lock("obs.compile_log",
                                 "compile-log records and builds")
        self._records = deque(maxlen=_CAPACITY)
        self._dropped = 0
        self._builds = deque(maxlen=16)

    def add(self, kind, seconds, name=""):
        rec = (kind, time.monotonic(), seconds, threading.get_ident(), name)
        with self._lock:
            if len(self._records) == _CAPACITY:
                self._dropped += 1
            self._records.append(rec)

    def records(self) -> list:
        with self._lock:
            return list(self._records)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def add_build(self, rec):
        with self._lock:
            self._builds.append(rec)

    def builds(self) -> list:
        with self._lock:
            return list(self._builds)


_LOG = _Log()


def _count(kind, amount):
    # looked up per event, not cached: a registry reset() between two
    # compiles must not leave the count on an orphaned metric
    name, text = _COUNTERS[kind]
    default_registry().counter(name, help=text).inc(amount)


def _on_start(name, *_a, **_kw):
    if name == _TRACE:
        _tls.open = getattr(_tls, "open", 0) + 1


def _on_duration(name, seconds, fun_name="", **_kw):
    kind = _DURATIONS.get(name)
    if kind is None:
        return
    if kind == "trace":
        _tls.open = still_open = max(getattr(_tls, "open", 0) - 1, 0)
        if still_open:
            return
    seconds = float(seconds)
    _LOG.add(kind, seconds, fun_name)
    if kind == "compile":
        _tls.n = getattr(_tls, "n", 0) + 1
        default_registry().counter(
            "mxtpu_xla_compiles_total",
            help="XLA backend compiles in this process (persistent-cache "
                 "loads included, in-memory hits not) — flat once every "
                 "program is warm").inc()
    if kind != "cache_load":
        _count(kind, seconds)


def _on_event(name, **_kw):
    kind = _INSTANTS.get(name)
    if kind is None:
        return
    _LOG.add(kind, 0.0)
    _count(kind, 1)


def on_this_thread() -> int:
    """Backend requests the calling thread has caused so far.  A compile
    runs on the thread whose call needed the executable, so the
    difference over a call is what that call compiled."""
    return getattr(_tls, "n", 0)


def log(until=None) -> list:
    """The records kept, oldest first, as ``(kind, end, seconds,
    thread, name)``; with ``until``, those that had ended by that instant of
    ``time.monotonic()``.  The log holds the newest 16,384: ``dropped()``
    says how many fell off."""
    recs = _LOG.records()
    if until is None:
        return recs
    return [r for r in recs if r[1] <= until]


def dropped() -> int:
    """Records the bounded log has lost, oldest first."""
    return _LOG.dropped()


def of_last(compiles: int) -> dict:
    """What the calling thread's last ``compiles`` backend requests cost
    it, from the log: everything this thread recorded since the request
    before them ended.  ``trace_s``, ``lower_s`` and ``compile_s`` are
    seconds (one thread's records of a kind do not overlap, a nested
    trace having given way to its outer one), ``cache_hits`` and
    ``cache_misses`` counts."""
    me = threading.get_ident()
    out = dict.fromkeys(_OF_LAST.values(), 0)
    seen = 0
    for kind, _end, seconds, thread, _name in reversed(_LOG.records()):
        if thread != me:
            continue
        if kind == "compile":
            if seen == compiles:
                break
            seen += 1
        key = _OF_LAST.get(kind)
        if key is not None:
            out[key] += seconds if key.endswith("_s") else 1
    return out


def note_build(start, end, settle_s, state_s, place_s):
    """One ``ShardedTrainer`` built itself between ``start`` and ``end``
    (``time.monotonic()``), its three phases taking these seconds: kept
    whether or not anything traces, once a trainer."""
    _LOG.add_build((start, end, settle_s, state_s, place_s))


def builds() -> list:
    """``(start, end, settle_s, state_s, place_s)`` of the trainers built
    in this process (the newest 16), oldest first."""
    return _LOG.builds()


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
