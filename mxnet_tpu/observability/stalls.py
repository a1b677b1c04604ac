"""The stalled step, seen from inside: what every thread, the host and the
awaited result were doing while a wait ran long.  Always on, bounded, on
``time.monotonic()`` (the clock of ``Span``, ``compiles.log()``, the flight
recorder and a benchmark's window).

**Every thread says which phase it is in.**  ``host_range`` calls
``enter(name)`` / ``leave(slot)`` around every host phase
(``ndarray.readback``, ``input.next``, ``trainer.dispatch`` ...), tracer or
no tracer; a ``DevicePrefetcher``'s feeder notes ``input.pull`` and
``input.ship`` by ``phase(name)``, which opens no profiler range.  A
thread's open phases are a chain of tuples in its own slot, a phase's
lengths a running history by name (the newest 64, enough for a median).
With nothing stalled that is all: no lock, no system call, three calls
into C and under half a microsecond a phase (the profiler's Python
tracer, when a capture runs, charges every call: they are kept few).
``awaiting(step, array)`` leaves the step's number and the array its
caller will wait for in the slot.  A read of a device array
(``ndarray.readback``) is the wait for a step only when it is the first
since an ``awaiting()``: any other (a parameter read in set-up, a second
output) waits for nothing, takes microseconds where a step takes half a
second, and is neither kept in the history nor judged, or a program that
reads fifty small arrays before its first loss would log its first steps
as stalls.

**When is a phase stalled?**  When it was open for more than twice its
name's running median and at least ``_FLOOR`` (0.15 s) over it, the history
holding ``_MIN_SAMPLES`` (8) or more: the benchmark drivers' own
``steps_over_twice_median`` rule with a floor, so that a hiccup of a short
step does not flood the log (the chip's hosts are not run for 0.09-0.11 s
once or twice a minute: a floor of 0.1 s logged about half of those).
The thread judges that itself as it leaves a phase that took over 0.15 s
(a frozen host freezes every thread, so nobody could be relied on to see
it meanwhile), once the watch is on (``start()``: the first
``ShardedTrainer.build``); a phase its owner ``declare()``d stalled by a
rule of its own (``DevicePrefetcher``'s ``stall_timeout``) is recorded
whatever its history says.

**One witness thread** (``start()``; ``resilience.watchdog.Watchdog``'s
loop, daemon) wakes every 20 ms.  It keeps its own lateness, wake time less
the time asked for: a process that was not run when it asked to be.  Once
a second it reads what the kernel says of scheduling (``_read_proc``).  And
while a phase is overdue it gathers what can only be seen meanwhile
(``_Watch``): the other threads' phases with one ``sys._current_frames()``,
how long each other thread then stayed in which phase, the first instant
the awaited array's ``is_ready()`` read true (its one JAX call, made only
then), and a ``TraceAnnotation("span:host.stall")`` held open so that a
profiler capture which holds the stall prints its idle gap under that
name.

**The record** (``log()``, newest 1,024, ``dropped()`` the rest):
``phase``, ``thread``, ``step``, ``start``, ``seconds``, ``expected_s``
(the median), ``witness_late_max_s``, ``ready_after_s``, ``threads``
(``name``, ``phase``, ``since``, ``frames``), ``compiles`` (records of
``compiles.log()`` that overlap), ``gc_s`` (collections that overlap),
``proc`` (what the kernel counted over the interval; None with no reading
from before its start), ``watched`` (whether the witness saw it open) and
``verdict``, by fixed rules in this order:

- ``host_frozen``: the witness was late (collections, which hold every
  Python thread, taken out) for half of the excess or more: the process
  was not run;
- ``compile`` / ``gc``: compiles, or collections, cover half of the excess;
- ``thread:<phase>``: another thread was inside one phase, and not one
  it only waits in, for most of the interval (the feeder in ``input.ship``
  is the case to catch);
- ``wake_late``: the awaited array was ready more than 0.1 s before the
  phase ended;
- ``device_or_runtime``: the host ran, nothing of this process was in the
  way and the result was not ready until the end: the device ran the step
  long or the runtime started it late, which only a device trace that holds
  the stall tells apart.

**Who gets it**: ``log()`` and ``summary()`` (``ShardedTrainer.stats()
["stalls"]``), the registry's ``mxtpu_host_stalls_total{phase,verdict}`` and
``mxtpu_host_stall_seconds_total{phase}`` (seconds over expected), the event
``host.stall`` (``data.stall`` for a ``DevicePrefetcher``'s) to the
``Tracer`` and the flight recorder when either is on, and one WARNING a
stall from logger ``mxnet_tpu.stalls`` with the record as JSON.
"""
from __future__ import annotations

import atexit
import gc
import json
import logging
import os
import resource
import sys
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..analysis.lockwitness import named_lock as _named_lock
from . import compiles as _compiles
from .flightrecorder import active as _fr_active
from .registry import default_registry

__all__ = ["enter", "leave", "phase", "awaiting", "declare", "start",
           "log", "dropped", "summary", "lateness"]

_PERIOD = 0.02          # the witness's sleep, seconds
_OVER_MEDIAN = 2.0      # stalled: open for more than this many medians ...
_FLOOR = 0.15           # ... and at least this many seconds over the median
_MIN_SAMPLES = 8        # a phase's history is not judged before it holds these
_HISTORY = 64           # lengths kept a phase name
_CAPACITY = 1024        # records kept
_SHARE = 0.5            # of the excess (the interval, for a thread): a cause
_WAKE_LATE = 0.1        # ready this long before the phase ended: woken late
_PROC_EVERY = 1.0       # seconds between the witness's readings of /proc
_FRAMES = 3             # frames kept a thread
_LATE_KEPT = 0.001      # a wake this late or later is kept with its instants
# the phases in which a thread waits for the array it said it is awaiting
_AWAITS = ("ndarray.readback",)
# the phases in which a thread only waits: never what held another one up
_WAITS = _AWAITS + ("input.next",)
_CLK_TCK = os.sysconf("SC_CLK_TCK")

_now = time.monotonic
_logger = logging.getLogger("mxnet_tpu.stalls")


class _Slot:
    """What one thread says of itself.  ``phase`` is written by its thread
    alone, one store a change; the witness reads it."""

    __slots__ = ("thread", "tid", "phase", "awaited", "fresh", "declared",
                 "watch")

    def __init__(self):
        self.thread = threading.current_thread().name
        self.tid = threading.get_native_id()
        self.phase = None       # (name, start, the phase around it) or None
        self.awaited = None     # (step, array)
        self.fresh = False      # awaited, and not read back since
        self.declared = None    # (event, attrs): the owner calls it stalled
        self.watch = None       # the witness's, while the phase is overdue
        _SLOTS[threading.get_ident()] = _TLS.slot = self


_TLS = threading.local()    # .slot: the calling thread's, found with no call
_SLOTS = {}     # thread ident -> _Slot, for the witness; one store a thread
_LENGTHS = {}   # phase name -> deque of its newest lengths
_ON = False     # start() was called: a thread judges the phases it leaves


def enter(name):
    """The calling thread is inside phase ``name`` from now; returns what
    ``leave`` takes."""
    try:
        slot = _TLS.slot
    except AttributeError:
        slot = _Slot()
    slot.phase = (name, _now(), slot.phase)
    return slot


def leave(slot, span=None):
    """The innermost phase of ``slot``'s thread has ended (``span``: the
    live tracer span of the phase, the parent of a ``host.stall`` event)."""
    name, start, slot.phase = slot.phase
    end = _now()
    if name in _AWAITS:
        if not slot.fresh:      # a read that waits for no step
            return
        slot.fresh = False
    if (end - start > _FLOOR and _ON) or slot.declared is not None:
        _judge(slot, name, start, end, span)
    try:
        lengths = _LENGTHS[name]
    except KeyError:
        lengths = _LENGTHS.setdefault(name, deque(maxlen=_HISTORY))
    lengths.append(end - start)


class phase:
    """``with phase("input.ship"):`` — a phase noted and nothing else: no
    profiler range, no span (a new range on a host line would rename the
    idle gaps a trace reader takes from the innermost range of any
    thread)."""

    __slots__ = ("_name", "_slot")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        self._slot = enter(self._name)

    def __exit__(self, *_exc):
        leave(self._slot)


def awaiting(step, array):
    """The calling thread has launched ``step`` and will wait for
    ``array`` (anything with ``is_ready()``): one reference, replaced by
    the next call."""
    try:
        slot = _TLS.slot
    except AttributeError:
        slot = _Slot()
    slot.awaited = (step, array)
    slot.fresh = True


def declare(event, **attrs):
    """The calling thread holds its innermost phase stalled by a rule of
    its own: the phase is recorded when it ends, whatever its history, its
    event named ``event`` and carrying ``attrs``."""
    slot = getattr(_TLS, "slot", None)
    if slot is not None and slot.phase is not None:
        slot.declared = (event, attrs)


def _expected(name):
    """The running median of ``name``'s lengths, None while it holds too
    few to judge by."""
    lengths = _LENGTHS.get(name)
    if lengths is None or len(lengths) < _MIN_SAMPLES:
        return None
    ordered = sorted(lengths)   # one C call: the deque cannot move under it
    return ordered[len(ordered) // 2]


def _overdue(seconds, expected) -> bool:
    return expected is not None and seconds > _OVER_MEDIAN * expected \
        and seconds >= expected + _FLOOR


# ------------------------------------------------------------ collections
_GC = deque(maxlen=1024)    # (start, seconds) of collections over 1 ms
_gc_t0 = 0.0


def _on_gc(when, _info):
    # on the collecting thread, the others held: one collection at a time
    global _gc_t0
    if when == "start":
        _gc_t0 = _now()
    else:
        seconds = _now() - _gc_t0
        if seconds > 0.001:
            _GC.append((_gc_t0, seconds))


gc.callbacks.append(_on_gc)


def _overlap(intervals, lo, hi) -> float:
    """Seconds of ``(start, end)`` intervals inside ``[lo, hi]``, an
    instant covered twice counted once."""
    total, at = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            total += e - s
            at = e
    return total


# ------------------------------------------------------------- the kernel
def _first_line(path):
    try:
        with open(path) as f:
            return f.readline()
    except OSError:
        return None


def _read_proc() -> dict:
    """What the kernel has counted so far, in seconds and counts: each
    noted thread's time on a CPU and time runnable but not run
    (``schedstat``), the machine's steal and iowait, the pressure files'
    ``some total``, this process's involuntary switches, major faults and
    CPU seconds.  A file that is absent leaves its keys out."""
    out = {"at": _now(), "sched": {}}
    for slot in list(_SLOTS.values()):
        line = _first_line(f"/proc/self/task/{slot.tid}/schedstat")
        if line:
            run, wait = line.split()[:2]
            out["sched"][slot.tid] = (int(run) * 1e-9, int(wait) * 1e-9)
    line = _first_line("/proc/stat")
    if line and line.startswith("cpu "):
        f = line.split()
        out["iowait_s"] = int(f[5]) / _CLK_TCK
        out["steal_s"] = int(f[8]) / _CLK_TCK
    for what in ("cpu", "io", "memory"):
        line = _first_line(f"/proc/pressure/{what}")
        if line and "total=" in line:
            out[f"psi_{what}_s"] = int(line.rsplit("total=", 1)[1]) * 1e-6
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out["nivcsw"], out["majflt"] = ru.ru_nivcsw, ru.ru_majflt
    out["cpu_s"] = ru.ru_utime + ru.ru_stime
    return out


def _proc_between(before, after, tid):
    """The kernel's counts over a stall: ``after`` less ``before``, the
    stalled thread's own ``schedstat`` among them."""
    if before is None:
        return None
    out = {"over_s": round(after["at"] - before["at"], 3)}
    if tid in before["sched"] and tid in after["sched"]:
        for key, b, a in zip(("sched_run_s", "sched_wait_s"),
                             before["sched"][tid], after["sched"][tid]):
            out[key] = round(a - b, 6)
    for key in ("steal_s", "iowait_s", "psi_cpu_s", "psi_io_s",
                "psi_memory_s", "nivcsw", "majflt", "cpu_s"):
        if key in before and key in after:
            out[key] = round(after[key] - before[key], 6)
    return out


# ------------------------------------------------------------ the witness
class _Watch:
    """What the witness gathers of one overdue phase while it lasts.  The
    witness alone writes it; the phase's thread reads it once, at the
    end."""

    __slots__ = ("slot", "name", "start", "threads", "inside", "at",
                 "ready_at", "annotation")

    def __init__(self, slot, name, start, now):
        self.slot, self.name, self.start = slot, name, start
        self.inside = {}        # (thread, phase) -> seconds seen inside it
        self.at = start         # up to where the other threads were seen
        self.ready_at = None
        self.threads = _threads_now(slot, now)
        self.annotation = _TraceAnnotation("span:host.stall")
        self.annotation.__enter__()

    def look(self, now):
        """One wake of the witness while the phase is open."""
        for other in list(_SLOTS.values()):
            inside = other.phase
            if other is self.slot or inside is None:
                continue
            name, since, _around = inside
            seen = now - max(since, self.at)
            if seen > 0 and name not in _WAITS:
                key = (other.thread, name)
                self.inside[key] = self.inside.get(key, 0.0) + seen
        self.at = now
        awaited = self.slot.awaited
        if self.ready_at is None and self.name in _AWAITS:
            try:
                if awaited[1].is_ready():
                    self.ready_at = now
            except Exception:       # deleted, donated: nothing to say
                pass

    def close(self):
        self.annotation.__exit__(None, None, None)


def _threads_now(stalled, now) -> list:
    """Every other thread: its name, the phase it says it is in and since
    when (seconds before ``now``), and its innermost frames."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for ident, frame in sys._current_frames().items():
        slot = _SLOTS.get(ident)
        if slot is stalled or ident == threading.get_ident():
            continue
        frames = []
        while frame is not None and len(frames) < _FRAMES:
            code = frame.f_code
            frames.append(f"{os.path.basename(code.co_filename)}:"
                          f"{frame.f_lineno} {code.co_name}")
            frame = frame.f_back
        inside = None if slot is None else slot.phase
        out.append({"name": names.get(ident, str(ident)),
                    "phase": inside[0] if inside else None,
                    "since": round(now - inside[1], 3) if inside else None,
                    "frames": frames})
    return out


class Witness:
    """The thread that is asked to wake every ``_PERIOD`` and says how
    late it was.  ``tick()`` is one wake; ``clock`` is ``time.monotonic``
    outside tests."""

    def __init__(self, clock=None):
        self._clock = clock or _now
        self.asked = self._clock()      # when the current sleep began
        self._late = deque(maxlen=1024)     # (due, woke) of the late wakes
        # a second: [its start, its latest wake, how late that was]
        self._seconds = deque(maxlen=4096)
        self._readings = deque(maxlen=64)
        self._watches = []
        self._loop = None

    def tick(self):
        now = self._clock()
        due = self.asked + _PERIOD
        late = max(now - due, 0.0)
        if late >= _LATE_KEPT:
            self._late.append((due, now))
        second = self._seconds[-1] if self._seconds else None
        if second is None or now - second[0] >= _PROC_EVERY:
            self._seconds.append([now, now, late])
            self._readings.append(_read_proc())
            for ident in set(_SLOTS) - {t.ident
                                        for t in threading.enumerate()}:
                _SLOTS.pop(ident, None)     # a thread that is gone
        elif late > second[2]:
            second[1], second[2] = now, late
        for slot in list(_SLOTS.values()):
            inside = slot.phase
            if inside is None or now - inside[1] <= _FLOOR:
                continue
            name, start, _around = inside
            if name in _AWAITS and not slot.fresh:
                continue
            watch = slot.watch
            if watch is not None and (watch.name, watch.start) == \
                    (name, start):
                continue
            if slot.declared is not None or \
                    _overdue(now - start, _expected(name)):
                watch = slot.watch = _Watch(slot, name, start, now)
                self._watches.append(watch)
        watching = []
        for watch in self._watches:
            inside = watch.slot.phase
            if inside is not None and inside[:2] == (watch.name,
                                                     watch.start):
                watch.look(now)
                watching.append(watch)
            else:
                watch.close()
        self._watches = watching
        self.asked = self._clock()

    def late_between(self, lo, hi) -> list:
        """``(due, woke)`` of the wakes that were late inside ``[lo,
        hi]``, and of the one that is overdue at ``hi``."""
        out = [(d, w) for d, w in list(self._late) if w > lo and d < hi]
        due = self.asked + _PERIOD
        if hi - due >= _LATE_KEPT and (not out or out[-1][0] < due):
            out.append((due, hi))
        return out

    def reading_before(self, instant):
        before = [r for r in list(self._readings) if r["at"] <= instant]
        return before[-1] if before else None

    def lateness(self) -> list:
        return [(woke, late) for _start, woke, late in list(self._seconds)]

    # ---------------------------------------------------- as a thread
    def start(self):
        from ..resilience.watchdog import Watchdog

        self.asked = self._clock()
        # tick() returns None: to the loop, a healthy check
        self._loop = Watchdog(self.tick, self._tripped, interval=_PERIOD,
                              name="mxtpu-stall-witness")
        self._loop.start()
        atexit.register(self.stop)

    def stop(self):
        if self._loop is not None:
            self._loop.stop()

    @staticmethod
    def _tripped(reason):
        _logger.warning("the stall witness stopped: %s", reason)

    def alive(self) -> bool:
        return self._loop is not None and self._loop.is_alive()


_WITNESS = None
_START = _named_lock("obs.stall_witness", "the one stall witness's start")


def start():
    """Turn the watch on: threads judge the phases they leave, and the one
    witness thread of the process runs (started again if it died).  Called
    by the first ``ShardedTrainer.build``."""
    global _WITNESS, _ON
    with _START:
        if _WITNESS is None or not _WITNESS.alive():
            witness = Witness()
            witness.start()
            _WITNESS = witness
        _ON = True


# ------------------------------------------------------------- the record
class _Log:
    """The records, the count of those that fell off, the running sums."""

    def __init__(self):
        self._lock = _named_lock("obs.stall_log", "stall records and sums")
        self._records = deque(maxlen=_CAPACITY)
        self._dropped = 0
        self._count = 0
        self._over_s = 0.0

    def add(self, rec):
        with self._lock:
            if len(self._records) == _CAPACITY:
                self._dropped += 1
            self._records.append(rec)
            self._count += 1
            self._over_s += rec["seconds"] - (rec["expected_s"] or 0.0)

    def records(self) -> list:
        with self._lock:
            return list(self._records)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def summary(self) -> dict:
        with self._lock:
            return {"count": self._count,
                    "over_expected_s": round(self._over_s, 6),
                    "last": self._records[-1] if self._records else None}

    def clear(self):
        with self._lock:
            self._records.clear()
            self._dropped = self._count = 0
            self._over_s = 0.0


_LOG = _Log()


def log() -> list:
    """The stall records kept (the newest 1,024), oldest first."""
    return _LOG.records()


def dropped() -> int:
    """Records the bounded log has lost, oldest first."""
    return _LOG.dropped()


def summary() -> dict:
    """``count`` of stalls recorded since the process began,
    ``over_expected_s`` their seconds over expected added up, ``last`` the
    newest record (None before the first)."""
    return _LOG.summary()


def lateness() -> list:
    """``(instant, seconds)``: for each second the witness ran, its latest
    wake and when; empty with no witness."""
    witness = _WITNESS
    return [] if witness is None else witness.lateness()


def _verdict(excess, seconds, frozen_s, compile_s, gc_s, inside, ready_for_s):
    if frozen_s >= _SHARE * excess:
        return "host_frozen"
    if compile_s >= _SHARE * excess:
        return "compile"
    if gc_s >= _SHARE * excess:
        return "gc"
    if inside:
        (_thread, name), longest = max(inside.items(), key=lambda kv: kv[1])
        if longest > _SHARE * seconds:
            return f"thread:{name}"
    if ready_for_s is not None and ready_for_s > _WAKE_LATE:
        return "wake_late"
    return "device_or_runtime"


def _judge(slot, name, start, end, span):
    """On the phase's own thread, as it leaves a phase that took long or
    that it declared stalled: is it a stall, and if so, the record."""
    declared, slot.declared = slot.declared, None
    watch, slot.watch = slot.watch, None
    if watch is not None and (watch.name, watch.start) != (name, start):
        watch = None
    seconds = end - start
    expected = _expected(name)
    if declared is None and not _overdue(seconds, expected):
        return
    excess = seconds - (expected or 0.0)
    collections = [(s, s + d) for s, d in list(_GC) if s + d > start
                   and s < end]
    gc_s = _overlap(collections, start, end)
    witness = _WITNESS
    late = [] if witness is None else witness.late_between(start, end)
    # a collection holds every Python thread, the witness among them: what
    # it explains of the lateness is not the host's doing
    frozen_s = _overlap(late, start, end) - sum(
        _overlap(collections, max(d, start), min(w, end)) for d, w in late)
    programs = [r for r in _compiles.log()
                if r[0] not in ("cache_hit", "cache_miss")
                and r[1] > start and r[1] - r[2] < end]
    compile_s = _overlap([(r[1] - r[2], r[1]) for r in programs], start, end)
    ready_at = None if watch is None else watch.ready_at
    event, attrs = declared or ("host.stall", {})
    awaited = slot.awaited
    rec = {
        "phase": name, "thread": slot.thread,
        "step": awaited[0] if awaited else None,
        "start": start, "seconds": seconds, "expected_s": expected,
        "witness_late_max_s": (max((min(w, end) - max(d, start)
                                    for d, w in late), default=0.0)
                               if witness is not None else None),
        "ready_after_s": None if ready_at is None else ready_at - start,
        "threads": [] if watch is None else watch.threads,
        "compiles": [{"kind": k, "end": e, "seconds": s, "name": n}
                     for k, e, s, _t, n in programs[-16:]],
        "gc_s": gc_s,
        "proc": (None if witness is None else _proc_between(
            witness.reading_before(start), _read_proc(), slot.tid)),
        "watched": watch is not None,
        "verdict": _verdict(
            excess, seconds, frozen_s, compile_s, gc_s,
            {} if watch is None else watch.inside,
            None if ready_at is None else end - ready_at),
    }
    _LOG.add(rec)
    _tell(rec, event, attrs, span)


def _tell(rec, event, attrs, span):
    """One stall to everyone who listens."""
    over = rec["seconds"] - (rec["expected_s"] or 0.0)
    reg = default_registry()
    reg.counter("mxtpu_host_stalls_total",
                help="host phases that ran over twice their median and 0.15 s "
                     "more (or that their owner declared stalled)",
                phase=rec["phase"], verdict=rec["verdict"]).inc()
    reg.counter("mxtpu_host_stall_seconds_total",
                help="seconds stalled host phases took over their median",
                phase=rec["phase"]).inc(over)
    told = dict(attrs, step=rec["step"], phase=rec["phase"],
                seconds=round(rec["seconds"], 6), verdict=rec["verdict"],
                expected_s=rec["expected_s"])
    if event == "data.stall":
        told["waited"] = round(rec["seconds"], 3)
    from .trace import active as _trace_active      # trace imports this module

    tr = _trace_active()
    if tr is not None:
        tr.event(event, parent=span, **told)
    fr = _fr_active()
    if fr is not None:
        fr.record(event, **told)
    _logger.warning(
        "step %s: %s took %.3f s, expected %s: %s %s", rec["step"],
        rec["phase"], rec["seconds"],
        "%.3f s" % rec["expected_s"] if rec["expected_s"] is not None
        else "no history", rec["verdict"], json.dumps(rec, default=str))


def _reset():
    """For tests: forget every length, record and reading (the witness
    thread, if one runs, stays)."""
    _LENGTHS.clear()
    _GC.clear()
    _LOG.clear()
    for slot in list(_SLOTS.values()):
        slot.declared = slot.watch = slot.awaited = None
        slot.fresh = False
