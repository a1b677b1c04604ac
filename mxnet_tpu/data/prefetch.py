"""Double-buffered device prefetch: ship batch N+1 while step N runs.

``DevicePrefetcher`` wraps any batch source — a DataIter
(``io.PrefetchingIter``, ``NDArrayIter``, ``ImageRecordIter``), a
``ShardedLoader``, or a plain iterator of ``(data, labels)`` pairs —
and keeps a bounded ring of batches already RESIDENT on device: a
feeder thread calls ``jax.device_put`` with the trainer's target
``NamedSharding`` for upcoming batches while the current step computes,
so ``ShardedTrainer.step`` sees committed arrays and its own
``device_put`` is a no-op (zero H2D on the hot path, and the batch is
donation-eligible — see ``ShardedTrainer(donate_batch=True)``).

Concurrency contract: the ring is guarded by the witnessed condition
``data.prefetch`` (plain ``threading.Condition`` unless a lock witness
is enabled — the zero-cost-when-disabled idiom).  The feeder is the
ONLY reader of ``source`` while it is alive; on feeder death the
consumer takes ownership and degrades to synchronous pulls at the
correct offset, so a killed feeder mid-epoch loses no batch and the
delivered sequence stays bit-identical (chaos scenario
``training/input_stall``).

Fault sites (docs/resilience.md):

- ``data.prefetch``   — top of each feed cycle, BEFORE the source is
  touched (a kill here leaves the source position clean).  An injected
  fault degrades that one batch to a synchronous host-side hand-off
  (no async device placement), counted, never lost.
- ``data.device_put`` — around the device placement itself; retried
  once, then the batch falls back to host arrays (the trainer pays the
  H2D for that step instead).

Resume: ``state_dict()``/``load_state_dict()`` carry the consumed-batch
offset so a restored pipeline fast-forwards its source and replays the
exact remaining sequence (``ResilientLoop``'s replay contract).
"""

import threading
import time
from collections import deque
from typing import Callable, Optional

import jax

from .. import base as _base
from ..ndarray import NDArray
from ..io import DataBatch
from ..analysis.lockwitness import named_condition as _named_condition
from ..resilience.faults import inject as _inject
from ..observability import stalls as _stall_log
from ..observability.flightrecorder import active as _fr_active
from ..observability.registry import default_registry as _registry
from ..observability.trace import host_range as _host_range

__all__ = ["DevicePrefetcher", "DataPipelineError"]


class DataPipelineError(_base.MXNetError):
    """Typed failure from the mxnet_tpu.data subsystem."""


_END = object()          # source exhausted (clean end of epoch)


def _as_arrays(batch):
    """Normalize one source item to ``(kind, data_tuple, label_tuple,
    extra)`` where ``kind`` remembers the wire shape so the consumer
    sees the same type it fed in."""
    if isinstance(batch, DataBatch):
        return ("databatch", tuple(batch.data), tuple(batch.label),
                (batch.pad, batch.index))
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        data, labels = batch
        bare_d = not isinstance(data, (tuple, list))
        bare_l = not isinstance(labels, (tuple, list))
        if bare_d:
            data = (data,)
        if bare_l:
            labels = (labels,)
        return ("pair", tuple(data), tuple(labels), (bare_d, bare_l))
    raise DataPipelineError(
        f"DevicePrefetcher source yielded {type(batch).__name__}; "
        "expected a DataBatch or a (data, labels) pair")


def _rewrap(kind, data, labels, extra):
    if kind == "databatch":
        pad, index = extra
        return DataBatch(list(data), list(labels), pad=pad, index=index)
    bare_d, bare_l = extra
    return (data[0] if bare_d else data,
            labels[0] if bare_l else labels)


def _nbytes(arrays) -> int:
    n = 0
    for a in arrays:
        x = a.jax if isinstance(a, NDArray) else a
        n += int(getattr(x, "nbytes", 0) or 0)
    return n


class DevicePrefetcher:
    """Bounded ring of device-resident batches fed by a background
    thread; iterator over batches shaped like the source's.

    Parameters
    ----------
    source : DataIter-shaped object or iterator/iterable
        Must yield DataBatch or (data, labels) pairs deterministically;
        needs ``reset()`` for re-iteration / offset fast-forward.
    shardings : sequence of Sharding, or callable, optional
        Target placements for the flattened ``data + labels`` arrays —
        pass ``trainer.batch_shardings`` after ``trainer.build()``.  A
        callable is invoked per batch with the array tuple (lazy hookup
        for trainers built mid-stream).  ``None`` ships to the default
        device uncommitted to a mesh.
    depth : int
        Ring capacity (>= 1; default 2 = double buffering).  The feeder
        blocks when the ring is full — a slow consumer can never make
        the ring grow past ``depth`` (backpressure, tested).
    transform : callable, optional
        ``transform(data, labels, step) -> (data, labels)`` applied by
        the feeder AFTER device placement — the on-device augment hook
        (:class:`~mxnet_tpu.data.transforms.DeviceTransform`).
    stall_timeout : float
        Seconds the consumer waits on an empty ring before it counts a
        stall and declares its ``input.next`` phase stalled to
        ``observability.stalls``: when the wait ends that log takes one
        record with its verdict (what the feeder was inside meanwhile),
        and the flight recorder the event ``data.stall`` (diagnostic
        only; the wait itself is unbounded).
    """

    def __init__(self, source, shardings=None, depth: int = 2,
                 transform: Optional[Callable] = None,
                 stall_timeout: float = 1.0):
        if not isinstance(depth, int) or depth < 1:
            raise DataPipelineError(
                f"prefetch depth must be an int >= 1, got {depth!r}")
        if not (hasattr(source, "next") or hasattr(source, "__next__")
                or hasattr(source, "__iter__")):
            raise DataPipelineError(
                f"source {type(source).__name__} is not iterable")
        self._source = source
        self._shardings = shardings
        self._depth = depth
        self._transform = transform
        self._stall_timeout = stall_timeout
        self.batch_size = getattr(source, "batch_size", 0)

        reg = _registry()
        self._m_wait = reg.histogram(
            "mxtpu_data_input_wait_seconds",
            help="time a consumer step blocked on the prefetch ring")
        self._m_depth = reg.gauge(
            "mxtpu_data_prefetch_depth",
            help="configured DevicePrefetcher ring capacity")
        self._m_shipped = reg.counter(
            "mxtpu_data_batches_shipped_total",
            help="batches placed on device ahead of the step")
        self._m_fallback = reg.counter(
            "mxtpu_data_batches_fallback_total",
            help="batches degraded to synchronous/host hand-off")
        self._m_bytes = reg.counter(
            "mxtpu_data_bytes_shipped_total",
            help="bytes moved host->device by the feeder")
        self._m_depth.set(depth)

        # ring state — everything below is guarded by _cond's lock
        self._cond = _named_condition(
            "data.prefetch", "DevicePrefetcher ring: feeder <-> consumer "
            "hand-off and backpressure")
        self._ring: deque = deque()
        self._fed = 0            # batches successfully enqueued
        self._consumed = 0       # batches yielded to the consumer
        self._stop = False
        self._crashed: Optional[BaseException] = None
        self._finished = False
        self._stalls = 0
        self.last_wait_seconds = 0.0
        self._wait_total = 0.0
        self._skip = 0
        # per-instance tallies (the registry counters above are shared
        # process-wide by get-or-create; stats() must not conflate two
        # pipelines)
        self._n_shipped = 0
        self._n_fallback = 0
        self._n_bytes = 0
        self._thread: Optional[threading.Thread] = None
        self._start()

    # ------------------------------------------------------------ source
    def _pull(self):
        """One item from the source (feeder thread, or consumer after a
        feeder crash — never both: ownership hands off exactly once)."""
        nxt = getattr(self._source, "next", None)
        if nxt is not None and not isinstance(self._source, _IterWrap):
            return nxt()
        return next(self._source_iter)

    def _start(self):
        if not hasattr(self._source, "next"):
            # plain iterable: keep ONE iterator for the pipeline's life
            if not isinstance(self._source, _IterWrap):
                self._source = _IterWrap(self._source)
        self._source_iter = self._source
        self._thread = threading.Thread(
            target=self._feed, name="mxtpu-data-feeder", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ feeder
    def _ship(self, data, labels):
        """Place one batch on device with the target shardings.
        Returns (data, labels, shipped_bytes) — on a double
        ``data.device_put`` fault the original host arrays come back
        (the trainer's own device_put covers that step)."""
        arrays = tuple(data) + tuple(labels)
        sh = self._shardings
        if callable(sh):
            sh = sh(arrays)
        if sh is not None and len(sh) != len(arrays):
            raise DataPipelineError(
                f"{len(sh)} shardings for {len(arrays)} batch arrays")
        for attempt in (0, 1):
            try:
                _inject("data.device_put")
                out = []
                for i, a in enumerate(arrays):
                    x = a.jax if isinstance(a, NDArray) else a
                    x = jax.device_put(x, sh[i] if sh is not None else None)
                    out.append(NDArray(x))
                nd, nl = len(data), len(labels)
                return tuple(out[:nd]), tuple(out[nd:]), _nbytes(out)
            except Exception:
                if attempt:          # retried once already: degrade
                    self._m_fallback.inc()
                    self._n_fallback += 1
                    return tuple(data), tuple(labels), 0
        raise AssertionError("unreachable")   # pragma: no cover

    def _feed(self):
        main = threading.main_thread()
        try:
            while True:
                with self._cond:
                    while len(self._ring) >= self._depth and \
                            not self._stop and main.is_alive():
                        self._cond.wait(0.05)
                    if self._stop or not main.is_alive():
                        # interpreter teardown: a daemon thread calling
                        # into XLA past main-thread exit aborts the
                        # process — stop touching jax and bow out
                        return
                sync_batch = False
                try:
                    # the fault site sits BEFORE the source read so a
                    # kill here leaves the offset clean for takeover
                    _inject("data.prefetch")
                except Exception:
                    sync_batch = True      # degrade: host hand-off
                try:
                    if self._skip:  # raceguard: unguarded(feeder-exclusive: _skip is written before _start() under a joined feeder, then owned by this thread)
                        for _ in range(self._skip):  # raceguard: unguarded(feeder-exclusive: see above)
                            self._pull()
                        self._skip = 0  # raceguard: unguarded(feeder-exclusive: see above)
                    # what a stall record says this thread was inside
                    with _stall_log.phase("input.pull"):
                        item = self._pull()
                except StopIteration:
                    with self._cond:
                        self._ring.append(_END)
                        self._cond.notify_all()
                    return
                kind, data, labels, extra = _as_arrays(item)
                if sync_batch:
                    self._m_fallback.inc()
                    self._n_fallback += 1
                else:
                    with _stall_log.phase("input.ship"):
                        data, labels, nbytes = self._ship(data, labels)
                    if nbytes:
                        self._m_shipped.inc()
                        self._m_bytes.inc(nbytes)
                        self._n_shipped += 1
                        self._n_bytes += nbytes
                if self._transform is not None and not sync_batch:
                    data, labels = self._transform(data, labels,
                                                   self._fed)  # raceguard: unguarded(feeder-exclusive: _fed is only advanced by this thread while it is alive)
                with self._cond:
                    if self._stop:
                        return
                    self._ring.append((kind, data, labels, extra))
                    self._fed += 1
                    self._cond.notify_all()
        except BaseException as e:         # includes SimulatedPreemption
            with self._cond:
                self._crashed = e
                self._cond.notify_all()
            fr = _fr_active()
            if fr is not None:
                fr.record("data.feeder_crash", error=type(e).__name__,
                          fed=self._fed, detail=str(e)[:200])  # raceguard: unguarded(final diagnostic read on the dying feeder thread)

    # ---------------------------------------------------------- consumer
    def next(self):
        """The next batch, already on the mesh.  The whole call is the
        host range ``span:input.next`` (a wait, it launches nothing);
        ``last_wait_seconds`` stays the counter of the time spent on an
        empty ring."""
        with _host_range("input", "next", launches=False):
            return self._next()

    def _next(self):
        t0 = time.perf_counter()
        stalled = False
        with self._cond:
            while not self._ring and self._crashed is None \
                    and not self._finished:
                if not self._cond.wait(self._stall_timeout):
                    if not stalled:
                        stalled = True
                        self._stalls += 1
                        # recorded when next()'s phase ends, by the one
                        # stall log, with the whole wait and a verdict
                        _stall_log.declare("data.stall",
                                           consumed=self._consumed)
            if self._ring:
                item = self._ring.popleft()
                self._cond.notify_all()
            elif self._finished:
                item = _END
            else:
                item = None                # feeder crashed, ring dry
        wait = time.perf_counter() - t0
        self.last_wait_seconds = wait
        self._wait_total += wait
        self._m_wait.observe(wait)
        if item is _END:
            self._finished = True  # raceguard: unguarded(consumer-exclusive: the feeder appends _END and exits, it never reads _finished)
            raise StopIteration
        if item is None:
            if isinstance(self._crashed, DataPipelineError):  # raceguard: unguarded(write-once: set by the feeder as its last act, observed non-None under the lock above)
                # the feeder died of pipeline misuse (malformed batch,
                # bad shardings) — surface it; takeover is for kills
                raise self._crashed  # raceguard: unguarded(write-once: see above)
            return self._takeover()
        kind, data, labels, extra = item
        self._consumed += 1  # raceguard: unguarded(consumer-exclusive: only next()/_takeover() on the consumer thread advance _consumed)
        return _rewrap(kind, data, labels, extra)

    def _takeover(self):
        """Feeder died (killed/crashed): the consumer now owns the
        source and degrades to synchronous pulls at the feeder's last
        clean offset — batches keep flowing, each one counted as a
        fallback, sequence unchanged."""
        try:
            if self._skip:  # raceguard: unguarded(takeover runs only after the feeder died — the consumer inherited sole ownership of the source state)
                for _ in range(self._skip):  # raceguard: unguarded(post-crash consumer ownership: see above)
                    self._pull()
                self._skip = 0  # raceguard: unguarded(post-crash consumer ownership: see above)
            item = self._pull()
        except StopIteration:
            self._finished = True  # raceguard: unguarded(post-crash consumer ownership: see above)
            raise
        kind, data, labels, extra = _as_arrays(item)
        data, labels, _ = self._ship(data, labels)
        if self._transform is not None:
            data, labels = self._transform(data, labels, self._consumed)  # raceguard: unguarded(post-crash consumer ownership: see above)
        self._m_fallback.inc()
        self._n_fallback += 1
        self._consumed += 1  # raceguard: unguarded(post-crash consumer ownership: see above)
        self._fed += 1  # raceguard: unguarded(post-crash consumer ownership: see above)
        return _rewrap(kind, data, labels, extra)

    def __next__(self):
        return self.next()

    def __iter__(self):
        return self

    # --------------------------------------------------------- lifecycle
    def _join_feeder(self):
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None and self._thread.is_alive():
            # the feeder re-checks _stop at every blocking point within
            # 50ms, so a bounded join cannot leave a zombie reading the
            # source behind our back
            self._thread.join(timeout=5.0)
            if self._thread.is_alive():    # pragma: no cover
                raise DataPipelineError(
                    "feeder thread failed to stop within 5s")

    def reset(self):
        """Stop the feeder, reset the source, restart from offset 0."""
        self._join_feeder()
        if hasattr(self._source, "reset"):
            self._source.reset()
        with self._cond:
            self._ring.clear()
            self._fed = 0
            self._consumed = 0
            self._stop = False
            self._crashed = None
            self._finished = False
            self._skip = 0
        self._start()

    def close(self):
        self._join_feeder()

    # ------------------------------------------------------------ resume
    def state_dict(self) -> dict:
        """The source offset (batches consumed); everything else —
        ring contents, feeder position — is derived state that a
        restore rebuilds by fast-forwarding the source."""
        return {"offset": self._consumed}  # raceguard: unguarded(consumer-thread snapshot: _consumed is consumer-exclusive and ResilientLoop checkpoints between steps)

    def load_state_dict(self, state: dict):
        off = int(state.get("offset", 0))
        if off < 0:
            raise DataPipelineError(f"negative resume offset {off}")
        self._join_feeder()
        if hasattr(self._source, "reset"):
            self._source.reset()
        with self._cond:
            self._ring.clear()
            self._fed = off
            self._consumed = off
            self._stop = False
            self._crashed = None
            self._finished = False
            self._skip = off       # feeder discards these before feeding
        self._start()

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._cond:
            ring = len(self._ring)
            return {
                "depth": self._depth,
                "ring_occupancy": ring,
                "fed": self._fed,
                "consumed": self._consumed,
                "stalls": self._stalls,
                "feeder_alive": (self._thread is not None
                                 and self._thread.is_alive()),
                "crashed": (type(self._crashed).__name__
                            if self._crashed is not None else None),
                "input_wait_seconds_total": round(self._wait_total, 6),
                "last_wait_seconds": round(self.last_wait_seconds, 6),
                "batches_shipped": self._n_shipped,
                "batches_fallback": self._n_fallback,
                "bytes_shipped": self._n_bytes,
            }

    def __repr__(self):
        return (f"DevicePrefetcher(depth={self._depth}, "
                f"consumed={self._consumed}, fed={self._fed})")  # raceguard: unguarded(repr diagnostic: atomic int reads, momentary staleness is harmless)


class _IterWrap:
    """Give a plain iterable/iterator a ``next()``/``reset()`` face so
    the feeder treats every source uniformly.  ``reset`` re-invokes
    ``iter()`` on the ORIGINAL object — generators are single-shot, so
    sources that must survive reset should be DataIter-shaped or pass a
    fresh pipeline per epoch (``ResilientLoop``'s make_iter does)."""

    def __init__(self, obj):
        self._obj = obj
        self._it = iter(obj)
        self.batch_size = getattr(obj, "batch_size", 0)
        # a generator IS its own iterator: single-shot, unresettable;
        # containers / DataIters hand out fresh iterators
        self.resettable = (hasattr(obj, "reset")
                           or iter(obj) is not self._it)

    def next(self):
        return next(self._it)

    def __next__(self):
        return next(self._it)

    def reset(self):
        if not self.resettable:
            raise DataPipelineError(
                "source is a single-shot iterator (generator) — "
                "reset/offset fast-forward needs a resettable source "
                "(DataIter, ShardedLoader, or a re-iterable container); "
                "ResilientLoop replay uses a FRESH pipeline per run() "
                "instead")
        if hasattr(self._obj, "reset"):
            self._obj.reset()
        self._it = iter(self._obj)
