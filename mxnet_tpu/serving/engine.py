"""`InferenceEngine` — the online-serving front end.

One background scheduler thread owns all device work; callers interact
through ``submit()`` (async, returns an :class:`InferenceFuture`) or
``infer()`` (sync).  Two first-class execution paths:

- **decode** (GPT-2 style LMs exposing ``prefill_slots``/``decode_step``):
  continuous batching over a persistent slot-batched KV cache — new
  requests prefill into free cache rows between decode steps of the
  in-flight ones, so a long generation never blocks a short one and the
  decode matmuls stay batched at all times (Orca-style iteration-level
  scheduling; the slot cache is the XLA-static stand-in for vLLM's
  paged blocks).

- **forward** (any ``HybridBlock``, e.g. vision): classic dynamic
  batching — group same-shape requests, pad the batch dim to the bucket
  lattice, run one compiled forward, scatter rows back.

Both paths pad to a fixed shape-bucket lattice so XLA compiles once per
bucket; ``warmup()`` pre-compiles the whole lattice so no request ever
pays a compile.  The compiled step itself reuses CachedOp's
functionalization (``make_pure_fn``): parameters are swapped in as
traced arguments, inference mode, no tape.

Backpressure & overload control (docs/overload.md): the bounded
admission queue is PRIORITY-AWARE — requests carry a class
(``interactive`` / ``batch`` / ``best_effort``), batches form highest
class first, and at depth an arriving request evicts the youngest
queued request of a strictly lower class before shedding itself
(:class:`QueueFullError`, reason-labeled).  Each request can carry a
deadline, enforced while queued AND mid-generation; with
``deadline_admission`` the engine also rejects ON ARRIVAL any request
whose deadline is already infeasible given the observed queue wait and
prefill/decode latency estimates (:class:`DeadlineInfeasibleError`) so
doomed work never burns a queue slot.  An AIMD
:class:`~.overload.OverloadController` watches queue depth and
deadline misses: under sustained pressure it enters BROWNOUT — caps
``max_new_tokens`` for non-interactive classes and pauses prefix-pool
inserts before shedding anything, hard-shedding only the lowest class
at the floor — and recovers automatically.  A high-priority request
arriving with every slot busy may PREEMPT a ``best_effort`` request
mid-decode: the victim's generated-so-far prefix is parked in the
prefix pool (one compiled slot→pool row copy) and the request requeues
to resume by prefix hit, so preemption wastes almost no work.
``stats()`` exposes latency percentiles, token counters, per-class
shed/served counts and the bucket-hit/compile counters; scheduler
batches are wrapped in :mod:`~mxnet_tpu.profiler` annotations.

Paged KV memory (docs/serving.md "Paged KV"): with
``kv_layout='paged'`` the dense per-slot ``(Tmax, H, D)`` rows are
replaced by a process-wide pool of fixed-size KV PAGES plus a per-slot
page table (:mod:`.kv_pages` — the PagedAttention design), decoupling
concurrency from ``Tmax``: a slot claims pages lazily as its position
advances, so HBM is bounded by LIVE TOKENS and ``num_slots`` can far
exceed what dense rows would fit.  Admission blocks on PAGE
availability (requests wait queued, never fail, until the pool — free
pages plus evictable prefix claims — covers their prompt); pool
exhaustion mid-flight is a PAGE FAULT handled by evicting zero-reader
prefix entries, then parking the youngest lowest-class slot by
reference (its pages become an evictable prefix entry, its request
requeues to resume by prefix hit — no copy).  The prefix cache becomes
shared read-only pages: a whole-page hit is a page-table write + a
refcount bump (the dense engine's compiled masked row copy disappears;
only a partial tail page still pays one compiled page copy), and
scrub-on-NaN zeroes exactly the pages the victim's release freed.
Everything else — the bucket lattice, chunked prefill, ``warmup()``
compile freeze, greedy token parity — composes unchanged.

Sampling & speculative decode (docs/serving.md "Speculative decode"):
``submit(temperature=, top_k=, top_p=, seed=)`` opens the sampling
workload — per-request seeded PRNG keys ride the batched programs as
traced arguments (``fold_in(key, position)`` per draw), so mixed
greedy/sampled batches share one compiled program per bucket and every
request's stream is deterministic regardless of batch composition.
With ``spec_tokens=k`` the engine amortizes per-token dispatch: a cheap
DRAFTER (early exit through the first ``draft_layers`` blocks, reusing
the slot caches' leading layers) proposes ``k`` tokens per slot in one
compiled call, and ONE batched VERIFY forward — the decode step
generalized to ``(S, k+1)`` tokens, structurally the chunked-prefill
path with logits kept at every position — accepts each slot's longest
draft prefix matching the per-position seeded samples, plus one
correction/bonus token.  Accepted tokens are exactly the
non-speculative stream (greedy: longest argmax match); rejected tokens
rewind by bookkeeping (dense) or by releasing over-claimed pages back
to the pool (paged).  Faults at ``serving.draft``/``serving.verify``
degrade that cycle to plain one-token decode — speculation can slow
down, never fail or corrupt, a request.

Sharded decode (docs/serving.md "Sharded decode"): with ``mesh=`` the
engine serves TENSOR-PARALLEL over a named GSPMD mesh — one
``InferenceEngine`` drives N devices.  GPT-2 parameters are placed by
their logical sharding axes (heads/vocab/mlp over the model axis,
Megatron column/row parallel) and every per-layer KV cache shards its
HEAD dimension, so each chip holds ``1/N`` of the weights and of the
KV state; every compiled program in the (batch, seq) bucket lattice —
full prefill, chunked/offset prefill, decode step, the prefix-cache
row copy, draft/verify, the paged page-table variants — becomes ONE
pjit-partitioned executable (committed sharded operands +
``with_sharding_constraint`` on the cache outputs) with the same
donation and the same compile-freeze contract, now per (bucket, mesh)
point.  The slot/batch axis stays replicated by default or
data-shards over a second mesh axis (dense layout only).  Decode is
token-identical to the 1-device engine — sharding moves bytes, never
the math — which CPU verification pins via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Prefix reuse (docs/serving.md): with ``prefix_pool_rows > 0`` a
host-side radix tree (:mod:`.prefix_cache`) maps admitted prompt
prefixes to a reserved pool of KV cache rows; a request whose prompt
extends a cached prefix copies the matched K/V into its slot (one
compiled row-to-row masked copy) and prefills ONLY the suffix.  Prefill
itself is CHUNKED: K/V for ``[off, off+Tb)`` can be written behind an
already-populated ``[0, off)`` region, so long prompts (longer than the
largest seq bucket, or than ``prefill_chunk``) prefill in bucket-sized
chunks interleaved with decode steps — a long prompt no longer stalls
in-flight decodes.  Greedy decode is token-identical with the cache on
or off.

Hardening (docs/resilience.md): a :class:`~mxnet_tpu.resilience.Watchdog`
monitors the scheduler thread — if it dies, or (with ``hang_timeout``
set) stops heartbeating while work is pending, every queued and
in-flight request fails with :class:`EngineCrashedError` instead of
hanging its caller, and the engine is condemned.  Transient step faults
(:class:`~mxnet_tpu.resilience.RetryableFault`) are retried with a
bounded per-request budget.  ``install_signal_handlers()`` turns SIGTERM
into a graceful ``stop(drain=True)``.  ``health()`` is the
liveness/readiness probe.  Fault-injection sites on the hot paths:
``serving.scheduler`` (per cycle, outside the recovery net — a raise
here IS a scheduler crash), ``serving.prefill``, ``serving.decode_step``
and ``serving.forward`` (before each compiled call), plus the prefix
cache's ``serving.prefix_lookup`` (host radix-tree ops) and
``serving.prefix_copy`` (device row-to-row K/V copies) — faults there
degrade to a cache miss / full prefill, never fail the request, and
repeated faults disable the cache for the engine's lifetime.
"""
from __future__ import annotations

import itertools
import signal as _signal
import threading
import time
import weakref
from typing import Optional, Sequence

import numpy as onp

from ..analysis.lockwitness import (named_condition as _named_condition,
                                    named_lock as _named_lock,
                                    note_blocking as _note_blocking)
from ..observability.compiles import on_this_thread as _xla_compiles
from ..observability.flightrecorder import active as _fr_active
from ..observability.trace import active as _trace_active
from ..resilience.faults import (RetryableFault, inject as _inject,
                                 poison as _poison)
from .batcher import BucketLattice, DynamicBatcher
from .errors import (DeadlineInfeasibleError, EngineCrashedError,
                     EngineStoppedError, InvalidRequestError,
                     MigrationError, NonFiniteOutputError, QueueFullError,
                     RequestCancelledError, RequestTimeoutError,
                     ServingError)
from .kv_pages import PagedPrefixCache, PagePool
from .kv_slots import SlotAllocator, SlotState
from .kv_tiers import HostKVTier
from .metrics import ServingMetrics
from .overload import (OverloadController, PRIORITY_BATCH,
                       PRIORITY_BEST_EFFORT, PRIORITY_INTERACTIVE,
                       priority_name, priority_ordinal)
from .prefix_cache import PrefixCache
from .sampling import request_key, sample_tokens

__all__ = ["InferenceEngine", "InferenceFuture", "Request"]

# Live engines by metrics name.  An engine's name is its IDENTITY in the
# process-wide observability registry (the ``engine=`` label on every
# mxtpu_serving_* series and the ``serving:<name>`` collector key), so
# two LIVE engines must never share one — same-name re-registration
# replaces, which is right for the rebuilt-after-crash case but silently
# drops one replica's series in a fleet.  Weak values: a collected
# engine releases its name, so sequential same-name engines (tests, the
# rebuilt-engine case) keep the plain name.
_LIVE_NAMES = weakref.WeakValueDictionary()
_NAME_LOCK = _named_lock("serving.engine_names",
                         "process-wide live-engine name claims")


def _claim_engine_name(base: str, engine: "InferenceEngine") -> str:
    with _NAME_LOCK:
        name, i = base, 1
        while _LIVE_NAMES.get(name) is not None:
            i += 1
            name = f"{base}-{i}"
        _LIVE_NAMES[name] = engine
        return name


def _release_engine_name(engine: "InferenceEngine") -> None:
    """A fully stopped or condemned engine is a corpse for naming
    purposes: release its claim immediately (don't wait for GC) so a
    replacement under the same base — the fleet's rebuild-after-crash
    path — reclaims the PLAIN name and its metric series keep their
    labels across restarts."""
    with _NAME_LOCK:
        if _LIVE_NAMES.get(engine.name) is engine:
            del _LIVE_NAMES[engine.name]


class InferenceFuture:
    """Write-once result holder; safe across threads.  ``trace_id`` is
    the request's observability trace id (None with tracing disabled) —
    the handle a caller passes to ``Tracer.timeline()`` to dump the
    request's span timeline.  ``t_done`` is the ``time.monotonic()``
    instant the engine resolved the future (result or exception) — the
    server-side completion stamp, so a caller that collects futures
    after the fact can still score each request against its deadline
    without per-request waiter threads."""

    __slots__ = ("_ev", "_result", "_exc", "trace_id", "t_done")

    def __init__(self):
        self._ev = threading.Event()
        self._result = None
        self._exc = None
        self.trace_id = None
        self.t_done: Optional[float] = None

    def done(self) -> bool:
        return self._ev.is_set()

    def set_result(self, value):
        if not self._ev.is_set():
            self._result = value
            self.t_done = time.monotonic()
            self._ev.set()

    def set_exception(self, exc: BaseException):
        if not self._ev.is_set():
            self._exc = exc
            self.t_done = time.monotonic()
            self._ev.set()

    def result(self, timeout: Optional[float] = None):
        _note_blocking("serving.future_wait")
        if not self._ev.wait(timeout):
            raise TimeoutError("result() wait timed out (the request may "
                               "still complete server-side)")
        if self._exc is not None:
            raise self._exc
        return self._result


class Request:
    __slots__ = ("id", "kind", "payload", "prompt_len", "max_new_tokens",
                 "eos_id", "deadline", "future", "t_submit", "t_enqueue",
                 "t_schedule", "shape_key", "retries_left", "trace_id",
                 "priority", "preempted", "temperature", "top_k", "top_p",
                 "seed", "key", "route_hint")

    _ids = itertools.count()

    def __init__(self, kind, payload, max_new_tokens=0, eos_id=None,
                 deadline=None, priority=PRIORITY_BATCH,
                 temperature=0.0, top_k=0, top_p=1.0, seed=0,
                 route_hint=None):
        self.retries_left = 0     # engine grants the budget at submit
        # opaque routing cookie (a fleet affinity key): the engine never
        # reads it, it rides the request into the migration bundle so a
        # disaggregated router can place the decode half by the SAME
        # family key it routed the prefill by (docs/fleet.md)
        self.route_hint = None if route_hint is None else bytes(route_hint)
        # trace-id propagation crosses the scheduler thread boundary BY
        # VALUE on the request itself (no thread-locals to lose)
        self.trace_id = None
        self.id = next(self._ids)
        self.kind = kind
        self.payload = payload
        self.prompt_len = int(payload.shape[0]) if kind == "decode" else 0
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.deadline = deadline
        self.priority = priority       # ordinal into overload.PRIORITIES
        self.preempted = 0             # times preempted (slot reclaimed)
        # per-request sampling (docs/serving.md): temperature <= 0 is
        # exact greedy; key is the seeded PRNG key every draw for this
        # request folds with its absolute position — deterministic per
        # request, batch-composition-independent, and preemption/
        # speculation re-sample positions with the same key
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.key = request_key(self.seed)
        self.future = InferenceFuture()
        self.t_submit = time.monotonic()
        self.t_enqueue = self.t_submit
        self.t_schedule = None
        self.shape_key = (tuple(payload.shape), str(payload.dtype)) \
            if kind == "forward" else None

    @property
    def priority_name(self) -> str:
        return priority_name(self.priority)

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now > self.deadline


class InferenceEngine:
    """Serve a ``HybridBlock`` online.  See the module docstring.

    Parameters
    ----------
    net : HybridBlock
        Initialized model.  ``mode='decode'`` needs the serving decode
        surface (``prefill_slots``/``decode_step``/``init_slot_cache``,
        e.g. :class:`~mxnet_tpu.models.gpt2.GPT2Model`); any block
        serves in ``mode='forward'``.
    mode : 'decode' | 'forward' | None (auto-detect)
    max_batch / max_wait_us : dynamic-batching policy — a batch closes
        at ``max_batch`` requests or when the oldest has waited
        ``max_wait_us``.
    queue_depth : bounded admission queue; beyond it ``submit`` raises
        :class:`QueueFullError`.
    default_timeout : per-request deadline in seconds (None = no limit),
        overridable per ``submit``.
    num_slots : decode concurrency (KV cache rows); default
        ``max_batch``.
    max_length : decode KV length per slot; default ``net.max_length``.
    batch_buckets / seq_buckets : explicit shape lattice (defaults:
        powers of two up to ``max_batch`` / ``max_length``).
    eos_id : stop token for decode requests (overridable per submit).
    default_max_new_tokens : decode budget when ``submit`` omits it.
    hang_timeout : seconds of stale scheduler heartbeat (with work
        pending) before the watchdog condemns the engine.  ``None``
        (default) disables hang detection; dead-thread detection is
        always on while the engine runs.
    watchdog_interval : watchdog poll period in seconds.
    max_request_retries : per-request budget for retryable step faults
        (transient infra errors / injected ``RetryableFault``).
    retry_backoff : sleep before a step retry (doubles per attempt).
    prefix_pool_rows : reserved KV rows for the prefix cache (decode
        mode; 0 = disabled).  Each row costs the same HBM as one slot
        (Tmax × heads × head_dim × 2 × layers); cached prompt prefixes
        live there and are copied into a leased slot on a hit so only
        the suffix prefills.
    prefill_chunk : cap on tokens prefilled per compiled call (default:
        the largest seq bucket).  Prompts longer than it (and suffixes
        after a prefix hit) prefill in chunks of at most this many
        tokens, one chunk batch per scheduler cycle, interleaved with
        decode steps.  Also raises the admissible prompt length from
        the largest seq bucket to ``max_length - max_new_tokens``.
    prefix_min_tokens : minimum prefix length worth caching/copying —
        shorter matches prefill from scratch (a row copy costs more
        than it saves), shorter prompts are never inserted.
    prefix_fault_limit : consecutive faults at a ``serving.prefix_*``
        site (per-site streaks — a clean lookup must not launder a
        permanently failing copy path) before the cache is disabled for
        the engine's lifetime (each fault already degrades to a plain
        miss).
    guard_nonfinite : fail a request whose model output went NaN/Inf
        with :class:`NonFiniteOutputError` instead of returning garbage
        tokens (decode: a per-row ``isfinite(logits)`` flag computed
        IN-GRAPH next to the argmax, so it costs no extra device→host
        sync; forward: a host-side check of the already-fetched rows).
        The engine keeps serving — one poisoned request never condemns
        the batch or trips the watchdog.
    default_priority : class for requests whose ``submit`` omits one —
        ``'interactive'`` | ``'batch'`` (default) | ``'best_effort'``
        (docs/overload.md).
    preemption : allow an ``interactive`` request arriving with every
        slot busy to preempt a ``best_effort`` request mid-decode: the
        victim's generated-so-far prefix parks in the prefix pool (when
        usable) and the request requeues at the front of its class to
        resume by prefix hit.
    deadline_admission : reject-on-arrival requests whose deadline is
        infeasible given observed queue wait + prefill/decode latency
        estimates (:class:`DeadlineInfeasibleError`).  Engages only
        once the phase histograms hold ``deadline_min_history``
        completions; ``deadline_safety`` scales the estimate (>1 =
        shed earlier).
    brownout : run the AIMD :class:`~.overload.OverloadController` —
        under sustained queue pressure / deadline misses the engine
        caps non-interactive ``max_new_tokens``, pauses prefix-pool
        inserts, and only at the floor sheds ``best_effort`` arrivals;
        recovers automatically.  ``overload_controller`` swaps in a
        pre-tuned controller instance.
    kv_layout : ``'dense'`` (default) | ``'paged'`` — the KV memory
        layout (docs/serving.md "Paged KV").  Dense reserves a full
        ``(Tmax, H, D)`` row per slot; paged carves the cache into
        fixed-size pages with per-slot page tables, so HBM is bounded
        by live tokens and ``num_slots`` decouples from the worst-case
        request.  Greedy decode is token-identical between the two.
    page_size : positions per KV page (paged layout; must divide
        ``max_length``).  Smaller pages waste less tail capacity but
        grow the page table; 16 is the vLLM-ish default.
    num_pages : physical KV pages in the pool (paged layout).  Default
        ``num_slots * max_length / page_size`` — the dense-equivalent
        footprint; provision FEWER to serve the same concurrency in
        less memory (what tests/test_paged_kv.py provisions).  Must
        cover at least one worst-case request
        (``max_length / page_size``).  In the paged layout the prefix
        cache reserves nothing (``prefix_pool_rows`` is ignored):
        cached prefixes are evictable refcount claims on this same
        pool, so it is always enabled.
    spec_tokens : speculative decode depth ``k`` (decode mode;
        0 = off, the exact pre-speculation engine).  Each cycle a cheap
        drafter (early exit through the first ``draft_layers`` blocks,
        reusing the slot caches' leading layers — no second model)
        proposes ``k`` tokens per slot in ONE compiled call, and one
        batched VERIFY forward — the decode step generalized to
        ``(S, k+1)`` tokens — accepts the longest prefix that matches
        what the per-request seeded sampler draws at each position, so
        output streams are token-identical to the non-speculative
        engine (greedy AND sampled) and only speed varies with drafter
        quality.  See docs/serving.md "Speculative decode".
    draft_layers : transformer blocks the drafter runs before its
        early-exit LM head (must be < the model's layer count — the
        drafter has to be cheaper than the verify forward it feeds).
    mesh : sharded decode over a GSPMD mesh (decode mode; docs/
        serving.md "Sharded decode").  ``None`` (default) is the exact
        single-device engine; a device COUNT builds a tensor-parallel
        mesh over the first N local devices; an explicit
        :class:`jax.sharding.Mesh` (e.g. from
        :func:`~mxnet_tpu.parallel.make_mesh`) serves over that.
        Parameters shard by their logical axes (heads/vocab/mlp
        Megatron-style), every per-layer KV cache shards its head
        dimension, and each compiled program in the bucket lattice
        becomes one pjit-partitioned executable — token-identical to
        the 1-device engine, compile counter frozen per (bucket, mesh)
        point.  Incompatible configs (device count not dividing the
        head count, slot axis with ``kv_layout='paged'``, more devices
        than the process has) raise :class:`ServingError` at
        construction.  CPU verification:
        ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    mesh_axes : mesh axis name(s) the engine shards over (default
        ``"tp"``): the first is the MODEL axis (heads/vocab/mlp + the
        KV head dim); an optional second is the SLOT axis,
        data-sharding the KV rows (dense layout only — must divide
        ``num_slots + 1 + prefix_pool_rows``).
    name : base name for this engine's metrics identity.  The claimed
        name (``self.name``) is uniquified against every other live
        engine (``serving``, ``serving-2``, …) so fleet replicas export
        distinct ``engine=`` label sets in one registry ``collect()``;
        a garbage-collected engine releases its name, so the
        rebuilt-after-crash case still reclaims the plain one.
    """

    def __init__(self, net, mode: Optional[str] = None, *,
                 max_batch: int = 8, max_wait_us: float = 2000.0,
                 queue_depth: int = 64,
                 default_timeout: Optional[float] = None,
                 num_slots: Optional[int] = None,
                 max_length: Optional[int] = None,
                 batch_buckets: Optional[Sequence[int]] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None,
                 default_max_new_tokens: int = 16,
                 hang_timeout: Optional[float] = None,
                 watchdog_interval: float = 0.1,
                 max_request_retries: int = 2,
                 retry_backoff: float = 0.01,
                 guard_nonfinite: bool = True,
                 prefix_pool_rows: int = 0,
                 prefill_chunk: Optional[int] = None,
                 prefix_min_tokens: int = 4,
                 prefix_fault_limit: int = 3,
                 default_priority: str = "batch",
                 preemption: bool = True,
                 deadline_admission: bool = True,
                 deadline_safety: float = 1.0,
                 deadline_min_history: int = 8,
                 brownout: bool = True,
                 overload_controller: Optional[OverloadController] = None,
                 kv_layout: str = "dense",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 paged_attention: Optional[str] = None,
                 debug_parity: bool = False,
                 host_pool_bytes: int = 0,
                 tier_fault_limit: int = 3,
                 disk_tier_dir: Optional[str] = None,
                 spec_tokens: int = 0,
                 draft_layers: int = 1,
                 mesh=None,
                 mesh_axes="tp",
                 role: str = "unified",
                 name: str = "serving"):
        if mode is None:
            mode = "decode" if hasattr(net, "decode_step") and \
                hasattr(net, "prefill_slots") else "forward"
        if mode not in ("decode", "forward"):
            raise ServingError(f"mode must be 'decode'|'forward', got {mode}")
        if mode == "decode" and not hasattr(net, "prefill_slots"):
            raise ServingError(f"{type(net).__name__} lacks the serving "
                             "decode surface (prefill_slots/decode_step)")
        self.net = net
        self.mode = mode
        self.max_batch = int(max_batch)
        self.max_wait_us = float(max_wait_us)
        self.default_timeout = default_timeout
        self.eos_id = eos_id
        self.default_max_new_tokens = int(default_max_new_tokens)
        # `name` is a BASE: the claimed identity is uniquified against
        # every other LIVE engine ("serving", "serving-2", …) so two
        # replicas can never collide in the metrics registry — a fleet
        # of engines scrapes as distinct engine= label sets in one
        # collect().  A dead (collected) engine releases its name.
        self.name = _claim_engine_name(str(name), self)
        self.metrics = ServingMetrics(self.name)
        if kv_layout not in ("dense", "paged"):
            raise ServingError(f"kv_layout must be 'dense'|'paged', got "
                               f"{kv_layout!r}")
        if kv_layout == "paged" and mode != "decode":
            raise ServingError("kv_layout='paged' is a decode-mode layout "
                               "(forward mode has no KV cache to page)")
        self.kv_layout = kv_layout
        self._paged = self.kv_layout == "paged"
        # disaggregated serving (docs/serving.md "Disaggregated
        # serving"): a prefill-role engine hands each request off at
        # end-of-prefill to its migration target (falling back to
        # finishing it locally when the handoff faults); a decode-role
        # engine additionally accepts migrated requests via adopt().
        # Roles only steer the PREFERRED path — both roles remain
        # complete engines, which is what makes colocated fallback a
        # degradation instead of a failure.
        if role not in ("prefill", "decode", "unified"):
            raise ServingError(f"role must be 'prefill'|'decode'|"
                               f"'unified', got {role!r}")
        if role != "unified" and mode != "decode":
            raise ServingError(
                f"role={role!r} is a decode-mode concept (prefill/decode "
                f"disaggregation splits LM phases; forward mode has "
                f"neither)")
        self.role = role
        self._migrate_target = None

        if mode == "decode":
            self.max_length = int(max_length or net.max_length)
            if getattr(net, "max_length", None) is not None and \
                    self.max_length > net.max_length:
                raise ServingError(
                    f"max_length={self.max_length} exceeds the model's "
                    f"position table (net.max_length={net.max_length}) — "
                    "positions past it would silently clamp, not error")
            self.num_slots = int(num_slots or max_batch)
            self.lattice = BucketLattice(
                batch_buckets, seq_buckets,
                max_batch=min(self.max_batch, self.num_slots),
                max_seq=self.max_length)
            if self.lattice.max_seq > self.max_length:
                raise ServingError(
                    f"largest seq bucket {self.lattice.max_seq} exceeds "
                    f"KV length max_length={self.max_length}")
            self._alloc = SlotAllocator(self.num_slots)
            self.prefix_pool_rows = int(prefix_pool_rows)
            if self.prefix_pool_rows < 0:
                raise ServingError(f"prefix_pool_rows must be >= 0, got "
                                 f"{self.prefix_pool_rows}")
            self.prefill_chunk = int(prefill_chunk) \
                if prefill_chunk is not None else self.lattice.max_seq
            if self.prefill_chunk < 1:
                raise ServingError(f"prefill_chunk must be >= 1, got "
                                 f"{self.prefill_chunk}")
            self.prefill_chunk = min(self.prefill_chunk,
                                     self.lattice.max_seq)
            self.prefix_min_tokens = max(1, int(prefix_min_tokens))
            if self._paged:
                self.page_size = int(page_size)
                if self.page_size < 1 or self.max_length % self.page_size:
                    raise ServingError(
                        f"page_size={page_size} must be >= 1 and divide "
                        f"max_length={self.max_length} (fixed-shape page "
                        "tables need a whole number of logical pages)")
                self._n_logical = self.max_length // self.page_size
                self.num_pages = int(num_pages) if num_pages is not None \
                    else self.num_slots * self._n_logical
                if self.num_pages < self._n_logical:
                    raise ServingError(
                        f"num_pages={self.num_pages} cannot hold even one "
                        f"worst-case request ({self._n_logical} pages of "
                        f"{self.page_size}); a request could be admitted "
                        "that no amount of eviction/preemption can serve")
                self._pool = PagePool(self.num_pages, self.page_size)
                # host-authoritative page table (scheduler-thread-only,
                # like the allocator): row = slot (+ the scratch row),
                # entries init to the scratch page id.  Shipped to the
                # device as a traced argument per compiled call.
                self._page_table = onp.full(
                    (self.num_slots + 1, self._n_logical),
                    self._pool.scratch, "int32")
                self._table_dev = None
                # paged prefix cache reserves NOTHING (entries are
                # evictable refcount claims on the shared pool), so it
                # is always on; prefix_pool_rows is a dense-only knob
                self.prefix_pool_rows = 0
                self._prefix = PagedPrefixCache(
                    self._pool, min_tokens=self.prefix_min_tokens,
                    demote_hook=self._tier_demote)
            else:
                self.page_size = None
                self.num_pages = 0
                self._pool = None
                self._page_table = None
                self._prefix = PrefixCache(
                    self.prefix_pool_rows, row_base=self.num_slots + 1,
                    min_tokens=self.prefix_min_tokens) \
                    if self.prefix_pool_rows else None
            # speculative decode (docs/serving.md "Speculative decode")
            self.spec_tokens = int(spec_tokens)
            self.draft_layers = int(draft_layers)
            if self.spec_tokens < 0:
                raise ServingError(f"spec_tokens must be >= 0, got "
                                   f"{self.spec_tokens}")
            if self.spec_tokens:
                if not hasattr(net, "draft_slots") or \
                        not hasattr(net, "verify_slots"):
                    raise ServingError(
                        f"{type(net).__name__} lacks the speculative "
                        "decode surface (draft_slots/verify_slots) — "
                        "set spec_tokens=0 to serve it")
                if self.spec_tokens + 1 > self.max_length:
                    raise ServingError(
                        f"spec_tokens={self.spec_tokens} leaves no room "
                        f"for the verify window in max_length="
                        f"{self.max_length}")
                n_blocks = len(getattr(net, "blocks", ()) or ())
                if self.draft_layers < 1 or \
                        (n_blocks and self.draft_layers >= n_blocks):
                    raise ServingError(
                        f"draft_layers={self.draft_layers} must be >= 1 "
                        f"and < the model's layer count"
                        f"{f' ({n_blocks})' if n_blocks else ''} — the "
                        "drafter must be cheaper than the verify "
                        "forward")
        else:
            self.max_length = None
            self.num_slots = 0
            self.lattice = BucketLattice(batch_buckets, (1,),
                                         max_batch=self.max_batch)
            self._alloc = None
            self.prefix_pool_rows = 0
            self.prefill_chunk = None
            self.prefix_min_tokens = int(prefix_min_tokens)
            self._prefix = None
            self.page_size = None
            self.num_pages = 0
            self._pool = None
            self._page_table = None
            if int(spec_tokens):
                raise ServingError("spec_tokens is a decode-mode knob "
                                   "(forward mode has no decode loop to "
                                   "speculate)")
            self.spec_tokens = 0
            self.draft_layers = int(draft_layers)
        # tiered prefix cache (docs/serving.md "Tiered prefix cache"):
        # a bounded host-RAM spill pool behind the PAGED prefix cache —
        # evicted-at-zero-readers entries demote device→host instead of
        # vanishing, and a later radix hit promotes them back.  Other
        # layouts accept the knob but stay inert: demotion only exists
        # where eviction frees pages.
        self.host_pool_bytes = int(host_pool_bytes)
        if self.host_pool_bytes < 0:
            raise ServingError(f"host_pool_bytes must be >= 0, got "
                               f"{host_pool_bytes}")
        self.tier_fault_limit = int(tier_fault_limit)
        self.disk_tier_dir = disk_tier_dir
        self._tier = None
        self._tier_pending: dict = {}  # PrefixEntry -> in-flight TierHandle
        self._tier_timeout = 5.0       # s a slot waits on one promotion
        self._tier_gather_fn = None    # fused demote gather (lazy jit)
        self._tier_scatter_fn = None   # fused promote install (lazy jit)
        self._tier_parked = 0          # slots waiting on a promotion
        if self.host_pool_bytes and self._paged:
            # started below, once the scheduler condition exists — the
            # resolve hook pokes it, and the hook must be in place
            # before the worker thread can resolve anything
            self._tier = HostKVTier(
                self.host_pool_bytes, page_size=self.page_size,
                fault_limit=self.tier_fault_limit,
                disk_dir=self.disk_tier_dir, scope=self.name,
                metrics=self.metrics,
                # raw param: self.kv_quant is validated just below, and
                # a rejected value raises before the tier thread starts
                kv_quant=kv_quant or None)
        # sharded decode (docs/serving.md "Sharded decode") — resolved
        # AFTER the layout knobs above: validation reads num_slots /
        # prefix_pool_rows / kv_layout
        self._init_mesh(mesh, mesh_axes)
        # quantized KV pages + paged-attention kernel (docs/serving.md
        # "Quantized KV + paged attention kernel").  kv_quant='int8'
        # stores pages int8 with per-(position, head) fp32 scales
        # beside them; paged_attention picks the read arm: 'kernel'
        # (the Pallas paged kernel — pages read in place through the
        # page table) or 'gather' (the PR 11 dense-row gather, kept as
        # the reference arm).  None auto-resolves: kernel when
        # unsharded, gather under a mesh (the Pallas call is not
        # GSPMD-partitionable).
        if kv_quant not in (None, "int8"):
            raise ServingError(f"kv_quant must be None|'int8', got "
                               f"{kv_quant!r}")
        if kv_quant and not self._paged:
            raise ServingError("kv_quant='int8' requires "
                               "kv_layout='paged' — the dense layout "
                               "IS the fp32 reference arm")
        self.kv_quant = kv_quant if self._paged else None
        if paged_attention not in (None, "kernel", "gather"):
            raise ServingError(f"paged_attention must be None|'kernel'|"
                               f"'gather', got {paged_attention!r}")
        if paged_attention and not self._paged:
            raise ServingError("paged_attention picks the PAGED read "
                               "arm; set kv_layout='paged' first")
        if paged_attention == "kernel" and self.mesh is not None:
            raise ServingError(
                "paged_attention='kernel' does not compose with a "
                "serving mesh (the Pallas paged kernel is not GSPMD-"
                "partitionable); use the 'gather' arm under mesh")
        if self._paged:
            self.paged_attention = paged_attention or \
                ("gather" if self.mesh is not None else "kernel")
        else:
            self.paged_attention = None
        self._paged_kernel = self.paged_attention == "kernel"
        # debug_parity: a fp32 GATHER-arm twin cache sharing the SAME
        # page table mirrors every cache write path the plain engine
        # has (prefill/chunk/decode/scrub/tail-page copy), and each
        # step's max-abs logit delta vs the twin feeds the
        # mxtpu_serving_kv_quant_error histogram.  Restricted to
        # configurations where those ARE the only write paths — the
        # speculative window, tier promotions, migration ingress and
        # cross-engine seeding all write K/V the twin cannot see.
        self.debug_parity = bool(debug_parity)
        self._parity_caches = None
        if self.debug_parity:
            if not self._paged:
                raise ServingError("debug_parity compares against the "
                                   "fp32 paged gather arm — it needs "
                                   "kv_layout='paged'")
            if self.spec_tokens or self.host_pool_bytes or \
                    self.mesh is not None or role != "unified":
                raise ServingError(
                    "debug_parity is a single-engine debug knob: "
                    "incompatible with spec_tokens, host_pool_bytes "
                    "(tiering), mesh, and non-unified roles — those "
                    "paths write K/V the fp32 twin cache cannot "
                    "mirror")
        self.prefix_fault_limit = int(prefix_fault_limit)
        # consecutive-fault streaks, PER SITE: a clean host lookup runs
        # right before every device copy, so a shared counter could
        # never trip on a permanently failing copy path
        self._prefix_faults = {"lookup": 0, "copy": 0}
        self._prefix_disabled = False

        self.guard_nonfinite = bool(guard_nonfinite)
        # ---- overload control (docs/overload.md) ----
        self.default_priority = priority_ordinal(default_priority)
        self.preemption = bool(preemption)
        self.deadline_admission = bool(deadline_admission)
        self.deadline_safety = float(deadline_safety)
        self.deadline_min_history = int(deadline_min_history)
        self._overload = overload_controller if overload_controller \
            is not None else OverloadController(queue_depth,
                                                enabled=bool(brownout))
        self._cancels: set = set()     # futures flagged for slot reclaim
        self._timeouts_seen = 0        # controller's deadline-miss delta
        self.hang_timeout = hang_timeout
        self.watchdog_interval = float(watchdog_interval)
        self.max_request_retries = int(max_request_retries)
        self.retry_backoff = float(retry_backoff)
        self._cond = _named_condition(
            "serving.engine.cond", "admission queue + scheduler wakeups")
        self._batcher = DynamicBatcher(queue_depth, cond=self._cond)
        if self._tier is not None:
            # wake a parked scheduler the moment a promotion resolves
            self._tier.on_resolve = self._tier_wake
            self._tier.start()
        self._step_lock = _named_lock(
            "serving.engine.step", "in-flight state vs stop()/watchdog")
        self._stop_lock = _named_lock(
            "serving.engine.stop", "stop()/condemn() mutual exclusion")
        self._thread: Optional[threading.Thread] = None
        self._watchdog = None
        self._heartbeat: Optional[float] = None
        self._compiling = False
        self._cycle_busy = False
        self._inflight_fwd = ()
        self._crashed: Optional[BaseException] = None
        self._prev_handlers = None
        self._stopping = False
        self._caches = None
        # paged layout: whether every decoding slot got page coverage
        # for the full speculation window this cycle (scheduler-owned;
        # under page pressure speculation degrades to plain decode
        # instead of parking victims for an optimization)
        self._spec_pages_ok = True
        self._shape_seen = set()
        self._fwd_single = None
        self._exporter = None
        self._build_fns()
        self._register_gauges()

    # -------------------------------------------------------- sharded decode
    def _init_mesh(self, mesh, mesh_axes):
        """Resolve the ``mesh=``/``mesh_axes=`` config into a validated
        GSPMD serving mesh (docs/serving.md "Sharded decode").  Every
        incompatibility is a typed :class:`ServingError` HERE, at
        construction — a mesh that cannot shard the model must never
        surface as an XLA shape error mid-warmup.

        ``mesh`` is ``None`` (single-device, the exact pre-sharding
        engine), a device count (builds a tensor-parallel-only mesh
        over the first N local devices via
        :func:`~mxnet_tpu.parallel.make_mesh`), or an explicit
        :class:`jax.sharding.Mesh`.  ``mesh_axes`` names the mesh axes
        the engine shards over: the first is the MODEL axis (attention
        heads, vocab-parallel LM head, MLP hidden — and the KV caches'
        head dimension), an optional second is the SLOT axis
        (data-sharding the KV rows; dense layout only — physical pages
        have no stable slot mapping to shard over)."""
        self.mesh = None
        self.mesh_axes = ()
        self.mesh_devices = 1
        self._model_axis = None
        self._slot_axis = None
        self._mesh_key = "1dev"
        self._kv_ns = None
        self._param_shardings = None
        self._mesh_param_cache = {}
        self._compiles_by_mesh = {}
        if mesh is None:
            return
        if self.mode != "decode":
            raise ServingError(
                "mesh= is a decode-mode knob — forward mode has no "
                "sharded serving surface (shard the net's params with "
                "parallel.shard_params instead)")
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from ..parallel.mesh import axis_size, make_mesh
        if isinstance(mesh, bool) or (not isinstance(mesh, (int, Mesh))):
            raise ServingError(
                f"mesh= must be None, a device count, or a "
                f"jax.sharding.Mesh, got {type(mesh).__name__}")
        if isinstance(mesh, int):
            if mesh < 1:
                raise ServingError(f"mesh={mesh} must be >= 1 devices")
            devs = jax.devices()
            if len(devs) < mesh:
                raise ServingError(
                    f"mesh={mesh} needs {mesh} devices, this process has "
                    f"{len(devs)} — for CPU verification set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={mesh} "
                    "BEFORE jax initializes (docs/serving.md 'Sharded "
                    "decode')")
            mesh = make_mesh(dp=1, tp=mesh, devices=devs[:mesh])
        axes = (mesh_axes,) if isinstance(mesh_axes, str) \
            else tuple(mesh_axes)
        if not 1 <= len(axes) <= 2 or len(set(axes)) != len(axes):
            raise ServingError(
                f"mesh_axes must be one or two DISTINCT axis names "
                f"(model axis[, slot axis]), got {axes!r}")
        for a in axes:
            if a not in mesh.axis_names:
                raise ServingError(
                    f"mesh_axes entry {a!r} is not an axis of the mesh "
                    f"(axes: {tuple(mesh.axis_names)})")
        model_ax = axes[0]
        slot_ax = axes[1] if len(axes) == 2 else None
        t = axis_size(mesh, model_ax)
        heads = None
        if hasattr(self.net, "kv_heads"):
            heads = int(self.net.kv_heads()[0])
        else:
            blocks = getattr(self.net, "blocks", None) or ()
            attn = getattr(blocks[0], "attn", None) if blocks else None
            heads = getattr(attn, "_num_heads", None)
        if heads is not None and heads % t:
            raise ServingError(
                f"mesh axis {model_ax!r} spans {t} devices, which does "
                f"not divide the model's {heads} attention heads — the "
                "KV head dimension must shard evenly (grow/pad the head "
                "count or shrink the mesh)")
        if slot_ax is not None:
            d = axis_size(mesh, slot_ax)
            if self._paged:
                raise ServingError(
                    "a slot axis in mesh_axes is incompatible with "
                    "kv_layout='paged': physical pages migrate between "
                    "slots, so the page axis has no stable slot mapping "
                    "to shard over — use the model axis alone, or "
                    "kv_layout='dense'")
            rows = self.num_slots + 1 + self.prefix_pool_rows
            if rows % d:
                raise ServingError(
                    f"slot axis {slot_ax!r} ({d} devices) does not "
                    f"divide the KV row count num_slots+1+"
                    f"prefix_pool_rows={rows} — pad num_slots or "
                    "prefix_pool_rows")
        self.mesh = mesh
        self.mesh_axes = axes
        self.mesh_devices = int(mesh.size)
        self._model_axis = model_ax
        self._slot_axis = slot_ax
        self._mesh_key = "%ddev:%s" % (self.mesh_devices, ",".join(
            "%s=%d" % (a, axis_size(mesh, a)) for a in axes))
        if heads is not None:
            # per-layer cache leaves: dense (R, Tmax, H, D) rows, paged
            # (N+1, ps, H, D) pages — the HEAD axis shards either way
            # (validated above), the row axis only under a slot axis
            spec = PartitionSpec(None if self._paged else slot_ax, None,
                                 model_ax if t > 1 else None, None)
            self._kv_ns = NamedSharding(mesh, spec)

    def _place_caches(self, caches):  # guarded-by: _step_lock
        """Commit every KV cache leaf onto the mesh.  Also the RE-pin
        after eager host-side cache surgery (scrub-on-NaN, slot
        zeroing): an eager op can come back differently sharded, and a
        committed input whose sharding moved would MISS the jit cache —
        a silent recompile on traffic the warmup() freeze forbids.
        ``device_put`` of an already-correctly-placed array is a
        no-op."""
        if self._kv_ns is None:
            return caches
        import jax
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._kv_ns), caches)

    def _register_gauges(self):
        """Compile-event and bucket-lattice gauges in the process-wide
        observability registry (docs/observability.md).  Bound via
        WEAKREF: a collected engine's gauges drop out of the next
        scrape instead of resurrecting it; a new engine under the same
        name replaces the registrations."""
        from ..observability.registry import default_registry
        reg = default_registry()
        ref = weakref.ref(self)

        def bound(fn):
            def sample():
                eng = ref()
                if eng is None:
                    raise ReferenceError("engine collected")
                return fn(eng)
            return sample

        lbl = {"engine": self.metrics.name}
        reg.gauge("mxtpu_serving_queue_depth",
                  help="requests waiting in the admission queue",
                  fn=bound(lambda e: len(e._batcher)), **lbl)
        reg.gauge("mxtpu_serving_queue_depth_highwater",
                  help="deepest the admission queue has been "
                       "(capacity-planning: distance to shedding)",
                  fn=bound(lambda e: e._batcher.depth_highwater), **lbl)
        reg.gauge("mxtpu_serving_active_slots",
                  help="KV cache slots currently leased",
                  fn=bound(lambda e: e._alloc.active_count
                           if e._alloc else 0), **lbl)
        reg.gauge("mxtpu_serving_num_slots",
                  help="decode concurrency (total KV cache slots)",
                  fn=bound(lambda e: e.num_slots), **lbl)
        reg.gauge("mxtpu_serving_compile_cache_entries",
                  help="distinct compiled program shapes seen",
                  fn=bound(lambda e: len(e._shape_seen)), **lbl)
        reg.gauge("mxtpu_serving_bucket_lattice_points",
                  help="size of the (batch, seq) shape-bucket lattice "
                       "— the upper bound on compiles",
                  fn=bound(lambda e: len(e.lattice)), **lbl)
        reg.gauge("mxtpu_serving_prefix_entries",
                  help="live prefix-cache radix-tree entries",
                  fn=bound(lambda e: len(e._prefix)
                           if e._prefix is not None else 0), **lbl)
        reg.gauge("mxtpu_serving_kv_pages_total",
                  help="paged-KV page pool capacity (0 = dense layout)",
                  fn=bound(lambda e: e._pool.num_pages
                           if e._pool is not None else 0), **lbl)
        reg.gauge("mxtpu_serving_kv_pages_free",
                  help="paged-KV pages on the free list",
                  fn=bound(lambda e: e._pool.free_count
                           if e._pool is not None else 0), **lbl)
        reg.gauge("mxtpu_serving_kv_pages_shared",
                  help="paged-KV pages with >= 2 readers (prefix "
                       "sharing / park-by-reference — each would be a "
                       "duplicated row under the dense layout)",
                  fn=bound(lambda e: e._pool.shared_count
                           if e._pool is not None else 0), **lbl)
        def kv_bytes_per_token(e):
            # layout efficiency, scales INCLUDED: total KV cache bytes
            # over the token positions the layout can hold.  fp32
            # paged reads ~= layers*2*H*D*4; int8 drops to
            # ~layers*2*(H*D + 4*H) — the ~3.8x shrink the quantized
            # arm is bought for.  0 until caches materialize.
            if e._caches is None:
                return 0.0
            import jax
            leaves = jax.tree_util.tree_leaves(e._caches)
            total = sum(int(l.nbytes) for l in leaves)
            first = leaves[0]
            positions = int(first.shape[0]) * int(first.shape[1])
            return total / positions if positions else 0.0

        reg.gauge("mxtpu_serving_kv_bytes_per_token",
                  help="KV cache bytes (scale sidecars included) per "
                       "token position of the layout — the quantized-"
                       "KV density signal (0 = caches not built yet)",
                  fn=bound(kv_bytes_per_token), **lbl)
        reg.gauge("mxtpu_serving_tier_host_bytes",
                  help="host-RAM bytes held by the tiered prefix "
                       "cache's demoted KV bundles (0 = tier off)",
                  fn=bound(lambda e: e._tier.used_bytes
                           if e._tier is not None else 0), **lbl)
        reg.gauge("mxtpu_serving_tier_entries",
                  help="demoted KV bundles resident in the host (and "
                       "disk) tier",
                  fn=bound(lambda e: len(e._tier)
                           if e._tier is not None else 0), **lbl)
        reg.gauge("mxtpu_serving_tier_disabled",
                  help="1 once the tier self-disabled after its fault "
                       "limit (the engine serves from HBM only)",
                  fn=bound(lambda e: 1 if e._tier is not None
                           and not e._tier.enabled else 0), **lbl)
        reg.gauge("mxtpu_serving_mesh_devices",
                  help="devices the engine's compiled programs span "
                       "(GSPMD sharded decode; 1 = unsharded "
                       "single-device serving)",
                  fn=bound(lambda e: e.mesh_devices), **lbl)
        reg.gauge("mxtpu_serving_overload_factor",
                  help="brownout degradation factor (1.0 = normal; "
                       "lower = non-interactive token budgets capped "
                       "at this fraction)",
                  fn=bound(lambda e: e._overload.factor), **lbl)
        reg.gauge("mxtpu_serving_brownout",
                  help="1 while the overload controller is in brownout",
                  fn=bound(lambda e: 1 if e._overload.brownout else 0),
                  **lbl)

        def accept_rate(e):
            c = e.metrics.counters
            p = c["spec_tokens_proposed"]
            return c["spec_tokens_accepted"] / p if p else 0.0

        reg.gauge("mxtpu_serving_spec_draft_tokens",
                  help="speculative draft depth k (0 = speculation off)",
                  fn=bound(lambda e: e.spec_tokens), **lbl)
        reg.gauge("mxtpu_serving_spec_acceptance_rate",
                  help="accepted / proposed draft tokens — the "
                       "drafter-quality signal (the per-cycle bonus "
                       "token is not counted as proposed)",
                  fn=bound(accept_rate), **lbl)

        def compile_samples():
            eng = ref()
            if eng is None:
                raise ReferenceError("engine collected")
            # one gauge per (engine, mesh point): the per-mesh-point
            # compile freeze — stats()["compile"]["by_mesh_point"] —
            # made scrapeable, so a production dashboard can alert on
            # ANY mesh point whose count moves after warmup(), not
            # only an in-process assertion
            return [{"name": "mxtpu_serving_compiles", "kind": "gauge",
                     "labels": {"engine": eng.metrics.name,
                                "mesh_point": mp},
                     "value": n,
                     "help": "XLA compiles at this (engine, mesh "
                             "point) — frozen after warmup()"}
                    for mp, n in sorted(eng._compiles_by_mesh.items())]

        reg.register_collector(
            f"serving-compiles:{self.metrics.name}", compile_samples)

    # ------------------------------------------------------------- exporter
    def attach_exporter(self, exporter) -> "InferenceEngine":
        """Tie a :class:`~mxnet_tpu.observability.BackgroundExporter`
        to this engine's lifecycle: started here (if not already) and
        drained — final flush + join — by ``stop()``, including the
        SIGTERM path.  Returns ``self`` for chaining."""
        self._exporter = exporter
        if exporter.ident is None:       # never started
            exporter.start()
        return self

    # ------------------------------------------------------------ compiled fns
    def _build_fns(self):
        import jax
        import jax.numpy as jnp

        from ..gluon.cached_op import make_pure_fn
        from ..ndarray import NDArray

        net = self.net
        if self.mode == "decode":
            guard = self.guard_nonfinite
            kv_ns = self._kv_ns

            def pin_c(c):
                # sharded decode: constrain the cache outputs IN-GRAPH.
                # With committed sharded inputs GSPMD usually propagates
                # this anyway — the explicit constraint makes every
                # program deterministically partitioned (and keeps the
                # output sharding stable, which the jit cache keys on:
                # a drifting cache sharding would recompile on traffic)
                if kv_ns is None:
                    return c
                return jax.tree_util.tree_map(
                    lambda a: jax.lax.with_sharding_constraint(a, kv_ns),
                    c)

            def row_ok(logits_jax):
                # per-row health flag, computed IN-GRAPH next to the
                # argmax: a NaN/Inf logit row fails ITS request typed
                # instead of silently emitting an argmax over garbage.
                # Reduced over every non-row axis so (B, V) and
                # (B, T, V) logits both yield a (B,) flag.
                axes = tuple(range(1, logits_jax.ndim))
                return jnp.all(jnp.isfinite(logits_jax), axis=axes)

            def post(logits, c, temp, topk, topp, keys, fpos):
                # ONE guard/sampling post-processing body shared by
                # every prefill/chunk/step closure in both layouts —
                # parity cannot diverge between them.  fpos is the
                # absolute position of the token each row just consumed
                # (the sampler's per-request fold constant); greedy
                # rows (temperature <= 0) take the exact argmax branch,
                # bit-identical to the pre-sampling engine.
                ok = row_ok(logits.jax) if guard else \
                    jnp.ones((logits.jax.shape[0],), jnp.bool_)
                return (sample_tokens(logits.jax, temp, topk, topp,
                                      keys, fpos), ok, pin_c(c))

            spec_k = self.spec_tokens
            spec_layers = self.draft_layers

            def verify_post(logits, c, pos, temp, topk, topp, keys):
                # verify keeps logits at EVERY window position: column
                # i samples with the fold position pos + i — exactly
                # the (key, position) the non-speculative engine would
                # use when it reached that token, which is what makes
                # longest-match acceptance stream-identical.  All
                # columns sample in ONE flattened (S*W, V) call —
                # sample_tokens is row-independent, and a per-column
                # unroll would trace W copies of its two full-vocab
                # sorts into the hot verify program
                lj = logits.jax
                s, w, v = lj.shape
                ok = row_ok(lj) if guard else \
                    jnp.ones((s,), jnp.bool_)
                fpos = (pos[:, None]
                        + jnp.arange(w, dtype=jnp.int32)[None, :]
                        ).reshape(-1)
                toks = sample_tokens(
                    lj.reshape(s * w, v),
                    jnp.repeat(temp, w, axis=0),
                    jnp.repeat(topk, w, axis=0),
                    jnp.repeat(topp, w, axis=0),
                    jnp.repeat(keys, w, axis=0), fpos)
                return toks.reshape(s, w), ok, pin_c(c)

            if self._paged:
                # the paged programs take the page table as ONE extra
                # traced argument.  pk routes the attention read to the
                # Pallas paged kernel or the dense-row gather arm —
                # STATIC per engine, so it never adds a lattice point.
                # With debug_parity on, every sampling closure also
                # returns its raw logits so the scheduler can diff them
                # against the fp32 twin (one extra fetched output —
                # still zero extra programs).
                pk = self._paged_kernel
                dbg = self.debug_parity  # raceguard: unguarded(closure build: read once before the scheduler thread starts; later flips only disable the twin, never re-enable)

                def chunk(toks, lens, caches, sidx, off, temp, topk,
                          topp, keys, table):
                    logits, c = net.prefill_slots(
                        NDArray(toks), lens, caches, sidx, offset=off,
                        page_table=table, paged_kernel=pk)
                    fpos = lens - 1 if off is None else off + lens - 1
                    r = post(logits, c, temp, topk, topp, keys, fpos)
                    return r + (logits.jax,) if dbg else r

                def prefill(toks, lens, caches, sidx, temp, topk, topp,
                            keys, table):
                    return chunk(toks, lens, caches, sidx, None, temp,
                                 topk, topp, keys, table)

                def step(tok, caches, pos, temp, topk, topp, keys,
                         table):
                    logits, c = net.decode_step(NDArray(tok), caches,
                                                pos, page_table=table,
                                                paged_kernel=pk)
                    r = post(logits, c, temp, topk, topp, keys, pos)
                    return r + (logits.jax,) if dbg else r

                def verify(toks, caches, pos, temp, topk, topp, keys,
                           table):
                    logits, c = net.verify_slots(NDArray(toks), caches,
                                                 pos, page_table=table,
                                                 paged_kernel=pk)
                    return verify_post(logits, c, pos, temp, topk,
                                       topp, keys)

                # fp32 reference twins (debug_parity): the GATHER arm,
                # never quantized, sharing the live page table — same
                # page allocation decisions, bit-independent K/V
                def parity_chunk(toks, lens, caches, sidx, off, table):
                    logits, c = net.prefill_slots(
                        NDArray(toks), lens, caches, sidx, offset=off,
                        page_table=table)
                    return logits.jax, pin_c(c)

                def parity_prefill(toks, lens, caches, sidx, table):
                    return parity_chunk(toks, lens, caches, sidx,
                                        None, table)

                def parity_step(tok, caches, pos, table):
                    logits, c = net.decode_step(NDArray(tok), caches,
                                                pos, page_table=table)
                    return logits.jax, pin_c(c)

                def draft(tok, caches, pos, temp, topk, topp, keys,
                          pois, table):
                    return net.draft_slots(
                        NDArray(tok), caches, pos, spec_k, spec_layers,
                        temp, topk, topp, keys, poison=pois,
                        page_table=table)
            else:
                # dense closures call the PRE-PAGING decode surface —
                # no page_table kwarg, so any net implementing the
                # documented duck-typed contract (prefill_slots(tokens,
                # lens, caches, slot_idx, offset=)/decode_step) keeps
                # serving under the default layout
                def chunk(toks, lens, caches, sidx, off, temp, topk,
                          topp, keys):
                    logits, c = net.prefill_slots(
                        NDArray(toks), lens, caches, sidx, offset=off)
                    fpos = lens - 1 if off is None else off + lens - 1
                    return post(logits, c, temp, topk, topp, keys, fpos)

                def prefill(toks, lens, caches, sidx, temp, topk, topp,
                            keys):
                    # full prefill IS the offset=None case
                    return chunk(toks, lens, caches, sidx, None, temp,
                                 topk, topp, keys)

                def step(tok, caches, pos, temp, topk, topp, keys):
                    logits, c = net.decode_step(NDArray(tok), caches,
                                                pos)
                    return post(logits, c, temp, topk, topp, keys, pos)

                def verify(toks, caches, pos, temp, topk, topp, keys):
                    logits, c = net.verify_slots(NDArray(toks), caches,
                                                 pos)
                    return verify_post(logits, c, pos, temp, topk,
                                       topp, keys)

                def draft(tok, caches, pos, temp, topk, topp, keys,
                          pois):
                    return net.draft_slots(
                        NDArray(tok), caches, pos, spec_k, spec_layers,
                        temp, topk, topp, keys, poison=pois)

            def copy_rows(caches, src, dst, length):
                # masked row-to-row K/V copy for the prefix cache:
                # positions [0, length) of row `src` land in row `dst`,
                # the rest of `dst` is preserved.  src/dst/length are
                # traced scalars, so this is ONE compiled program for
                # every (pool->slot, slot->pool, any length) copy.  The
                # mask is not optional hygiene: unmasked row garbage
                # beyond `length` could carry NaN from a scrubbed
                # neighbour epoch, and NaN survives additive masking.
                # Under the PAGED layout axis 1 is the page dim, so the
                # SAME program is the partial-tail-page copy (positions
                # [0, length) of page `src` into page `dst`).
                import jax as _jax

                def cp(a):
                    m = (jnp.arange(a.shape[1]) < length).reshape(
                        (a.shape[1],) + (1,) * (a.ndim - 2))
                    return a.at[dst].set(jnp.where(m, a[src], a[dst]))
                return pin_c(_jax.tree_util.tree_map(cp, caches))

            self._items, pure_prefill = make_pure_fn(net, prefill, "serving_prefill")
            if self.mesh is not None:
                # one NamedSharding per parameter, from the logical axes
                # the model layer annotates (transformer.py): heads and
                # MLP hidden shard Megatron-style, the tied vocab table
                # vocab-parallel; dimensions the mesh cannot divide
                # evenly replicate (divisible_spec) — only the KV head
                # axis is a hard divisibility requirement, validated at
                # construction
                from jax.sharding import NamedSharding

                from ..parallel.sharding import (divisible_spec,
                                                 logical_axes_of)
                mapping = {"heads": self._model_axis,
                           "vocab": self._model_axis,
                           "mlp": self._model_axis}
                self._param_shardings = tuple(
                    NamedSharding(self.mesh, divisible_spec(
                        p.shape, logical_axes_of(p), self.mesh, mapping))
                    for p in self._items)
            _, pure_step = make_pure_fn(net, step, "serving_decode")
            _, pure_chunk = make_pure_fn(net, chunk, "serving_chunk")
            pure_verify = pure_draft = None
            if spec_k:
                _, pure_verify = make_pure_fn(net, verify, "serving_verify")
                _, pure_draft = make_pure_fn(net, draft, "serving_draft")
            # donate the cache buffers on TPU (in-place update, no copy of
            # the S×Tmax×H×D arrays per step); CPU jax warns on donation.
            # The DRAFT never donates: it only reads the caches (its
            # speculated K/V live in window registers) and the same
            # buffers go into the verify right after.
            if jax.default_backend() == "tpu":
                self._jit_prefill = jax.jit(pure_prefill,
                                            donate_argnums=(3,))
                self._jit_step = jax.jit(pure_step, donate_argnums=(2,))
                self._jit_chunk = jax.jit(pure_chunk, donate_argnums=(3,))
                self._jit_copy = jax.jit(copy_rows, donate_argnums=(0,))
                self._jit_verify = jax.jit(pure_verify,
                                           donate_argnums=(2,)) \
                    if spec_k else None
            else:
                self._jit_prefill = jax.jit(pure_prefill)
                self._jit_step = jax.jit(pure_step)
                self._jit_chunk = jax.jit(pure_chunk)
                self._jit_copy = jax.jit(copy_rows)
                self._jit_verify = jax.jit(pure_verify) if spec_k \
                    else None
            self._jit_draft = jax.jit(pure_draft) if spec_k else None
            self._jit_parity_prefill = None
            self._jit_parity_chunk = None
            self._jit_parity_step = None
            if self._paged and self.debug_parity:  # raceguard: unguarded(jit build: read once before the scheduler thread starts; later flips only disable the twin, never re-enable)
                _, pure_pp = make_pure_fn(net, parity_prefill,
                                          "serving_parity_prefill")
                _, pure_pc = make_pure_fn(net, parity_chunk,
                                          "serving_parity_chunk")
                _, pure_ps = make_pure_fn(net, parity_step,
                                          "serving_parity_decode")
                if jax.default_backend() == "tpu":
                    self._jit_parity_prefill = jax.jit(
                        pure_pp, donate_argnums=(3,))
                    self._jit_parity_chunk = jax.jit(
                        pure_pc, donate_argnums=(3,))
                    self._jit_parity_step = jax.jit(
                        pure_ps, donate_argnums=(2,))
                else:
                    self._jit_parity_prefill = jax.jit(pure_pp)
                    self._jit_parity_chunk = jax.jit(pure_pc)
                    self._jit_parity_step = jax.jit(pure_ps)
        else:
            def forward(xs):
                out = net(NDArray(xs))
                if isinstance(out, NDArray):
                    if self._fwd_single is None:
                        self._fwd_single = True
                    return (out.jax,)
                if self._fwd_single is None:
                    self._fwd_single = False
                return tuple(o.jax for o in out)

            self._items, pure_forward = make_pure_fn(net, forward, "serving_forward")
            self._jit_forward = jax.jit(pure_forward)

    def _params(self):
        # atomic w.r.t. any OTHER engine tracing over the same shared
        # net (fleet rebuild-and-rewarm): a mid-trace read here would
        # capture that trace's swapped-in tracers as "parameters"
        from ..gluon.cached_op import param_snapshot
        vals = param_snapshot(self._items)
        if self._param_shardings is None:
            return vals
        return self._mesh_params(vals)

    def _mesh_params(self, vals):  # guarded-by: _step_lock
        """Mesh-placed view of the live parameter payloads, cached by
        payload IDENTITY: steady-state dispatch reuses the committed
        sharded copies (zero transfers), while a payload swapped under
        the engine (``set_data``, a trainer sharing the net) re-shards
        lazily at its next dispatch — the live-weights contract of
        ``param_snapshot`` survives sharding.  The net itself is never
        touched, so a 1-device engine (or ``generate``) sharing the
        same net keeps its own placement."""
        import jax
        cache = self._mesh_param_cache
        out = []
        for i, v in enumerate(vals):
            ent = cache.get(i)
            if ent is None or ent[0] is not v:
                ent = (v, jax.device_put(v, self._param_shardings[i]))
                cache[i] = ent
            out.append(ent[1])
        return tuple(out)

    def _counted(self, key, fn, *args):
        """Run a compiled entry, counting it as a bucket hit or as the
        XLA compiles it caused.  A first call per key legitimately
        spends seconds-to-minutes in XLA compilation, so the hang
        watchdog is suspended for its duration (``_compiling``) —
        compile-time slowness must not condemn a healthy engine."""
        first = key not in self._shape_seen
        if first:
            self._shape_seen.add(key)
            self._compiling = True
        compiled0 = _xla_compiles()
        try:
            with self.metrics.span(key[0]):
                return fn(*args)
        finally:
            # what XLA was actually asked for over THIS call (compiles
            # run on the calling thread), not a guess from the first
            # call per bucket: a program that compiles again behind a
            # seen key — committed weights after set_data against the
            # uncommitted ones warmup() saw — moves the counter too
            compiled = _xla_compiles() - compiled0
            if compiled:
                self.metrics.count("compiles", compiled)
                # per-(bucket, mesh)-point accounting: one engine serves
                # exactly one mesh point, so its compiles all land under
                # its own key — stats()["compile"]["by_mesh_point"]
                # merges across engines in a sharded-vs-1-device
                # comparison
                self._compiles_by_mesh[self._mesh_key] = \
                    self._compiles_by_mesh.get(self._mesh_key, 0) \
                    + compiled
            else:
                self.metrics.count("bucket_hits")
            if first:
                self._compiling = False
                self._heartbeat = time.monotonic()

    # ---------------------------------------------------------------- lifecycle
    def start(self):
        if self._thread is not None:
            raise ServingError("engine already started")
        if self._batcher.closed:
            raise ServingError("engine cannot be restarted once stopped "
                               "— build a fresh InferenceEngine")
        self._heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._loop,
                                        name="mxnet_tpu-serving",
                                        daemon=True)
        self._thread.start()
        from ..resilience.watchdog import Watchdog
        self._watchdog = Watchdog(self._watchdog_check,
                                  self._watchdog_trip,
                                  interval=self.watchdog_interval,
                                  name="mxnet_tpu-serving-watchdog")
        self._watchdog.start()
        return self

    def stop(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the engine.  ``drain=True`` finishes everything queued
        and in flight first; ``drain=False`` fails pending AND in-flight
        requests with :class:`EngineStoppedError` immediately.  Either
        way NOTHING is silently dropped: any request still held once the
        scheduler is down (crashed scheduler, request that slipped in
        around the stop flag, engine never started) is failed with a
        typed error.  Concurrent calls serialize (the SIGTERM handler
        spawns a stop thread, which may race an explicit stop()); if a
        bounded ``timeout`` expires mid-drain the engine is left RUNNING
        (still draining, watchdog still guarding) and a ServingError is
        raised."""
        with self._stop_lock:
            self._stop_locked(drain, timeout)

    def _stop_locked(self, drain: bool, timeout: Optional[float]):
        self._batcher.close()
        if not drain and self._crashed is None:
            # a HUNG scheduler holds _step_lock mid-step: a bounded
            # acquire keeps stop() from deadlocking on it.  Futures are
            # write-once, so failing them without the lock is safe; only
            # the slot free is skipped (scheduler-owned state).
            got = self._step_lock.acquire(timeout=1.0)
            try:
                exc = EngineStoppedError("engine stopped without drain")
                for req in self._batcher.drain():
                    self._fail(req, exc)
                if got:
                    if self._alloc is not None:
                        for slot, st in list(self._alloc.items()):
                            self._alloc.free(slot)
                            self._fail(st.request, exc)
                    for req in self._inflight_fwd:
                        self._fail(req, exc)
                else:
                    # hung scheduler owns the lock: fail its riders via
                    # the race-safe snapshot, leave allocator state alone
                    for req in self._snapshot_inflight_requests():
                        self._fail(req, exc)
            finally:
                if got:
                    self._step_lock.release()
        with self._cond:
            self._stopping = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            if timeout is None:
                # unbounded drain, but stay responsive to a watchdog
                # condemnation landing mid-join (hung scheduler): once
                # condemned, its futures are failed — grant a short
                # grace, then give up on the (daemon) thread
                while t.is_alive() and self._crashed is None:
                    t.join(0.5)
                if t.is_alive():
                    t.join(2.0)
            else:
                t.join(timeout)
            if t.is_alive() and self._crashed is None:
                # still draining: leave thread + watchdog running (the
                # queued futures WILL resolve), but release the signal
                # handlers so the abandoned-engine path can't resurrect
                self.uninstall_signal_handlers()
                raise ServingError(
                    f"scheduler thread still draining after {timeout}s — "
                    "engine left running; call stop() again to keep "
                    "waiting")
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        if self._tier is not None:
            # after the scheduler is down: queued promotions fail (no
            # slot is waiting anymore), queued demotions drop
            self._tier.stop()
        # sweep: whatever survived the drain must resolve, never drop
        exc = self._crashed or EngineStoppedError(
            "engine stopped — request was never scheduled")
        for req in self._batcher.drain():
            self._fail(req, exc)
        if self._alloc is not None and (t is None or not t.is_alive()):
            for slot, st in list(self._alloc.items()):
                self._alloc.free(slot)
                self._fail(st.request, exc)
        self._thread = None
        self.uninstall_signal_handlers()
        # graceful exporter drain LAST: the final flush must see the
        # terminal counters (sweep failures included).  Never raises —
        # a broken exporter must not turn a clean stop into an error.
        exp = self._exporter
        if exp is not None:
            self._exporter = None
            try:
                exp.stop(flush=True)
            except Exception:
                pass
        # fully stopped: release the name claim so a successor under
        # the same base reclaims it (the mid-drain timeout path raised
        # above and keeps the claim — that engine is still live)
        _release_engine_name(self)

    # ------------------------------------------------------------- watchdog
    def _watchdog_check(self) -> Optional[str]:
        if self._crashed is not None:
            return None
        t = self._thread
        if t is None:
            return None
        if not t.is_alive():
            # after a requested stop a dead thread is a NORMAL exit; a
            # hang during the drain itself must still trip below, so
            # _stopping only suppresses the died-check
            return None if self._stopping else "scheduler thread died"  # raceguard: unguarded(watchdog heuristic: atomic bool read, stale value only delays one poll)
        if self.hang_timeout is not None and self._heartbeat is not None \
                and not self._compiling:
            age = time.monotonic() - self._heartbeat
            # _cycle_busy covers work that lives in NEITHER the queue
            # nor the slot allocator: a forward batch is popped before
            # the compiled call, so a hang there would otherwise look
            # idle and strand the popped futures
            busy = (not self._batcher.empty() or self._cycle_busy  # raceguard: unguarded(watchdog heuristic: atomic bool read, stale value only delays one poll)
                    or (self._alloc is not None
                        and self._alloc.active_count > 0))
            if busy and age > self.hang_timeout:
                return (f"scheduler heartbeat stale for {age:.2f}s "
                        f"(hang_timeout={self.hang_timeout}s) with work "
                        "pending")
        return None

    def _snapshot_inflight_requests(self):
        """Requests currently riding the scheduler, readable from OTHER
        threads: slot leases plus a popped forward batch.  The allocator
        is scheduler-owned, so iterating it here can race a live
        mutation (RuntimeError) — retry over the tiny window; mutation
        means the scheduler is alive and will resolve those futures
        itself."""
        fwd = list(self._inflight_fwd)
        if self._alloc is None:
            return fwd
        for _ in range(10):
            try:
                return fwd + [st.request for _s, st in self._alloc.items()]
            except RuntimeError:
                time.sleep(0.005)
        return fwd

    def _watchdog_trip(self, reason: str):
        """Condemn the engine: fail every queued and in-flight request so
        no caller blocks forever.  Runs on the watchdog thread and must
        not block on the (possibly hung) scheduler."""
        exc = EngineCrashedError(
            f"serving scheduler failed: {reason} — all pending requests "
            "failed; build a fresh InferenceEngine", engine=self.name)
        self._crashed = exc
        self.metrics.count("watchdog_trips")
        self.metrics.mark("watchdog_trip")
        # forensics: the condemnation IS the moment the evidence dies
        # with the engine — bundle before the futures are swept, so
        # the ring still holds the 30 seconds that led here
        fr = _fr_active()
        if fr is not None:
            fr.trigger("serving.crash", engine=self.name, reason=reason)
        self._batcher.close()
        with self._cond:
            self._stopping = True       # a recovered scheduler exits
            self._cond.notify_all()
        # futures are write-once and thread-safe: failing them here wins
        # the race; a zombie scheduler completing later is a no-op.  The
        # slot allocator stays untouched (scheduler-owned state).
        for req in self._batcher.drain():
            self._fail(req, exc)
        for req in self._snapshot_inflight_requests():
            self._fail(req, exc)
        # a condemned engine can never serve again: release its name so
        # the rebuilt replacement reclaims the plain one
        _release_engine_name(self)

    def condemn(self, reason: str):
        """Externally condemn the engine — the fleet router's force-stop
        path for a replica whose drain blew its deadline.  Same effect
        as a watchdog trip: every queued and in-flight request fails
        with :class:`EngineCrashedError` (write-once futures, so a
        still-running scheduler completing a request later is a no-op),
        the engine is closed to new work and cannot be restarted.  Safe
        from any thread; never blocks on the (possibly hung)
        scheduler."""
        self._watchdog_trip(f"condemned: {reason}")

    # ---------------------------------------------------------------- health
    def health(self) -> dict:
        """Liveness/readiness report for external probes.

        ``live``: the scheduler thread exists, runs, and has not been
        condemned.  ``ready``: live AND accepting new requests (not
        stopping/stopped).  Counters mirror ``stats()['resilience']``.
        """
        t = self._thread
        alive = t is not None and t.is_alive()
        live = alive and self._crashed is None
        hb_age = None if self._heartbeat is None else \
            round(time.monotonic() - self._heartbeat, 4)
        c = self.metrics.counters
        return {
            "name": self.name,
            "live": live,
            "ready": live and not self._stopping  # raceguard: unguarded(health probe: atomic bool read, a stale ready flag is corrected next probe)
            and not self._batcher.closed,
            "crashed": None if self._crashed is None else str(self._crashed),
            "heartbeat_age_s": hb_age,
            "queued": len(self._batcher),
            "active_slots": self._alloc.active_count if self._alloc else 0,
            "retries": c["retries"],
            "watchdog_trips": c["watchdog_trips"],
        }

    # ---------------------------------------------------------- SIGTERM drain
    def install_signal_handlers(self, signals=(_signal.SIGTERM,)):
        """Route the given signals (default SIGTERM — the preemption
        notice) to a graceful ``stop(drain=True)`` on a helper thread.
        Main-thread only; returns the previous handlers (restored by
        ``uninstall_signal_handlers()`` / ``stop()``)."""
        prev = {}
        for s in signals:
            prev[s] = _signal.signal(s, self._on_term_signal)
        self._prev_handlers = prev
        return prev

    def uninstall_signal_handlers(self):
        # restoring is main-thread-only (CPython rule); when stop() runs
        # on the drain helper thread the saved handlers are kept so a
        # later main-thread call can still restore them
        if self._prev_handlers and \
                threading.current_thread() is threading.main_thread():
            for s, h in self._prev_handlers.items():
                try:
                    _signal.signal(s, h)
                except (ValueError, TypeError):
                    pass
            self._prev_handlers = None

    def _on_term_signal(self, signum, frame):
        # never drain inside a signal handler (arbitrary interrupted
        # frame, possibly holding locks) — hand off to a helper thread.
        # The flight-recorder bundle ALSO runs there: the handler may
        # have interrupted a frame holding the very locks the bundle's
        # registry collect() needs, and a same-thread re-acquire is a
        # self-deadlock
        def _drain():
            fr = _fr_active()
            if fr is not None:
                # SIGTERM is the preemption notice — bundle FIRST, the
                # drain may not finish before the follow-up SIGKILL
                fr.trigger("signal.sigterm", engine=self.name,
                           signum=signum)
            self.stop(drain=True)

        threading.Thread(target=_drain,
                         name="mxnet_tpu-serving-drain",
                         daemon=True).start()

    def __enter__(self):
        if self._thread is None:
            self.start()
        return self

    def __exit__(self, *exc):
        self.stop(drain=not any(exc))

    # ------------------------------------------------------------------ submit
    #: shed reason → legacy aggregate counter (the reason-labeled
    #: breakdown rides mxtpu_serving_sheds_total{reason=,priority=})
    _SHED_COUNTER = {"queue_full": "rejected_queue_full",
                     "priority_shed": "rejected_queue_full",
                     "brownout": "rejected_queue_full",
                     "deadline_infeasible": "rejected_infeasible"}

    def _reject(self, reason: str, exc: BaseException, *,
                priority: Optional[str] = None, trace_id=None,
                request_id=None):
        """The ONE audited rejection path out of ``submit()``
        (docs/overload.md): every rejection — crashed engine, invalid
        request, queue-full / brownout shed, infeasible deadline —
        stamps exactly one aggregate counter, one reason-labeled shed
        sample (shed reasons only), and one trace event, in that
        order, then raises ``exc``."""
        counter = self._SHED_COUNTER.get(reason)
        if counter is not None:
            self.metrics.count(counter)
            self.metrics.count_shed(reason, priority or "unknown")
            self.metrics.mark("shed")
            event = "serving.shed"
        else:
            self.metrics.count("rejected_invalid" if reason == "invalid"
                               else "rejected_crashed")
            event = "serving.reject"
        tr = _trace_active()
        if tr is not None:
            tr.event(event, trace_id=trace_id, reason=reason,
                     request=request_id)
        fr = _fr_active()
        if fr is not None:
            fr.record(event, engine=self.name, reason=reason,
                      priority=priority, request=request_id,
                      trace_id=trace_id)
        raise exc

    def _shed_queued(self, victim: Request, reason: str):
        """Fail a QUEUED request shed in favor of arriving
        higher-class work (priority eviction): the same
        one-counter/one-event audit as :meth:`_reject`, but the typed
        error lands on the victim's FUTURE — its own ``submit()``
        already returned."""
        self.metrics.count(self._SHED_COUNTER[reason])
        self.metrics.count_shed(reason, victim.priority_name)
        self.metrics.mark("shed")
        tr = _trace_active()
        if tr is not None:
            tr.event("serving.shed", trace_id=victim.trace_id,
                     reason=reason, request=victim.id)
        fr = _fr_active()
        if fr is not None:
            fr.record("serving.shed", engine=self.name, reason=reason,
                      priority=victim.priority_name, request=victim.id,
                      trace_id=victim.trace_id)
        victim.future.set_exception(QueueFullError(
            f"request {victim.id} ({victim.priority_name}) evicted from "
            f"the queue by higher-priority arrival ({reason})"))

    def _brownout_shed_or_admit(self, pr: int, now: float):
        """Brownout's hard edge (docs/overload.md): at the controller
        floor, lowest-class arrivals shed on arrival; everything
        milder is degradation, not refusal."""
        if self._overload.shedding(pr, now):
            self._reject("brownout", QueueFullError(
                f"engine in brownout at floor — shedding "
                f"{priority_name(pr)} arrivals"),
                priority=priority_name(pr))

    def _feasible_or_reject(self, pr: int, mnt: int, deadline: float,
                            now: float):
        """Deadline-aware admission (docs/overload.md): estimate
        queue wait (behind same-or-higher-class work only) plus
        prefill + per-token decode time from the phase histograms; a
        deadline the estimate already overshoots is rejected ON
        ARRIVAL with :class:`DeadlineInfeasibleError`.  Engages only
        once ``deadline_min_history`` completions exist; a fault at
        ``overload.admission`` degrades to admitting (the request can
        still time out later — the gate is an optimization, never a
        correctness dependency)."""
        est = self.metrics.latency_estimates(self.deadline_min_history)
        if est is None:
            return
        try:
            _inject("overload.admission")
        except Exception:
            self.metrics.count("overload_faults")
            return
        prefill_p50, per_token, service_p50 = est
        ahead = self._batcher.depth_at_or_above(pr)
        waves = ahead / max(1, self.num_slots)
        need = (waves * service_p50 + prefill_p50
                + per_token * mnt) * self.deadline_safety
        if now + need > deadline:
            self._reject("deadline_infeasible", DeadlineInfeasibleError(
                f"deadline infeasible on arrival: estimated "
                f"{need * 1e3:.1f}ms (queue {ahead} ahead at class, "
                f"{mnt} tokens) exceeds the {(deadline - now) * 1e3:.1f}"
                f"ms remaining"), priority=priority_name(pr))

    def submit(self, x, max_new_tokens: Optional[int] = None,
               timeout: Optional[float] = None,
               eos_id: Optional[int] = None,
               priority: Optional[str] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: int = 0,
               route_hint: Optional[bytes] = None) -> InferenceFuture:
        """Enqueue one request; returns its future.

        ``temperature`` / ``top_k`` / ``top_p`` / ``seed`` are the
        request's sampling workload (decode mode; docs/serving.md):
        ``temperature <= 0`` (the default) is exact greedy argmax;
        otherwise the request samples from its
        temperature-scaled, top-k- then nucleus-filtered distribution
        with a PER-REQUEST seeded PRNG — every draw folds ``seed``'s
        key with the absolute token position, so a request's stream is
        deterministic no matter what shares its batches, and identical
        with speculation on or off.  All four ride the compiled
        programs as traced arguments: mixed greedy/sampled batches
        share one program per bucket and ``warmup()``'s compile freeze
        is untouched.

        decode mode: ``x`` is a 1-D int prompt (list/np/NDArray); the
        result is the full sequence (prompt + generated) as np.int32.
        forward mode: ``x`` is ONE example WITHOUT the batch dim; the
        result is the corresponding output row (tuple of rows for
        multi-output nets).

        ``timeout`` sets the request's SERVER-side deadline in seconds
        (``None``/``0`` = no deadline), enforced while queued and
        mid-generation — and, with ``deadline_admission``, already at
        arrival (infeasible deadlines reject with
        :class:`DeadlineInfeasibleError`).

        ``priority`` is the request's QoS class (``'interactive'`` |
        ``'batch'`` | ``'best_effort'``; default: the engine's
        ``default_priority``).  Under overload, lower classes are shed
        first, token-capped during brownout, and — lowest class only —
        preemptible mid-decode; a queued lower-class request may be
        EVICTED by a higher-class arrival (its future fails with
        :class:`QueueFullError`).  See docs/overload.md.

        ``route_hint`` is an opaque routing cookie (decode mode): the
        engine never interprets it, but a prefill-role engine copies it
        into the migration bundle so a disaggregated fleet router can
        place the decode half by the same affinity key it routed the
        prefill by (docs/fleet.md "Disaggregated serving").
        """
        try:
            pr = self.default_priority if priority is None \
                else priority_ordinal(priority)
        except ServingError as e:
            # an unknown class is the REQUEST's own fault and must obey
            # the typed-error contract like every other bad input:
            # priority_ordinal's generic ServingError is re-raised as
            # InvalidRequestError through the rejection audit so it
            # stamps exactly one counter + one trace event
            if isinstance(e, InvalidRequestError):
                raise
            self._reject("invalid", InvalidRequestError(str(e)))
        if self._crashed is not None:
            self._reject("crashed",
                         EngineCrashedError(str(self._crashed),
                                            engine=self.name),
                         priority=priority_name(pr))
        timeout = self.default_timeout if timeout is None else timeout
        now = time.monotonic()
        deadline = now + timeout if timeout else None
        if self.mode == "decode":
            import math as _math
            if not (_math.isfinite(float(temperature))
                    and float(temperature) >= 0.0) \
                    or int(top_k) < 0 \
                    or not (0.0 < float(top_p) <= 1.0):
                self._reject("invalid", InvalidRequestError(
                    f"bad sampling params: need temperature >= 0 "
                    f"(finite), top_k >= 0, 0 < top_p <= 1 — got "
                    f"temperature={temperature}, top_k={top_k}, "
                    f"top_p={top_p}"), priority=priority_name(pr))
            arr = onp.asarray(getattr(x, "asnumpy", lambda: x)(),
                              dtype="int32")
            if arr.ndim == 2 and arr.shape[0] == 1:
                arr = arr[0]        # generate-style (1, T) prompt
            if arr.ndim != 1:
                self._reject("invalid", InvalidRequestError(
                    f"a decode request is ONE prompt: expected shape (T,) "
                    f"or (1, T), got {arr.shape} — submit batch rows "
                    "individually, batching is the engine's job"),
                    priority=priority_name(pr))
            mnt = int(self.default_max_new_tokens if max_new_tokens is None
                      else max_new_tokens)
            if arr.size < 1 or mnt < 1:
                self._reject("invalid", InvalidRequestError(
                    f"need a non-empty prompt and max_new_tokens >= 1 "
                    f"(got len={arr.size}, max_new_tokens={mnt})"),
                    priority=priority_name(pr))
            # prompts longer than the largest seq bucket are fine now —
            # chunked prefill splits them — but prompt + generation must
            # fit the KV rows
            if arr.size + mnt > self.max_length:
                self._reject("invalid", InvalidRequestError(
                    f"prompt len {arr.size} + {mnt} new tokens does not "
                    f"fit the KV length ({self.max_length})"),
                    priority=priority_name(pr))
            # every VALID request counts submitted before the overload
            # gates, so every shed reason (queue_full, priority_shed,
            # brownout, deadline_infeasible) shares one denominator:
            # shed_rate = sheds_total / submitted_total holds per
            # reason (docs/overload.md)
            self.metrics.count("submitted")
            # brownout degrades before it refuses: at the controller
            # floor the lowest class sheds on arrival; above it,
            # non-interactive token budgets are capped instead
            self._brownout_shed_or_admit(pr, now)
            mnt = self._overload.cap_tokens(pr, mnt)
            if deadline is not None and self.deadline_admission:
                self._feasible_or_reject(pr, mnt, deadline, now)
            req = Request("decode", arr, mnt,
                          self.eos_id if eos_id is None else eos_id,
                          deadline, priority=pr,
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, seed=seed, route_hint=route_hint)
        else:
            if temperature or top_k or top_p != 1.0 or seed:
                self._reject("invalid", InvalidRequestError(
                    "sampling parameters (temperature/top_k/top_p/"
                    "seed) are a decode-mode surface — a forward "
                    "request has no token distribution to sample"),
                    priority=priority_name(pr))
            arr = onp.asarray(getattr(x, "asnumpy", lambda: x)())
            self.metrics.count("submitted")
            self._brownout_shed_or_admit(pr, now)
            req = Request("forward", arr, deadline=deadline, priority=pr)
        req.retries_left = self.max_request_retries
        tr = _trace_active()
        if tr is not None:
            # trace-id allocation happens on the CALLER thread; every
            # later span of this request — recorded from the scheduler
            # thread — joins it through req.trace_id
            req.trace_id = req.future.trace_id = tr.new_trace_id()
            tr.event("serving.submit", trace_id=req.trace_id,
                     request=req.id, kind=req.kind,
                     priority=req.priority_name)
        fr = _fr_active()
        if fr is not None:
            fr.record("serving.submit", engine=self.name,
                      request=req.id, kind=req.kind,
                      priority=req.priority_name, trace_id=req.trace_id)
        try:
            victim = self._batcher.put(req)
        except QueueFullError as e:
            self._reject("queue_full", e, priority=priority_name(pr),
                         trace_id=req.trace_id, request_id=req.id)
        if victim is not None:
            self._shed_queued(victim, "priority_shed")
        return req.future

    def cancel(self, fut: InferenceFuture) -> bool:
        """Actively cancel a submitted request (the fleet router's
        hedged-loser cleanup — docs/overload.md): a QUEUED request is
        dequeued and its future fails with
        :class:`RequestCancelledError`; a mid-decode request's slot is
        flagged reclaimable and the scheduler frees it at the next
        cycle.  Returns True iff a live (unresolved) request was
        found.  Safe from any thread.

        Forward mode: only QUEUED requests are cancellable — a popped
        forward batch resolves within the same scheduler cycle, so
        there is no capacity to reclaim mid-flight and ``cancel``
        reports False (the result is imminent anyway)."""
        req = self._batcher.remove(fut)
        if req is not None:
            self._fail(req, RequestCancelledError(
                f"request {req.id} cancelled while queued"))
            return True
        if fut.done() or self.mode == "forward":
            return False
        for r in self._snapshot_inflight_requests():
            if r.future is fut:
                with self._cond:
                    self._cancels.add(fut)
                    self._cond.notify_all()
                return True
        return False

    def force_brownout(self, reason: str = "external") -> None:
        """Slam the overload controller to its floor — the fleet
        router's coordinated-brownout hook for an all-replicas-
        saturated fleet.  Recovery is automatic (AIMD).  Safe from any
        thread; a no-op when brownout is disabled."""
        was = self._overload.brownout
        self._overload.force()
        if not was and self._overload.brownout:
            self.metrics.count("brownouts")
            self.metrics.mark("brownout", reason)
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.brownout", engine=self.name,
                          reason=reason)

    def coordinate_overload(self, factor_cap: Optional[float] = None,
                            deadline_safety: Optional[float] = None
                            ) -> None:
        """Fleet-coordination surface (docs/fleet.md "Elastic fleet"):
        an external controller with aggregate visibility — the fleet
        autoscaler — drives this engine's brownout factor cap and
        deadline-admission safety margin.  Both compose with the local
        loops instead of replacing them: the effective brownout factor
        is ``min(local AIMD factor, fleet cap)``, and the safety margin
        scales the admission-time service estimate.  Safe from any
        thread (GIL-atomic float writes — the same contract as the
        controller's own submit-side queries)."""
        if factor_cap is not None:
            entered = self._overload.set_fleet_cap(factor_cap)
            if entered:
                self.metrics.count("brownouts")
                self.metrics.mark("brownout", "fleet_coordinated")
                fr = _fr_active()
                if fr is not None:
                    fr.record("serving.brownout", engine=self.name,
                              reason="fleet_coordinated")
        if deadline_safety is not None:
            if deadline_safety <= 0:
                raise ServingError(
                    f"deadline_safety must be > 0, got {deadline_safety}")
            self.deadline_safety = float(deadline_safety)

    def infer(self, x, max_new_tokens: Optional[int] = None,
              timeout: Optional[float] = None,
              eos_id: Optional[int] = None,
              priority: Optional[str] = None,
              temperature: float = 0.0, top_k: int = 0,
              top_p: float = 1.0, seed: int = 0):
        """Synchronous ``submit()`` + wait.  ``timeout`` is the SERVER
        deadline; the wait itself is unbounded — the scheduler resolves
        every future (result, typed timeout, or engine error), so a
        timed-out request always surfaces as
        :class:`RequestTimeoutError`, never a bare client-side wait
        timeout (a fixed client grace could expire during a long first
        compile and mask the typed error)."""
        if self._thread is None:
            raise ServingError("engine not started — call start() or use "
                               "the context manager (submit() alone may "
                               "queue pre-start, but a sync infer() would "
                               "block forever)")
        fut = self.submit(x, max_new_tokens, timeout, eos_id, priority,
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, seed=seed)
        return fut.result(None)

    # ---------------------------------------------------------------- sampling
    def _zero_samp(self, n: int):
        """Greedy-default per-row sampling args (temperature, top_k,
        top_p, keys) — what warmup traces with and what padding rows
        carry.  Dtypes must match the live-traffic arrays exactly or
        the jit cache would miss on the first real batch."""
        import jax.numpy as jnp
        return (jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.int32),
                jnp.ones((n,), jnp.float32),
                jnp.zeros((n, 2), jnp.uint32))

    @staticmethod
    def _samp_rows(reqs, n):
        """Host-side per-row sampling arrays for a batch of ``n`` rows
        whose first ``len(reqs)`` carry the given requests (the rest
        are padding at greedy defaults).  Returned as numpy; callers
        convert once per dispatch."""
        temp = onp.zeros((n,), "float32")
        topk = onp.zeros((n,), "int32")
        topp = onp.ones((n,), "float32")
        keys = onp.zeros((n, 2), "uint32")
        for i, r in enumerate(reqs):
            temp[i] = r.temperature
            topk[i] = r.top_k
            topp[i] = r.top_p
            keys[i] = r.key
        return temp, topk, topp, keys

    # ------------------------------------------------------------------ warmup
    def warmup(self, example_shape: Optional[Sequence[int]] = None,
               dtype: str = "float32") -> int:
        """Pre-compile the whole bucket lattice so live traffic never
        pays an XLA compile.  Decode mode compiles the decode step,
        every (batch, seq) full-prefill point, the CHUNK-prefill lattice
        (same points, capped at the ``prefill_chunk`` bucket — offset
        prefill is a distinct program), and the prefix-cache row copy
        (one program; src/dst/length are traced); forward mode needs the
        per-example ``example_shape`` (no batch dim).  Requires an idle
        engine (no in-flight decodes).  Returns the number of programs
        compiled — after this the ``compiles`` counter must not move."""
        import jax.numpy as jnp

        with self._step_lock:
            before = self.metrics.counters["compiles"]
            params = self._params()
            if self.mode == "decode":
                if self._alloc.active_count:
                    raise ServingError("warmup needs an idle engine "
                                       "(decode writes would collide with "
                                       "in-flight slots)")
                self._ensure_caches()
                s1 = self.num_slots + 1
                zeros = jnp.zeros((s1,), jnp.int32)
                # the paged programs take the page table as one extra
                # traced arg — its SHAPE is fixed at construction, so
                # the lattice (and the compile freeze) is untouched:
                # one program per (bucket, page-table) point where the
                # page-table side has exactly one point.  The sampling
                # params (temp/top-k/top-p/key per row) are traced
                # args shaped by the batch bucket — same story.
                tbl = (self._table_arg(),) if self._paged else ()
                res = self._counted(
                    ("decode",), self._jit_step, params, zeros,
                    self._caches, zeros, *self._zero_samp(s1), *tbl)
                self._caches = res[2]
                if self._parity_caches is not None:
                    # debug_parity twins compile alongside their
                    # primaries — after warmup() the parity mirrors on
                    # traffic are bucket hits like everything else
                    _, self._parity_caches = self._counted(
                        ("parity_decode",), self._jit_parity_step,
                        params, zeros, self._parity_caches, zeros,
                        *tbl)
                if self.spec_tokens:
                    # the (bucket, k) lattice's k-side points: ONE
                    # draft and ONE verify program at the fixed
                    # (S+1, k) / (S+1, k+1) shapes — after this the
                    # compile counter must stay frozen through any mix
                    # of speculative and plain cycles
                    draft = self._counted(
                        ("draft",), self._jit_draft, params, zeros,
                        self._caches, zeros, *self._zero_samp(s1),
                        jnp.asarray(0.0, jnp.float32), *tbl)
                    # the verify window as the live cycle builds it,
                    # from the draft's own output: under a mesh that
                    # output is a committed sharded array, and a window
                    # of fresh zeros would warm a program the first
                    # live cycle cannot use (it compiled verify again)
                    toks2 = jnp.concatenate([zeros[:, None], draft],
                                            axis=1)
                    _vt, _ok, self._caches = self._counted(
                        ("verify",), self._jit_verify, params, toks2,
                        self._caches, zeros, *self._zero_samp(s1),
                        *tbl)
                scratch = self._alloc.scratch
                for bb, tb in self.lattice.prefill_points(
                        self.prefill_chunk):
                    toks = jnp.zeros((bb, tb), jnp.int32)
                    lens = jnp.ones((bb,), jnp.int32)
                    sidx = jnp.full((bb,), scratch, jnp.int32)
                    res = self._counted(
                        ("prefill", bb, tb), self._jit_prefill, params,
                        toks, lens, self._caches, sidx,
                        *self._zero_samp(bb), *tbl)
                    self._caches = res[2]
                    off = jnp.zeros((bb,), jnp.int32)
                    res = self._counted(
                        ("chunk", bb, tb), self._jit_chunk, params,
                        toks, lens, self._caches, sidx, off,
                        *self._zero_samp(bb), *tbl)
                    self._caches = res[2]
                    if self._parity_caches is not None:
                        _, self._parity_caches = self._counted(
                            ("parity_prefill", bb, tb),
                            self._jit_parity_prefill, params, toks,
                            lens, self._parity_caches, sidx, *tbl)
                        _, self._parity_caches = self._counted(
                            ("parity_chunk", bb, tb),
                            self._jit_parity_chunk, params, toks,
                            lens, self._parity_caches, sidx, off,
                            *tbl)
                if self._prefix is not None:
                    # dense: row-to-row prefix copy; paged: the same
                    # program IS the partial-tail-page copy (scratch
                    # page onto itself, length 0 — a no-op trace)
                    scr = jnp.asarray(self._pool.scratch if self._paged
                                      else scratch, jnp.int32)
                    self._caches = self._counted(
                        ("prefix_copy",), self._jit_copy, self._caches,
                        scr, scr, jnp.asarray(0, jnp.int32))
                    if self._parity_caches is not None:
                        # the twin's copy is a distinct jit-cache entry
                        # (fp32 tree vs the primary's int8+scale tree)
                        self._parity_caches = self._counted(
                            ("parity_copy",), self._jit_copy,
                            self._parity_caches, scr, scr,
                            jnp.asarray(0, jnp.int32))
            else:
                if example_shape is None:
                    raise ServingError("forward-mode warmup needs "
                                       "example_shape (per-example, no "
                                       "batch dim)")
                shape_key = (tuple(int(d) for d in example_shape),
                             str(onp.dtype(dtype)))
                for bb in self.lattice.batch_buckets:
                    xs = jnp.zeros((bb,) + shape_key[0],
                                   onp.dtype(dtype).name)
                    self._counted(("forward", bb) + shape_key,
                                  self._jit_forward, params, xs)
            return self.metrics.counters["compiles"] - before

    def lower_decode(self):
        """The decode-step program lowered at the shapes ``warmup()``
        and live traffic run it with (``jax.stages.Lowered``):
        ``.compile()`` gives its ``as_text()`` / ``memory_analysis()``.
        Consumes no donated buffer."""
        import jax.numpy as jnp

        if self.mode != "decode":
            raise ServingError("lower_decode() needs a decode-mode engine")
        with self._step_lock:
            self._ensure_caches()
            s1 = self.num_slots + 1
            zeros = jnp.zeros((s1,), jnp.int32)
            tbl = (self._table_arg(),) if self._paged else ()
            return self._jit_step.lower(
                self._params(), zeros, self._caches, zeros,
                *self._zero_samp(s1), *tbl)

    # ------------------------------------------------- disaggregated serving
    def migrate_to(self, target) -> "InferenceEngine":
        """Attach this prefill-role engine's migration egress
        (docs/serving.md "Disaggregated serving").  ``target`` is a
        callable ``(bundle, future) -> None`` — typically a decode-role
        engine's :meth:`adopt` or the fleet router's decode-placement
        shim — that must RAISE to refuse the handoff; any refusal makes
        the prefill engine finish that request itself (colocated
        fallback).  Returns ``self`` for chaining."""
        if self.role != "prefill":
            raise ServingError(
                f"migrate_to() is the prefill-role egress; engine "
                f"{self.name!r} has role={self.role!r}")
        self._migrate_target = target
        return self

    def adopt(self, bundle, future=None):
        """Decode-side ingress of a migrated request: verify the
        bundle's tree digest, claim a KV slot (+ pages under the paged
        layout), install the prefilled K/V into this engine's own
        storage, and resume the request at its accepted position —
        token-identically, because every sampling draw folds the
        request's seeded key with its ABSOLUTE position.  Runs on the
        CALLER's thread under ``_step_lock`` (the ``warmup()``
        precedent for caller-thread engine mutation); the resumed slot
        joins the scheduler's next decode cycle like any other.

        ``future`` (optional) is the origin request's
        :class:`InferenceFuture` — passing it makes the original
        submitter's handle resolve with the migrated result.  Returns
        the future that will carry ``prompt + generated``.

        Every refusal is typed and claims nothing it doesn't release:
        :class:`MigrationDigestError` for a torn bundle (checked FIRST
        — the pool is untouched), :class:`MigrationError` for
        role/layout/capacity mismatches.  The prefill side catches all
        of them and degrades to colocated."""
        from .migration import verify_bundle
        if self.role == "prefill":
            raise ServingError(
                f"adopt() is the decode-side ingress; engine "
                f"{self.name!r} has role='prefill'")
        if self.mode != "decode":
            raise ServingError("adopt() is a decode-mode surface "
                               "(forward mode has no KV to adopt)")
        if self._crashed is not None:
            raise EngineCrashedError(str(self._crashed), engine=self.name)
        if self._stopping or self._batcher.closed:  # raceguard: unguarded(advisory early refusal: atomic bool read; a stop racing past it just means the adopted rider is swept typed at the drain like any in-flight request — failed over by a fleet, never lost)
            raise EngineStoppedError(
                f"engine {self.name!r} is stopping — cannot adopt")
        # digest FIRST: a torn transfer is refused before any claim,
        # so rejection has nothing to undo
        verify_bundle(bundle)
        if bundle.layout != self.kv_layout:
            raise MigrationError(
                f"bundle layout {bundle.layout!r} != engine kv_layout "
                f"{self.kv_layout!r} — KV bytes are not portable "
                f"across layouts")
        if self._paged and bundle.page_size != self.page_size:
            raise MigrationError(
                f"bundle page_size={bundle.page_size} != engine "
                f"page_size={self.page_size}")
        if getattr(bundle, "kv_quant", None) != self.kv_quant:
            # int8 codes + scale sidecars are one storage contract —
            # never scatter one arm's leaves into the other's pool
            raise MigrationError(
                f"bundle kv_quant={getattr(bundle, 'kv_quant', None)!r} "
                f"!= engine kv_quant={self.kv_quant!r} — KV bytes are "
                f"not portable across storage arms")
        if self.debug_parity:  # raceguard: unguarded(advisory refusal: a stale True after the twin self-disables just rejects one adoption — conservative, never unsafe)
            # the fp32 parity twin only mirrors tokens THIS engine
            # computed; adopted K/V has no twin-side history, so the
            # divergence contract would report phantom error
            raise MigrationError(
                f"engine {self.name!r} runs debug_parity — adoption "
                f"would desynchronise the reference twin")
        if bundle.prompt_len + bundle.max_new_tokens > self.max_length:
            raise MigrationError(
                f"prompt len {bundle.prompt_len} + "
                f"{bundle.max_new_tokens} new tokens does not fit the "
                f"KV length ({self.max_length})")
        with self._step_lock:
            # the fault site guards the whole ingress: an injected
            # fault refuses the bundle before any claim and the
            # prefill side serves the request colocated
            _inject("serving.migrate_in", scope=self.name)
            if self._alloc.free_count == 0:
                raise MigrationError(
                    f"no free KV slot on {self.name!r}")
            self._ensure_caches()
            req = Request("decode", bundle.prompt,
                          bundle.max_new_tokens, bundle.eos_id,
                          bundle.deadline, priority=bundle.priority,
                          temperature=bundle.temperature,
                          top_k=bundle.top_k, top_p=bundle.top_p,
                          seed=bundle.seed)
            req.trace_id = bundle.trace_id
            if future is not None:
                req.future = future
            req.retries_left = self.max_request_retries
            st = SlotState(req, req.prompt_len, req.max_new_tokens,
                           tokens=req.payload)
            slot = self._alloc.alloc(st)
            try:
                self._install_kv(slot, st, bundle)
            except BaseException:
                self._release(slot)
                raise
            # adoption IS this engine's admission: same counters, so
            # shed/served rates keep their submitted denominator
            self.metrics.count("submitted")
            self.metrics.count("admitted")
            self.metrics.count("prompt_tokens", st.prompt_len)
            now = time.monotonic()
            req.t_schedule = now
            st.filled = st.prompt_len
            st.t_first = now
            # donate the adopted prompt to the LOCAL prefix cache:
            # this is what turns a decode replica into the residency
            # the fleet directory advertises — followers of a hot
            # family land here and hit
            self._prefix_insert(st, slot)
            st.advance(bundle.first_token)
            self.metrics.count("migrations_in")
            self.metrics.count("migrated_pages", bundle.n_pages)
            self.metrics.count_migration("in", "ok")
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.migrate_in", engine=self.name,
                          request=req.id, source=bundle.source,
                          pages=bundle.n_pages,
                          prompt_len=bundle.prompt_len,
                          trace_id=req.trace_id)
            self._finish_if_done(slot, st)
        with self._cond:
            self._cond.notify_all()   # wake an idle scheduler
        return req.future

    def _install_kv(self, slot: int, st: SlotState, bundle):  # guarded-by: _step_lock
        """Write a migrated bundle's arrays into this engine's own KV
        storage.  Paged: claim exactly the pages the prompt needs
        (evicting zero-reader prefix entries under pressure, like
        admission), scatter each cache leaf's page rows, point the
        slot's page-table row at the new pages.  Dense: set the slot's
        first ``prompt_len`` rows.  All writes are EAGER jax ops
        (``.at[].set`` + re-placement) — the :meth:`_scrub_pages`
        cache-surgery idiom — so adoption adds zero entries to the
        compile cache and the post-warmup freeze holds on both roles."""
        import jax
        import jax.numpy as jnp
        flat, treedef = jax.tree_util.tree_flatten(self._caches)
        if len(flat) != len(bundle.arrays):
            raise MigrationError(
                f"bundle carries {len(bundle.arrays)} cache leaves, "
                f"engine has {len(flat)} — model mismatch")
        if self._paged:
            need = self._pool.pages_for(bundle.prompt_len)
            if need != bundle.n_pages:
                raise MigrationError(
                    f"bundle carries {bundle.n_pages} pages but "
                    f"prompt_len={bundle.prompt_len} needs {need} at "
                    f"page_size={self.page_size}")
            pages = self._claim_pages(need)
            if pages is None:
                self.metrics.count("page_faults")
                raise MigrationError(
                    f"page pool on {self.name!r} cannot cover {need} "
                    f"pages (free + evictable short)")
            st.pages.extend(pages)
            pids = jnp.asarray(onp.asarray(pages, "int32"))
            new = [leaf.at[pids].set(jnp.asarray(arr))
                   for leaf, arr in zip(flat, bundle.arrays)]
            self._page_table[slot, :need] = pages
            self._table_dirty()
        else:
            new = [leaf.at[slot, :bundle.prompt_len].set(jnp.asarray(arr))
                   for leaf, arr in zip(flat, bundle.arrays)]
        self._caches = self._place_caches(
            jax.tree_util.tree_unflatten(treedef, new))

    # ------------------------------------------------------- prefix seeding
    @staticmethod
    def _entry_tokens(entry):
        """Reconstruct the token sequence a prefix-cache entry spells
        by concatenating radix-node edges root→node — the tree stores
        path-compressed edges, so the full sequence lives only on the
        path."""
        edges = []
        node = entry.node
        while node is not None:
            edges.append(node.edge)
            node = node.parent
        toks: list = []
        for e in reversed(edges):
            toks.extend(e)
        return toks

    def export_prefix_seeds(self, limit: Optional[int] = None):
        """Host-copy this engine's cached prefix entries as digest-
        stamped :class:`~.migration.PrefixSeed` bundles, hottest (most
        recently used) first — the scale-down drain path (docs/fleet.md
        "Elastic fleet").  Runs on the CALLER's thread under
        ``_step_lock`` (the :meth:`adopt` precedent), so it is safe
        both against a live scheduler and on a drained/stopped engine
        whose caches are still resident.  Best-effort by design: an
        engine with no prefix cache, no entries, or dropped device
        caches exports ``[]``."""
        from .migration import PrefixSeed, seed_digest
        if self.mode != "decode" or self._prefix is None:
            return []
        import jax
        import jax.numpy as jnp
        seeds = []
        with self._step_lock:
            if self._caches is None or not len(self._prefix):
                return []
            flat = jax.tree_util.tree_leaves(self._caches)
            entries = sorted(self._prefix._entries,
                             key=lambda e: e.last_used, reverse=True)
            if limit is not None:
                entries = entries[:int(limit)]
            for entry in entries:
                try:
                    tokens = self._entry_tokens(entry)
                    if self._paged:
                        pids = jnp.asarray(
                            onp.asarray(entry.pages, "int32"))
                        arrays = [onp.asarray(leaf[pids])
                                  for leaf in flat]
                    else:
                        arrays = [onp.asarray(leaf[entry.row,
                                                   :entry.length])
                                  for leaf in flat]
                    s = PrefixSeed(
                        source=self.name, layout=self.kv_layout,
                        page_size=self.page_size if self._paged else 0,
                        tokens=tokens, length=entry.length,
                        arrays=arrays, kv_quant=self.kv_quant)
                    s.digest = seed_digest(s)
                    seeds.append(s)
                except Exception:
                    continue     # one unreadable entry must not void the rest
        self.metrics.count("prefix_seeds_out", len(seeds))
        return seeds

    def seed_prefix(self, seed) -> bool:
        """Plant one migrated :class:`~.migration.PrefixSeed` into this
        engine's prefix cache — the survivor side of loss-free
        scale-down.  Verifies the digest FIRST (nothing to undo on a
        torn seed), then, under ``_step_lock``:

        - **dense**: reserve a pool row through the ordinary
          ``PrefixCache.insert`` path and install the K/V with eager
          ``.at[row, :length].set`` writes (the :meth:`_install_kv`
          cache-surgery idiom — zero compile-cache entries, so the
          post-warmup freeze holds);
        - **paged**: claim fresh pages, eager-write the seed's page
          contents, then hand the claims to the cache — the
          ``PagedPrefixCache.insert`` entry takes its OWN refcount on
          every page and this method releases the allocation claims,
          leaving the entry as sole owner (the refcount-claim handoff:
          eviction later frees the pages exactly like any cached
          prefix).

        Returns True iff the seed now backs a cache entry.  Refusals
        are typed (digest/layout mismatch) or False (already cached,
        pool full of pinned entries, engine without a prefix cache) —
        seeding is an optimization and must never fail a fleet
        operation."""
        from .migration import verify_seed
        verify_seed(seed)
        if self.mode != "decode" or self._prefix is None:
            return False
        if seed.layout != self.kv_layout:
            raise MigrationError(
                f"seed layout {seed.layout!r} != engine kv_layout "
                f"{self.kv_layout!r} — KV bytes are not portable "
                f"across layouts")
        if self._paged and seed.page_size != self.page_size:
            raise MigrationError(
                f"seed page_size={seed.page_size} != engine "
                f"page_size={self.page_size}")
        if getattr(seed, "kv_quant", None) != self.kv_quant:
            raise MigrationError(
                f"seed kv_quant={getattr(seed, 'kv_quant', None)!r} != "
                f"engine kv_quant={self.kv_quant!r} — KV bytes are not "
                f"portable across storage arms")
        if self.debug_parity:  # raceguard: unguarded(advisory refusal: a stale True after the twin self-disables just skips one seed — conservative, never unsafe)
            # seeded K/V has no twin-side history — planting it would
            # turn the divergence contract into phantom error.  Seeding
            # is an optimization, so this is a refusal, not a fault.
            return False
        if seed.length > self.max_length or \
                seed.length < self.prefix_min_tokens:
            return False
        import jax
        import jax.numpy as jnp
        with self._step_lock:
            if self._crashed is not None or not self._prefix_usable():
                return False
            self._ensure_caches()
            flat, treedef = jax.tree_util.tree_flatten(self._caches)
            if len(flat) != len(seed.arrays):
                raise MigrationError(
                    f"seed carries {len(seed.arrays)} cache leaves, "
                    f"engine has {len(flat)} — model mismatch")
            tokens = [int(t) for t in seed.tokens]
            if self._paged:
                need = self._pool.pages_for(seed.length)
                if need != len(seed.arrays[0]):
                    raise MigrationError(
                        f"seed carries {len(seed.arrays[0])} pages but "
                        f"length={seed.length} needs {need} at "
                        f"page_size={self.page_size}")
                pages = self._claim_pages(need)
                if pages is None:
                    self.metrics.count("page_faults")
                    return False
                pids = jnp.asarray(onp.asarray(pages, "int32"))
                new = [leaf.at[pids].set(jnp.asarray(arr))
                       for leaf, arr in zip(flat, seed.arrays)]
                entry = self._prefix.insert(tokens, pages, seed.length)
                # handoff: the entry's own refs (taken by insert) now
                # carry the pages; the allocation claims drop either
                # way — on a refused insert (family already cached)
                # this releases the pages entirely
                for pid in pages:
                    self._pool.unref(pid)
                if entry is None:
                    return False
            else:
                entry = self._prefix.insert(tokens)
                if entry is None:
                    return False
                new = [leaf.at[entry.row, :seed.length]
                       .set(jnp.asarray(arr))
                       for leaf, arr in zip(flat, seed.arrays)]
            try:
                self._caches = self._place_caches(
                    jax.tree_util.tree_unflatten(treedef, new))
            except BaseException:
                # the mapping must never outlive a failed install — a
                # tree pointing at a row/pages that do not hold what
                # they promise is silent corruption
                self._prefix.remove(entry)
                raise
            self.metrics.count("prefix_seeds_in")
            self.metrics.count("prefix_inserts")
        return True

    # ------------------------------------------------------------------- stats
    def stats(self) -> dict:
        s = self.metrics.stats()
        s["engine"] = {
            "name": self.name,
            "mode": self.mode,
            "queued": len(self._batcher),
            "active_slots": self._alloc.active_count if self._alloc else 0,
            "num_slots": self.num_slots,
            "batch_buckets": list(self.lattice.batch_buckets),
            "seq_buckets": list(self.lattice.seq_buckets)
            if self.mode == "decode" else None,
            "prefill_chunk": self.prefill_chunk,
            "prefix_pool_rows": self.prefix_pool_rows,
            "prefix_entries": len(self._prefix)
            if self._prefix is not None else 0,
            "prefix_disabled": self._prefix_disabled,  # raceguard: unguarded(stats snapshot: atomic bool read, staleness bounded by one cycle)
            "running": self._thread is not None,
            "crashed": self._crashed is not None,
            "default_priority": priority_name(self.default_priority),
            "preemption": self.preemption,
            "deadline_admission": self.deadline_admission,
            "spec_tokens": self.spec_tokens,
            "draft_layers": self.draft_layers,
            "role": self.role,
            "migrate_target": self._migrate_target is not None,  # raceguard: unguarded(stats snapshot: atomic ref read, staleness bounded by one cycle)
        }
        # KV capacity accounting (docs/serving.md "Paged KV"): slot
        # occupancy always; page-pool occupancy under the paged layout
        c = self.metrics.counters
        s["slots"] = {
            "kv_layout": self.kv_layout,
            "num_slots": self.num_slots,
            "active": self._alloc.active_count if self._alloc else 0,
            "active_highwater": self._alloc.active_highwater
            if self._alloc else 0,
            "page_size": self.page_size,
            "pages_total": self._pool.num_pages
            if self._pool is not None else 0,
            "pages_free": self._pool.free_count
            if self._pool is not None else 0,
            "pages_shared": self._pool.shared_count
            if self._pool is not None else 0,
            "page_faults": c["page_faults"],
            "pages_scrubbed": c["pages_scrubbed"],
        }
        # quantized-KV + paged-attention arm (docs/serving.md
        # "Quantized KV + paged attention kernel"): overlay the
        # engine's knobs on the metrics' counter/histogram section
        s["quantized_kv"].update({
            "kv_quant": self.kv_quant,
            "paged_attention": self.paged_attention,
            "debug_parity": self.debug_parity,  # raceguard: unguarded(stats snapshot: atomic bool read, staleness bounded by one cycle)
        })
        # sharded decode (docs/serving.md "Sharded decode"): the mesh
        # this engine's programs span, and the compile accounting per
        # (bucket, mesh) point — warmup() freezes the "compiles" total,
        # and by_mesh_point localizes any violation to the mesh that
        # compiled it when several engines' stats are merged
        mesh_axes = {}
        if self.mesh is not None:
            from ..parallel.mesh import axis_size
            mesh_axes = {a: axis_size(self.mesh, a)
                         for a in self.mesh_axes}
        s["mesh"] = {
            "enabled": self.mesh is not None,
            "devices": self.mesh_devices,
            "axes": mesh_axes,
            "model_axis": self._model_axis,
            "slot_axis": self._slot_axis,
            "mesh_point": self._mesh_key,
        }
        s["compile"] = {
            "mesh_point": self._mesh_key,
            "by_mesh_point": dict(self._compiles_by_mesh),
            "compiles": c["compiles"],
            "bucket_hits": c["bucket_hits"],
            "programs": len(self._shape_seen),
        }
        # overlay the live controller state on the metrics' per-class
        # shed/served accounting (docs/overload.md)
        s["overload"]["controller"] = self._overload.snapshot()
        # overlay the tier store's live state on the metrics' counters
        # (docs/serving.md "Tiered prefix cache")
        if self._tier is not None:
            s["tier"]["store"] = self._tier.snapshot()
            s["tier"]["enabled"] = self._tier.enabled
        return s

    # --------------------------------------------------------------- scheduler
    def _loop(self):
        while True:
            self._heartbeat = time.monotonic()
            # deliberately OUTSIDE the recovery net: a raise here kills
            # the scheduler thread, which is exactly the crash the
            # watchdog exists to detect.  scope= lets a plan target one
            # replica of a fleet (docs/integrity.md gray failures).
            _inject("serving.scheduler", scope=self.name)
            with self._cond:
                idle = (self._alloc is None
                        or self._alloc.active_count == 0)
                if self._batcher.empty() and idle:
                    if self._stopping:
                        return
                    # the controller must keep ticking while idle or a
                    # brownout could never LIFT once the storm passes
                    self._overload_tick(time.monotonic())
                    self._cond.wait(0.05)
                    continue
                parked = self._tier_parked  # raceguard: unguarded(advisory: a stale count only costs one 2ms tick)
                if (self._batcher.empty() and not idle and parked
                        and parked >= self._alloc.active_count):
                    # EVERY live slot is waiting on an async tier
                    # promotion: spinning here would hold the GIL in a
                    # pure-Python loop and starve the tier worker of
                    # the very upload the slots wait for.  Park on the
                    # condition — the tier's resolve hook notifies —
                    # then fall through and run the cycle either way.
                    self._cond.wait(0.002)
            try:
                with self._step_lock:
                    self._cycle_busy = True
                    try:
                        if self.mode == "decode":
                            self._decode_cycle()
                        else:
                            self._forward_cycle()
                    finally:
                        self._cycle_busy = False
            except Exception as e:  # defensive: never leave futures hung
                with self._step_lock:
                    self._fail_inflight(e)
            # a BaseException (SimulatedPreemption, interpreter
            # shutdown) escapes on purpose: recovery code that catches
            # plain Exception must not "survive" a kill.  The dying
            # scheduler thread is what the watchdog's dead-thread
            # detection exists for — it condemns the engine and fails
            # every rider with the typed EngineCrashedError a fleet
            # can fail over on.

    def _run_step(self, site: str, key, fn, args, reqs):
        """One compiled call with the injection site + bounded retry for
        retryable faults.  ``reqs`` are the requests riding this call:
        each retry spends one unit of every rider's budget; once any
        rider is exhausted the fault escalates to the caller's failure
        path.  Injection fires BEFORE dispatch, so a retried call never
        re-executes a partially applied step."""
        delay = self.retry_backoff
        counted = False
        while True:
            try:
                _inject(site, scope=self.name)
                # compiled-program dispatch blocks for the whole device
                # step — doing so under any project lock stalls every
                # producer for that long (lockwitness finding)
                _note_blocking("serving.dispatch")
                if counted:
                    # a retry re-executes device work (an honest span)
                    # but is the SAME logical step: don't re-count the
                    # bucket hit or stats() degrades exactly when
                    # operators read it
                    with self.metrics.span(key[0]):
                        return fn(*args)
                counted = True
                return self._counted(key, fn, *args)
            except RetryableFault:
                if any(r.retries_left <= 0 for r in reqs) or not reqs:
                    raise
                for r in reqs:
                    r.retries_left -= 1
                self.metrics.count("retries")
                self.metrics.mark("retry")
                time.sleep(delay)
                delay *= 2

    def _filter_expired(self, reqs):
        """Fail deadline-blown queued requests; return the live rest."""
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.expired(now):
                self._fail(r, RequestTimeoutError(
                    f"request {r.id} timed out in queue"))
            else:
                live.append(r)
        return live

    def _fail(self, req: Request, exc: BaseException):
        req.future.set_exception(exc)
        if isinstance(exc, RequestTimeoutError):
            self.metrics.count("timeouts")
            self.metrics.mark("timeout")
        elif isinstance(exc, (EngineStoppedError, RequestCancelledError)):
            self.metrics.count("cancelled")
        tr = _trace_active()
        if tr is not None and req.trace_id is not None:
            tr.event("serving.error", trace_id=req.trace_id,
                     error=type(exc).__name__)
        fr = _fr_active()
        if fr is not None:
            fr.record("serving.error", engine=self.name, request=req.id,
                      error=type(exc).__name__, trace_id=req.trace_id)

    def _fail_inflight(self, exc: BaseException):  # guarded-by: _step_lock
        for req in self._batcher.drain():
            self._fail(req, exc)
        if self._alloc is not None:
            for slot, st in list(self._alloc.items()):
                self._release(slot)
                self._fail(st.request, exc)
            # the cache buffers may be donated-away or poisoned by the
            # failed step — drop them so the next admission rebuilds.
            # Every prefix-pool row dies with them: the radix tree must
            # forget its mappings or a later hit would copy ZEROED K/V
            # into a slot and silently serve wrong tokens.
            self._caches = None
            # the fp32 parity twin dies with the primaries (it shares
            # their page table, which resets below)
            self._parity_caches = None
            # in-flight promotions target the dead buffers; waiters were
            # already degraded by _release above, so just forget the
            # handle map (the tier's own store survives — its bundles
            # are host-side and still valid for the rebuilt caches)
            self._tier_pending.clear()
            self._tier_parked = 0
            if self._prefix is not None:
                self._prefix.reset()
            if self._paged:
                # every page's K/V died with the buffers: rebuild the
                # pool accounting from zero (reset AFTER the tree
                # forgot its claims — unref'ing into a reset pool
                # would double-free) and point every table entry back
                # at scratch
                self._pool.reset()
                self._page_table[:] = self._pool.scratch
                self._table_dirty()

    def _complete(self, st: SlotState):
        req = st.request
        seq = onp.concatenate(
            [req.payload, onp.asarray(st.generated, "int32")])
        now = time.monotonic()
        t_first = st.t_first if st.t_first is not None else now
        self.metrics.observe_request(req.t_schedule - req.t_submit,
                                     t_first - req.t_schedule,
                                     now - t_first)
        self.metrics.count("completed")
        self.metrics.count_served(req.priority_name)
        self.metrics.count("tokens_generated", len(st.generated))
        self.metrics.count("decode_tokens_observed", len(st.generated))
        tr = _trace_active()
        if tr is not None and req.trace_id is not None:
            # phase spans are RETROSPECTIVE — rebuilt from the request
            # timestamps the engine keeps anyway, so a completing
            # request costs three ring appends, no live bookkeeping
            root = tr.new_span_id()
            tr.record_span("serving.prefill_phase", req.t_schedule,
                           t_first, trace_id=req.trace_id, parent=root)
            tr.record_span("serving.decode_phase", t_first, now,
                           trace_id=req.trace_id, parent=root,
                           tokens=len(st.generated))
            tr.record_span("serving.request", req.t_submit, now,
                           trace_id=req.trace_id, span_id=root,
                           request=req.id)
            tr.event("serving.complete", trace_id=req.trace_id)
        req.future.set_result(seq)

    # ------------------------------------------------------------ decode path
    def _ensure_caches(self):  # guarded-by: _step_lock
        if self._caches is None:
            if self._paged:
                # pool + scratch page share one array per layer so
                # page copies and gathers stay in a single buffer;
                # kv_quant='int8' makes each layer an int8 page array
                # plus its fp32 per-(position, head) scale leaves
                self._caches = self.net.init_page_cache(
                    self.num_pages + 1, self.page_size,
                    kv_quant=self.kv_quant)
            else:
                # slots + scratch + prefix pool share one array per
                # layer so row-to-row copies and slot reads stay in a
                # single buffer
                self._caches = self.net.init_slot_cache(
                    self.num_slots + 1 + self.prefix_pool_rows,
                    self.max_length)
            # sharded decode: commit the fresh caches onto the mesh so
            # every compiled call sees stably-sharded operands
            self._caches = self._place_caches(self._caches)
        if self.debug_parity and self._parity_caches is None:
            # the fp32 reference twin: same page geometry, never
            # quantized, updated through the parity programs only
            self._parity_caches = self._place_caches(
                self.net.init_page_cache(self.num_pages + 1,
                                         self.page_size))

    def _release(self, slot: int):  # guarded-by: _step_lock
        """End a slot lease, dropping any prefix-cache read pin the
        (possibly unfinished) prefill still holds.  Paged layout: drop
        the slot's claim on every page it mapped and reset its table
        row to scratch; returns the page ids that actually FREED (last
        reader gone) — the scrub-on-NaN path zeroes exactly those.
        Pages still referenced (shared prefix pages, parked entries)
        survive untouched."""
        st = self._alloc.free(slot)
        self._tier_cancel(st)
        if st.pinned is not None:
            if self._prefix is not None:
                self._prefix.unpin(st.pinned)
            st.pinned = None
        freed = []
        if self._paged:
            freed = self._pool.release(st.pages)
            st.pages = []
            st.pages_shared = 0
            st.waiting = False
            self._page_table[slot, :] = self._pool.scratch
            self._table_dirty()
        return freed

    def _decode_cycle(self):  # guarded-by: _step_lock
        alloc = self._alloc
        now = time.monotonic()
        self._sweep_cancelled()
        # mid-flight deadline enforcement
        for slot, st in alloc.items():
            if st.request.expired(now):
                self._release(slot)
                self._fail(st.request, RequestTimeoutError(
                    f"request {st.request.id} timed out after "
                    f"{len(st.generated)} tokens"))
        self._overload_tick(now)
        # priority preemption BEFORE admission: the slots it frees are
        # leased to the waiting interactive requests this same cycle
        self._preempt_cycle(now)
        # admission: lease free slots to queued requests (prefix-cache
        # lookup + copy happens at lease); only an IDLE engine waits out
        # the batching window — with requests in flight the arrivals
        # ride the next cycle (continuous batching)
        free = alloc.free_count
        if free and not self._batcher.empty():
            wait_us = self.max_wait_us if alloc.active_count == 0 else 0
            reqs = self._batcher.get_batch(
                min(free, self.lattice.max_batch), wait_us, wait=False)
            self._admit(self._filter_expired(reqs))
        self._prefill_cycle()
        if self._paged:
            # the page covering each decoding slot's write position
            # must exist before the step (page faults park victims by
            # reference — see docs/serving.md "Paged KV")
            self._grow_pages()
        if self.kv_quant:
            # numeric fault site serving.kv_scale (docs/resilience.md):
            # a poisoned per-page scale is detected AT DEQUANT by the
            # in-graph NaN guard on the very next step that reads the
            # page — never served, counted at _fail_nonfinite
            bad = _poison("serving.kv_scale")
            if bad is not None:
                self._poison_scale(float(bad))
        if any(not st.prefilling and not st.waiting
               for _s, st in alloc.items()):
            if self.spec_tokens and self._spec_pages_ok:
                self._spec_step()
            else:
                self._decode_step()

    def _poison_scale(self, value: float):  # guarded-by: _step_lock
        """Apply a ``serving.kv_scale`` poison: splice ``value``
        (NaN/garbage) into the layer-0 K-scale of a claimed page —
        modeling host-RAM rot in the scale sidecar.  Eager cache
        surgery + re-pin, same discipline as scrub-on-NaN: zero
        compiled-program cache entries.  No claimed page → no-op."""
        if self._caches is None:
            return
        pid = None
        for _slot, st in self._alloc.items():
            if st.pages:
                pid = st.pages[len(st.pages) - 1]
                break
        if pid is None:
            return
        caches = self._caches
        c0 = dict(caches[0])
        if "k_scale" not in c0:
            return
        c0["k_scale"] = c0["k_scale"].at[pid].set(value)
        rest = caches[1:]
        new = (c0,) + tuple(rest) if isinstance(caches, tuple) \
            else [c0] + list(rest)
        self._caches = self._place_caches(new)

    def _overload_tick(self, now: float):
        """One AIMD controller tick (docs/overload.md): pressure =
        queue depth vs capacity plus deadline misses since the last
        cycle.  Purely host-side — it can never add a compile."""
        t = self.metrics.counters["timeouts"]
        entered = self._overload.update(len(self._batcher),
                                        t - self._timeouts_seen, now)
        self._timeouts_seen = t
        if entered:
            self.metrics.count("brownouts")
            self.metrics.mark("brownout")
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.brownout", engine=self.name,
                          reason="overload")

    def _sweep_cancelled(self):
        """Free the slots of requests cancelled mid-decode (the
        hedged-loser path): their futures fail typed and the rows are
        reclaimable this same cycle.  A cancelled request that was
        PREEMPTED between the cancel() call and this sweep lives in
        the queue as a continuation — dequeue it there; anything still
        unmatched and unresolved carries over to the next sweep rather
        than silently un-cancelling."""
        if not self._cancels:  # raceguard: unguarded(lock-free emptiness fast path; the swap below re-checks under _cond)
            return
        with self._cond:
            cancels, self._cancels = self._cancels, set()
        for slot, st in list(self._alloc.items()):
            if st.request.future in cancels:
                cancels.discard(st.request.future)
                self._release(slot)
                self._fail(st.request, RequestCancelledError(
                    f"request {st.request.id} cancelled mid-decode "
                    f"after {len(st.generated)} tokens"))
        carry = set()
        for fut in cancels:
            if fut.done():
                continue
            req = self._batcher.remove(fut)
            if req is not None:
                self._fail(req, RequestCancelledError(
                    f"request {req.id} cancelled while requeued"))
            else:
                carry.add(fut)     # mid-flight this cycle: retry next
        if carry:
            with self._cond:
                self._cancels |= carry

    # ----------------------------------------------------------- preemption
    def _preempt_cycle(self, now: float):
        """Slot preemption (docs/overload.md): an ``interactive``
        request waiting with every slot busy may preempt a
        ``best_effort`` request mid-decode.  The victim's
        generated-so-far prefix parks in the prefix pool, so the
        resume costs one row copy + a one-token prefill — almost no
        wasted work, token-identical output (greedy decode is
        deterministic)."""
        if not self.preemption:
            return
        alloc = self._alloc
        if alloc.free_count:
            return
        # count only NON-expired interactive arrivals: an expired one
        # fails typed at its next admission anyway — evicting a healthy
        # victim for it would be pure churn
        waiting = self._batcher.waiting_at_or_above(
            PRIORITY_INTERACTIVE, now)
        if not waiting:
            return
        # a victim is only eligible when the "almost no wasted work"
        # promise holds: its progress can PARK (the pool is usable) or
        # it has populated fewer than prefix_min_tokens K/V rows (the
        # resume re-prefill is trivially cheap).  With
        # prefix_pool_rows=0 preemption therefore (almost) never fires
        # rather than paying a full re-prefill of prompt + generated
        # on every resume.
        parkable = self._prefix_usable()
        victims = [(slot, st) for slot, st in alloc.items()
                   if not st.prefilling
                   and st.request.priority == PRIORITY_BEST_EFFORT
                   and (parkable or st.pos < self.prefix_min_tokens)]
        if not victims:
            return
        # park the victims with the MOST remaining budget first: they
        # free capacity longest, and their progress parks either way
        victims.sort(key=lambda it: len(it[1].generated)
                     - it[1].max_new_tokens)
        for slot, st in victims[:waiting]:
            try:
                _inject("overload.preempt")
            except Exception:
                # contained: a faulted preemption attempt aborts — the
                # victim keeps decoding, the interactive request waits
                # for a natural slot
                self.metrics.count("overload_faults")
                continue
            self._preempt(slot, st)

    def _preempt(self, slot: int, st: SlotState):
        req = st.request
        seq = onp.concatenate([req.payload,
                               onp.asarray(st.generated, "int32")]) \
            if st.generated else req.payload
        # a DECODING victim's K/V rows are populated for [0, pos) —
        # everything up to (not including) the last generated token,
        # whose K/V the next decode step would have written; a
        # PREFILLING victim (paged page-fault parking) has exactly its
        # completed chunks [0, filled)
        park = st.filled if st.prefilling else st.pos
        if self._prefix_usable() and park >= self.prefix_min_tokens:
            self._pool_insert(seq[:park], slot, park, st)
        self._release(slot)
        cont = Request("decode", seq,
                       st.max_new_tokens - len(st.generated),
                       req.eos_id, req.deadline, priority=req.priority,
                       temperature=req.temperature, top_k=req.top_k,
                       top_p=req.top_p, seed=req.seed)
        # the continuation IS the original request: same future, same
        # submit time (latency metrics span the whole request), same
        # trace id, same remaining retry budget
        cont.future = req.future
        cont.t_submit = req.t_submit
        cont.trace_id = req.trace_id
        cont.retries_left = req.retries_left
        cont.preempted = req.preempted + 1
        try:
            self._batcher.requeue(cont)
        except EngineStoppedError as e:
            self._fail(cont, e)
            return
        self.metrics.count("preemptions")
        # the segment decoded before preemption is real served output;
        # the continuation's completion only credits its OWN generated
        # tokens, so count this run's here or they vanish from
        # throughput
        self.metrics.count("tokens_generated", len(st.generated))
        self.metrics.mark("preempt")
        tr = _trace_active()
        if tr is not None and req.trace_id is not None:
            tr.event("serving.preempt", trace_id=req.trace_id,
                     request=req.id, generated=len(st.generated))
        fr = _fr_active()
        if fr is not None:
            fr.record("serving.preempt", engine=self.name, request=req.id,
                      generated=len(st.generated),
                      priority=req.priority_name, trace_id=req.trace_id)

    # --------------------------------------------------------- prefix cache
    def _prefix_usable(self) -> bool:  # guarded-by: _step_lock
        return self._prefix is not None and not self._prefix_disabled

    def _prefix_fault(self, where: str):  # guarded-by: _step_lock
        """Contain a fault at a serving.prefix_* site: the request just
        loses the shortcut (full prefill), never fails.  Repeated
        consecutive faults at EITHER site disable the cache — a
        flapping lookup/copy path must not keep adding latency to every
        admission."""
        self.metrics.count("prefix_faults")
        self.metrics.mark("prefix_fault")
        self._prefix_faults[where] += 1
        if self._prefix_faults[where] >= self.prefix_fault_limit and \
                not self._prefix_disabled:
            self._prefix_disabled = True
            self.metrics.mark("prefix_disabled")

    def _prefix_admit(self, st: SlotState, slot: int):  # guarded-by: _step_lock
        """Lease-time prefix reuse: longest-prefix lookup, pin, and the
        device row copy (dense) or page-table sharing (paged).  On
        success ``st.filled`` skips the matched region; on any
        contained fault the request prefills in full."""
        req = st.request
        tr = _trace_active()
        t0 = time.monotonic() if tr is not None else 0.0
        try:
            _inject("serving.prefix_lookup")
            hit = self._prefix.lookup(req.payload)
        except Exception:           # incl. RetryableFault: a host-side
            self._prefix_fault("lookup")   # tree op has nothing to retry
            return
        if tr is not None and req.trace_id is not None:
            tr.record_span("serving.prefix_lookup", t0, time.monotonic(),
                           trace_id=req.trace_id, hit=hit is not None)
        # the limit counts CONSECUTIVE faults: a clean op resets ITS streak
        self._prefix_faults["lookup"] = 0
        if hit is None:
            self.metrics.count("prefix_misses")
            return
        # always leave >= 1 suffix token: the final chunk's logits are
        # where the FIRST generated token comes from
        match, entry = hit
        match = min(match, st.prompt_len - 1)
        if match < self.prefix_min_tokens:
            self.metrics.count("prefix_misses")
            return
        if self._paged and entry.tier == 2:
            # a host-tier claim (docs/serving.md "Tiered prefix cache"):
            # its K/V must ride an async host→device promotion before
            # any page can be shared — park the slot on a tier handle;
            # _tier_poll re-runs this admission once the upload lands
            if not self._tier_request(st, entry):
                self.metrics.count("prefix_misses")
            return
        if self._paged:
            self._prefix_admit_paged(st, slot, entry, match)
            return
        self._prefix.pin(entry)
        t0 = time.monotonic() if tr is not None else 0.0
        try:
            import jax.numpy as jnp
            self._ensure_caches()
            # riders=() — the copy is an OPTIONAL optimization: a
            # retryable fault here must degrade to a miss immediately,
            # not spend the request's retry budget (which a mandatory
            # prefill/decode step may later need)
            self._caches = self._run_step(
                "serving.prefix_copy", ("prefix_copy",), self._jit_copy,
                (self._caches, jnp.asarray(entry.row, jnp.int32),
                 jnp.asarray(slot, jnp.int32),
                 jnp.asarray(match, jnp.int32)), ())
        except Exception:
            # injection fires BEFORE dispatch, so the slot row is
            # untouched and a full prefill from 0 is always correct.
            # (A real device fault after a TPU donation would invalidate
            # the cache buffers — but then the request's own prefill
            # fails too and _fail_inflight rebuilds caches + resets the
            # tree, same as any other step failure.)
            self._prefix.unpin(entry)
            self._prefix_fault("copy")
            return
        if tr is not None and req.trace_id is not None:
            tr.record_span("serving.prefix_copy", t0, time.monotonic(),
                           trace_id=req.trace_id, tokens=match)
        self._prefix_faults["copy"] = 0
        st.filled = match
        st.pinned = entry            # read-pinned until prefill completes
        self.metrics.count("prefix_hits")
        self.metrics.count("prefix_tokens_saved", match)

    def _prefix_admit_paged(self, st, slot, entry, match):  # guarded-by: _step_lock
        """Paged prefix hit (docs/serving.md "Paged KV"): every WHOLE
        matched page is shared by reference — a page-table write plus a
        refcount bump, no device work at all (this is where the dense
        engine's compiled masked row copy disappears).  A partial tail
        page still pays one compiled page copy into a fresh page (the
        slot will write its own K/V behind the matched region inside
        that page, and shared pages are read-only to sharers).  A fault
        at ``serving.page_copy`` degrades to whole-page sharing only —
        the suffix prefill just starts a little earlier."""
        ps = self.page_size
        match = min(match, entry.length)
        n_full = match // ps
        for i in range(n_full):
            pid = entry.pages[i]
            self._pool.ref(pid)
            st.pages.append(pid)
            self._page_table[slot, i] = pid
            self._table_dirty()
        st.pages_shared = n_full
        filled = n_full * ps
        rem = match - filled
        if rem:
            self._prefix.pin(entry)   # tail-copy source must survive
            newp = self._claim_pages(1)
            if newp is not None:
                try:
                    import jax.numpy as jnp
                    self._ensure_caches()
                    # riders=() as in the dense copy: an optional
                    # optimization must never spend the request's own
                    # retry budget
                    self._caches = self._run_step(
                        "serving.page_copy", ("prefix_copy",),
                        self._jit_copy,
                        (self._caches,
                         jnp.asarray(entry.pages[n_full], jnp.int32),
                         jnp.asarray(newp[0], jnp.int32),
                         jnp.asarray(rem, jnp.int32)), ())
                except Exception:
                    self._pool.unref(newp[0])
                    self._prefix.unpin(entry)
                    self._prefix_fault("copy")
                else:
                    self._prefix_faults["copy"] = 0
                    if self._parity_caches is not None:
                        # mirror the tail-page copy into the fp32 twin
                        # (same src/dst/length, its own buffers) so the
                        # arms keep identical prefix state
                        try:
                            self._parity_caches = self._counted(
                                ("parity_copy",), self._jit_copy,
                                self._parity_caches,
                                jnp.asarray(entry.pages[n_full],
                                            jnp.int32),
                                jnp.asarray(newp[0], jnp.int32),
                                jnp.asarray(rem, jnp.int32))
                        except Exception:
                            self._parity_caches = None
                            self.debug_parity = False
                    st.pages.append(newp[0])
                    self._page_table[slot, n_full] = newp[0]
                    self._table_dirty()
                    filled += rem
                    st.pinned = entry
            else:
                self.metrics.count("page_faults")
                self._prefix.unpin(entry)
        if filled < self.prefix_min_tokens:
            # nothing usable shared (sub-page match whose copy failed):
            # release the claims and treat as a plain miss
            for pid in st.pages:
                self._pool.unref(pid)
            st.pages = []
            st.pages_shared = 0
            self._page_table[slot, :] = self._pool.scratch
            self._table_dirty()
            self.metrics.count("prefix_misses")
            return
        st.filled = filled
        self.metrics.count("prefix_hits")
        self.metrics.count("prefix_tokens_saved", filled)

    # ---------------------------------------------------- tiered prefix
    def _tier_demote(self, entry) -> bool:  # guarded-by: _step_lock
        """Demotion gate for :class:`PagedPrefixCache.evict_pages` —
        called at the moment an LRU sweep picked ``entry`` as a
        zero-reader victim.  True downgrades the entry to a tier-2
        claim (its pages still free); False evicts it outright, exactly
        as without the tier.  The device→host copy itself runs on the
        tier worker, OFF this scheduler thread — here we only gather
        the victim's pages into per-layer bundles and hand them over.
        Pages the pool marked dirty (a non-finite victim wrote them
        while a reader pinned them alive) are refused outright: a
        NaN-taintable page must never round-trip through host RAM."""
        tier = self._tier
        if tier is None or not tier.enabled or self._caches is None:
            return False
        if entry.length < self.prefix_min_tokens or not entry.pages:
            return False
        if any(pid in self._pool.dirty for pid in entry.pages):
            self.metrics.count("tier_drops")
            return False
        try:
            import jax
            import jax.numpy as jnp
            tokens = tuple(self._entry_tokens(entry))
            pids = jnp.asarray(onp.asarray(entry.pages, "int32"))
            if self._tier_gather_fn is None:
                # ONE fused dispatch for the whole layer stack — the
                # per-leaf loop costs a device round-trip per K/V leaf
                # on the scheduler thread, which is the hot path the
                # tier exists to keep clear
                self._tier_gather_fn = jax.jit(
                    lambda leaves, p: [leaf[p] for leaf in leaves])
            arrays = self._tier_gather_fn(
                jax.tree_util.tree_leaves(self._caches), pids)
        except Exception:
            self.metrics.count("tier_drops")
            return False
        return tier.offer(tokens, arrays, entry.length)

    def _tier_request(self, st: SlotState, entry) -> bool:  # guarded-by: _step_lock
        """Ask the tier to promote ``entry``'s bundle and park the slot
        on the resulting handle.  False means the claim was stale (the
        tier lost the bundle — rot, LRU pressure, self-disable): the
        dead claim is pruned and the caller treats it as a miss."""
        tier = self._tier
        if tier is None:
            self._tier_prune(entry)
            return False
        handle = self._tier_pending.get(entry)
        if handle is None:
            handle = tier.request(tuple(self._entry_tokens(entry)))
            if handle is None:
                self._tier_prune(entry)
                return False
            self._tier_pending[entry] = handle
        self._prefix.pin(entry)      # the claim must survive the wait
        if st.tier_promo is None:
            self._tier_parked += 1
        st.tier_promo = (entry, handle, time.monotonic())
        return True

    def _tier_wake(self):
        """Tier worker resolve hook: poke the scheduler's condition so
        a loop parked in the all-slots-waiting-on-promotion state picks
        the result up immediately instead of a poll tick later."""
        with self._cond:
            self._cond.notify_all()

    def _tier_prune(self, entry):  # guarded-by: _step_lock
        """Drop a tier-2 claim whose bundle is gone — the radix tree
        must never keep promising K/V nobody can produce.  Only an
        unreferenced claim is removed: concurrent waiters on the same
        entry resolve their own handles first."""
        self._tier_pending.pop(entry, None)
        if entry.tier == 2 and entry.refs == 0 and not entry.pages:
            self._prefix.remove(entry)

    def _tier_poll(self, st: SlotState, slot: int) -> bool:  # guarded-by: _step_lock
        """Resolve one slot's pending promotion.  True while the async
        upload is still in flight (the slot sits this prefill cycle
        out); False once resolved either way — on success the entry is
        tier-1 again and admission re-runs to share its pages, on
        failure/timeout the slot degrades to a full recompute."""
        entry, handle, t0 = st.tier_promo
        tier = self._tier
        status, arrays = tier.poll(handle)
        if status == "pending":
            if time.monotonic() - t0 <= self._tier_timeout:
                return True
            tier.abandon(handle)     # counted as a tier miss
            status = "failed"
        st.tier_promo = None
        self._tier_parked = max(0, self._tier_parked - 1)
        self._prefix.unpin(entry)
        self._tier_pending.pop(entry, None)
        ok = False
        if status == "ready":
            if entry.tier == 2:
                ok = self._tier_install(entry, handle, arrays)
                if not ok:
                    self.metrics.count("tier_misses")
            else:
                ok = entry.tier == 1   # a sibling waiter already installed
        if ok:
            self._prefix_admit(st, slot)
            return False
        self.metrics.count("prefix_misses")
        if not tier.contains(handle.key):
            self._tier_prune(entry)
        return False

    def _tier_install(self, entry, handle, arrays) -> bool:  # guarded-by: _step_lock
        """Eager-install a verified bundle's pages and re-back the
        tier-2 claim (the seed_prefix cache-surgery idiom: claim pages,
        ``.at[pids].set``, re-place — zero compile-cache entries, the
        post-warmup freeze holds).  The entry takes its own refcounts
        via :meth:`PagedPrefixCache.upgrade`; the allocation claims are
        dropped either way."""
        import jax
        import jax.numpy as jnp
        length = int(handle.length)
        need = self._pool.pages_for(length)
        self._ensure_caches()
        flat, treedef = jax.tree_util.tree_flatten(self._caches)
        if (not arrays or len(arrays) != len(flat)
                or int(arrays[0].shape[0]) != need
                or length != entry.length):
            return False
        pages = self._claim_pages(need)
        if not pages:
            self.metrics.count("page_faults")
            return False
        pids = jnp.asarray(onp.asarray(pages, "int32"))
        if self._tier_scatter_fn is None:
            # ONE fused dispatch installs every leaf — and because the
            # promoted bundle arrives as HOST arrays, the H2D transfer
            # rides the same call instead of one upload per leaf
            self._tier_scatter_fn = jax.jit(
                lambda leaves, p, arrs: [
                    leaf.at[p].set(arr)
                    for leaf, arr in zip(leaves, arrs)])
        new = self._tier_scatter_fn(flat, pids, arrays)
        self._prefix.upgrade(entry, pages, length)
        try:
            self._caches = self._place_caches(
                jax.tree_util.tree_unflatten(treedef, new))
        except BaseException:
            # the mapping must never outlive a failed install
            self._prefix.remove(entry)
            for pid in pages:
                self._pool.unref(pid)
            raise
        for pid in pages:            # handoff: entry's refs keep them
            self._pool.unref(pid)
        return True

    def _tier_cancel(self, st: SlotState):  # guarded-by: _step_lock
        """Release a slot's promotion wait (request done/failed/preempted
        mid-wait).  The in-flight upload itself is left to finish — a
        sibling waiter, or the next radix hit, still wants it."""
        if st.tier_promo is None:
            return
        entry, handle, _t0 = st.tier_promo
        st.tier_promo = None
        self._tier_parked = max(0, self._tier_parked - 1)
        if self._prefix is not None:
            self._prefix.unpin(entry)
        self._tier_pending.pop(entry, None)

    def _prefix_insert(self, st: SlotState, slot: int):
        """After a request's prefill completes, cache its full prompt:
        reserve a pool row (LRU-evicting zero-reader entries under
        pressure) and copy the slot's K/V [0, prompt_len) into it.  A
        failed copy removes the mapping — the tree must never point at
        a row that does not hold what it promises.  During brownout
        NEW inserts are paused (each costs a compiled row copy the
        overloaded engine cannot spare); preemption parking bypasses
        the pause via :meth:`_pool_insert` — parking is exactly the
        under-pressure path."""
        if not self._prefix_usable() or \
                st.prompt_len < self.prefix_min_tokens:
            return
        if self._overload.pause_inserts:
            self.metrics.count("prefix_inserts_paused")
            return
        self._pool_insert(st.tokens, slot, st.prompt_len, st)

    def _pool_insert(self, tokens, slot, length, st=None):  # guarded-by: _step_lock
        """Shared slot→pool insert body.  Dense: radix-tree insert +
        the compiled row copy of K/V ``[0, length)`` from ``slot``
        into the reserved pool row.  Paged (``st`` given): the entry
        simply takes refcounts on the slot's pages covering
        ``[0, length)`` — park/insert by REFERENCE, zero device work
        (a partial tail page is shared too: the donor only ever writes
        positions ``>= length`` inside it, which no reader reads).
        Usual per-site fault containment either way."""
        try:
            _inject("serving.prefix_lookup")
            if self._paged:
                npages = self._pool.pages_for(length)
                if npages > len(st.pages):
                    return           # cannot promise K/V it doesn't hold
                entry = self._prefix.insert(tokens, st.pages[:npages],
                                            length)
            else:
                ev0 = self._prefix.evictions
                entry = self._prefix.insert(tokens)
                self.metrics.count("prefix_evictions",
                                   self._prefix.evictions - ev0)
        except Exception:           # incl. RetryableFault, as in lookup
            self._prefix_fault("lookup")
            return
        self._prefix_faults["lookup"] = 0
        if entry is None:
            return
        if self._paged:
            self.metrics.count("prefix_inserts")
            return
        try:
            import jax.numpy as jnp
            self._caches = self._run_step(
                "serving.prefix_copy", ("prefix_copy",), self._jit_copy,
                (self._caches, jnp.asarray(slot, jnp.int32),
                 jnp.asarray(entry.row, jnp.int32),
                 jnp.asarray(length, jnp.int32)), ())
        except Exception:
            self._prefix.remove(entry)
            self._prefix_fault("copy")
            return
        self._prefix_faults["copy"] = 0
        self.metrics.count("prefix_inserts")

    # ---------------------------------------------------------- paged pages
    def _table_arg(self):  # guarded-by: _step_lock
        """Device copy of the page table, re-uploaded only after a
        mutation: steady-state decode (no admissions, no page growth)
        reuses one cached array across thousands of steps instead of
        paying a host-to-device transfer per dispatch.  Every writer of
        ``_page_table`` must call :meth:`_table_dirty`."""
        if self._table_dev is None:
            import jax.numpy as jnp
            self._table_dev = jnp.asarray(self._page_table)
        return self._table_dev

    def _table_dirty(self):  # guarded-by: _step_lock
        self._table_dev = None

    def _evict_hook(self):  # guarded-by: _step_lock
        """Allocation-pressure reclaim hook for :meth:`PagePool.alloc`:
        evict zero-reader prefix entries (LRU) until the shortfall is
        covered, counting the evictions like the dense LRU path."""
        if not self._prefix_usable():
            return None
        cache, metrics = self._prefix, self.metrics

        def reclaim(k):
            ev0 = cache.evictions
            freed = cache.evict_pages(k)
            metrics.count("prefix_evictions", cache.evictions - ev0)
            return freed
        return reclaim

    def _claim_pages(self, n: int, reclaim: bool = True):  # guarded-by: _step_lock
        """Allocate ``n`` pages (with the eviction reclaim hook),
        scrubbing any that a non-finite victim dirtied while another
        reader kept them alive past its release — stale NaN must never
        reach the new tenant (0·NaN = NaN through the value einsum
        survives the select mask).  ``reclaim=False`` allocates from
        the free list only — the speculation window's SOFT claim must
        not evict cached prefixes (a TTFT asset of future requests) to
        fund an optimization, least of all one that then fails to
        run."""
        pages = self._pool.alloc(n, self._evict_hook() if reclaim
                                 else None)
        if pages and self.kv_quant:
            # every page claimed on a quantized engine will be written
            # int8 — this is the single allocation choke point, so the
            # counter covers prefill, decode growth, tail copies and
            # the speculative soft claim alike
            self.metrics.count("kv_quant_pages", len(pages))
        if pages and self._pool.dirty:
            tainted = [p for p in pages if p in self._pool.dirty]
            if tainted:
                self._scrub_pages(tainted)
                self._pool.dirty.difference_update(tainted)
        return pages

    def _pages_available(self) -> int:  # guarded-by: _step_lock
        """Pages an admission could obtain RIGHT NOW: the free list
        plus what evicting every zero-reader prefix entry would free —
        cached prefixes never block live work."""
        avail = self._pool.free_count
        if self._prefix_usable():
            avail += self._prefix.evictable_pages()
        return avail

    def _page_need(self, req: Request) -> int:  # guarded-by: _step_lock
        """Pages an admission must be able to cover: the prompt plus
        the first decode page.  Conservative — a prefix hit will claim
        fewer."""
        return self._pool.pages_for(min(req.prompt_len + 1,
                                        self.max_length))

    def _page_admissible(self, need, budget) -> bool:  # guarded-by: _step_lock
        """Paged admission gate (docs/serving.md "Paged KV"): admit
        only while the batch's running page BUDGET covers the request
        (``_admit`` computes the pool's availability ONCE and deducts
        per admission — without the reservation, every request in one
        batch would pass against the same free pages and the
        just-admitted slots would immediately thrash each other out by
        mutual page-fault preemption).  A blocked request WAITS queued
        (alloc retry next cycle) instead of failing: admission blocks
        on page availability, not slot count.  A fault at
        ``serving.page_alloc`` degrades the same way."""
        try:
            _inject("serving.page_alloc", scope=self.name)
        except Exception:
            self.metrics.count("page_faults")
            return False
        if budget >= need:
            return True
        self.metrics.count("page_faults")
        return False

    def _ensure_pages(self, slot, st, upto) -> str:  # guarded-by: _step_lock
        """Grow ``slot``'s page table to cover positions ``[0, upto)``.
        Returns ``"ok"`` (covered), ``"retry"`` (transient — injected
        alloc fault or pool pressure relieved by parking a victim is
        still in flight; the slot sits out THIS cycle and retries), or
        ``"full"`` (pool exhausted and no other victim exists — the
        caller parks this slot itself).  A page fault (pool dry) first
        evicts zero-reader prefix entries, then parks the youngest
        lowest-class OTHER slot by reference — its pages become an
        evictable entry, so the retry inside the loop reclaims them."""
        need = self._pool.pages_for(upto) - len(st.pages)
        if need <= 0:
            st.waiting = False
            return "ok"
        try:
            _inject("serving.page_alloc", scope=self.name)
        except Exception:
            # contained: degrade to an alloc retry next cycle — the
            # slot keeps its lease and its progress, it just waits
            self.metrics.count("page_faults")
            st.waiting = True
            return "retry"
        pages = self._claim_pages(need)
        while pages is None:
            self.metrics.count("page_faults")
            self.metrics.mark("page_fault")
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.page_fault", engine=self.name,
                          slot=slot, need=need,
                          request=st.request.id)
            victim = self._page_victim(slot, st.request.priority)
            if victim is None:
                st.waiting = True
                return "full"
            self._preempt(*victim)
            pages = self._claim_pages(need)
        base = len(st.pages)
        st.pages.extend(pages)
        self._page_table[slot, base:base + need] = pages
        self._table_dirty()
        st.waiting = False
        return "ok"

    def _page_victim(self, exclude, floor):  # guarded-by: _step_lock
        """Pick the slot whose parking relieves page pressure at the
        least cost: lowest priority class first, youngest admission
        within a class — never ``exclude`` (the slot being grown; the
        OLDEST work keeps running, which guarantees forward progress:
        admission capped every request at ``num_pages``, so the last
        runner standing always fits alone) and never a class ABOVE the
        grower's (``floor``): a best_effort page fault must not park an
        interactive request — same semantics as overload preemption,
        which only ever victims downward.  With no eligible victim the
        grower parks ITSELF."""
        cands = [(slot, st) for slot, st in self._alloc.items()
                 if slot != exclude and st.pages
                 and st.request.priority >= floor]
        if not cands:
            return None
        cands.sort(key=lambda it: (it[1].request.priority,
                                   it[1].request.t_schedule))
        return cands[-1]

    def _grow_pages(self):  # guarded-by: _step_lock
        """Decode-time page growth, oldest slot first: before the step
        writes K/V at ``st.pos``, the page covering it must exist.  A
        slot that cannot get one even after victim parking parks
        ITSELF by reference (progress becomes an evictable prefix
        entry; the continuation resumes by prefix hit when pages
        free).

        With speculation on, each decoding slot additionally wants
        coverage for the whole verify window ``[pos, pos+k]`` — but as
        a SOFT claim: speculation is an optimization, so a shortfall
        here never parks a victim and never makes a slot wait, it just
        degrades the cycle to plain one-token decode
        (``_spec_pages_ok``); rejected speculation returns over-claimed
        pages via :meth:`_rewind_pages`."""
        self._spec_pages_ok = True
        decoding = [(slot, st) for slot, st in self._alloc.items()
                    if not st.prefilling]
        decoding.sort(key=lambda it: it[1].request.t_schedule)
        for slot, st in decoding:
            if slot not in self._alloc:
                continue               # parked as a victim already
            if self._ensure_pages(slot, st, st.pos + 1) == "full":
                self._preempt(slot, st)
                continue
            if not self.spec_tokens or st.waiting or \
                    slot not in self._alloc:
                continue
            # soft window claim, capped at the cache end (a slot close
            # to Tmax simply stops speculating that far).  Free-list
            # only (reclaim=False): the soft claim may not evict prefix
            # entries — eviction pressure is reserved for real work
            upto = min(st.pos + 1 + self.spec_tokens, self.max_length)
            need = self._pool.pages_for(upto) - len(st.pages)
            if need <= 0:
                continue
            pages = self._claim_pages(need, reclaim=False)
            if pages is None:
                self._spec_pages_ok = False
                continue
            base = len(st.pages)
            st.pages.extend(pages)
            self._page_table[slot, base:base + need] = pages
            self._table_dirty()

    def _scrub_pages(self, freed, count: bool = True):  # guarded-by: _step_lock
        """Zero freed pages after a non-finite failure: NaN K/V written
        by the victim survives ADDITIVE masking (flash-kernel style),
        so a later tenant of the page must never see it.  Pages still
        referenced are untouched — a shared prefix page was written
        only by clean prefill, and its readers' copies must not be
        zeroed under them.  ``count=False`` callers (the speculative
        rewind) keep their own counter — ``pages_scrubbed`` stays the
        NaN-hygiene signal."""
        if not freed or self._caches is None:
            return
        import jax
        import jax.numpy as jnp
        pids = jnp.asarray(freed, jnp.int32)
        self._caches = self._place_caches(jax.tree_util.tree_map(
            lambda a: a.at[pids].set(0), self._caches))
        # quantized pages: a.at[pids].set(0) above zeroed the scale
        # leaves too (they are ordinary cache leaves) — a scrubbed
        # page dequantizes to exactly 0.0, never 0·NaN.  The fp32
        # parity twin mirrors the scrub so the arms stay in lockstep.
        if self._parity_caches is not None:
            self._parity_caches = self._place_caches(
                jax.tree_util.tree_map(lambda a: a.at[pids].set(0),
                                       self._parity_caches))
        if count:
            self.metrics.count("pages_scrubbed", len(freed))
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.scrub", engine=self.name,
                          pages=len(freed))

    # ------------------------------------------------------------ admission
    def _admit(self, live):
        """Lease a slot per request; prefix-cache hits copy their
        matched K/V now, so the prefill phase only sees suffixes."""
        alloc = self._alloc
        now = time.monotonic()
        tr = _trace_active()
        n_prompt = 0
        admitted = 0
        # one availability snapshot per batch, deducted per admission:
        # the batch must not over-admit against the same free pages
        budget = self._pages_available() if self._paged else 0
        for i, req in enumerate(live):
            need = self._page_need(req) if self._paged else 0
            if self._paged and not self._page_admissible(need, budget):
                # admission blocks on PAGE availability, not slot
                # count: park this and everything behind it back at
                # the FRONT of their classes (reversed, so the
                # original order survives the appendleft) and retry
                # next cycle once decode frees pages
                for r in reversed(live[i:]):
                    try:
                        self._batcher.requeue(r)
                    except EngineStoppedError as e:
                        self._fail(r, e)
                break
            budget -= need
            st = SlotState(req, req.prompt_len, req.max_new_tokens,
                           tokens=req.payload)
            slot = alloc.alloc(st)
            req.t_schedule = now
            admitted += 1
            if req.preempted:
                # a preemption victim re-admitted: its parked prefix
                # should hit in _prefix_admit below (resume ≈ one row
                # copy + a one-token prefill)
                self.metrics.count("preempt_resumes")
            if tr is not None and req.trace_id is not None:
                tr.record_span("serving.queue", req.t_submit, now,
                               trace_id=req.trace_id, slot=slot)
            n_prompt += req.prompt_len
            if self._prefix_usable() and req.prompt_len > 1:
                self._prefix_admit(st, slot)
        if admitted:
            self.metrics.count("admitted", admitted)
            self.metrics.count("prompt_tokens", n_prompt)
            self.metrics.mark("admit", admitted)

    # -------------------------------------------------------------- prefill
    def _prefill_cycle(self):
        """Run prefill work: full-prompt groups (fresh short prompts —
        the unchanged fast path) all at once, then at most ONE chunked
        batch (suffixes behind a prefix hit, and long prompts) so a
        giant prompt never starves the decode step more than one
        chunk's worth per cycle."""
        ready = []
        for slot, st in self._alloc.items():
            if slot not in self._alloc or not st.prefilling:
                continue               # a victim parked by an earlier
            if st.tier_promo is not None and self._tier_poll(st, slot):
                continue           # awaiting an async tier promotion
            if self._paged:            # _ensure_pages in this loop
                # the pages a chunk will write must exist BEFORE the
                # compiled call; a slot that cannot get them parks
                # (by reference — resumes by prefix hit) or sits this
                # cycle out (transient alloc fault)
                take = min(st.prompt_len - st.filled, self.prefill_chunk)
                got = self._ensure_pages(slot, st, st.filled + take)
                if got == "full":
                    self._preempt(slot, st)
                    continue
                if got == "retry":
                    continue
            ready.append((slot, st))
        full, chunked = {}, []
        for slot, st in ready:
            if slot not in self._alloc:
                continue               # parked as a later slot's victim
            if st.filled == 0 and st.prompt_len <= self.prefill_chunk:
                full.setdefault(self.lattice.seq(st.prompt_len),
                                []).append((slot, st))
            else:
                chunked.append((slot, st))
        for tb in sorted(full):
            self._prefill_full(full[tb], tb)
        if chunked:
            # oldest-admitted first, NOT slot order: under sustained
            # long-prompt load the LIFO slot free list keeps re-leasing
            # low slot numbers, and slot-ordered selection would starve
            # high-numbered mid-prefill rows into deadline timeouts
            chunked.sort(key=lambda it: it[1].request.t_schedule)
            self._prefill_chunk_batch(chunked[:self.lattice.max_batch])

    def _prefill_full(self, rows, tb):  # guarded-by: _step_lock
        import jax.numpy as jnp

        if not self._quant_write_ok():
            return
        bb = self.lattice.batch(len(rows))
        toks = onp.zeros((bb, tb), "int32")
        lens = onp.ones((bb,), "int32")
        sidx = onp.full((bb,), self._alloc.scratch, "int32")
        n_real = 0
        for i, (slot, st) in enumerate(rows):
            toks[i, :st.prompt_len] = st.tokens
            lens[i] = st.prompt_len
            sidx[i] = slot
            n_real += st.prompt_len
        self.metrics.count("padded_tokens", bb * tb - n_real)
        self.metrics.count("prefill_batches")
        self._ensure_caches()
        tbl = (self._table_arg(),) if self._paged else ()
        samp = tuple(jnp.asarray(a) for a in self._samp_rows(
            [st.request for _s, st in rows], bb))
        tr = _trace_active()
        t0 = time.monotonic() if tr is not None else 0.0
        res = self._run_step(
            "serving.prefill", ("prefill", bb, tb), self._jit_prefill,
            (self._params(), jnp.asarray(toks), jnp.asarray(lens),
             self._caches, jnp.asarray(sidx)) + samp + tbl,
            [st.request for _s, st in rows])
        first, ok, self._caches = res[0], res[1], res[2]
        if self._parity_caches is not None:
            self._parity_mirror(
                ("parity_prefill", bb, tb), self._jit_parity_prefill,
                (jnp.asarray(toks), jnp.asarray(lens),
                 self._parity_caches, jnp.asarray(sidx)) + tbl,
                res[3], list(range(len(rows))))
        if tr is not None:
            # ONE span for the batched device call, carrying every
            # rider's trace id — each request's timeline includes the
            # shared steps it rode
            tr.record_span(
                "serving.prefill", t0, time.monotonic(),
                trace_ids=tuple(st.request.trace_id for _s, st in rows
                                if st.request.trace_id is not None),
                batch=bb, seq=tb)
        first = onp.asarray(first)
        ok = onp.asarray(ok)
        for i, (slot, st) in enumerate(rows):
            if self.guard_nonfinite and not ok[i]:
                self._fail_nonfinite(slot, st, "prefill")
                continue
            st.filled = st.prompt_len
            self._finish_prefill(slot, st, int(first[i]))

    def _prefill_chunk_batch(self, rows):  # guarded-by: _step_lock
        """One chunked/offset prefill call over up to max_batch
        prefilling rows: row i writes K/V for its next
        ``min(remaining, prefill_chunk)`` prompt tokens behind its
        already-populated [0, filled) region.  Rows at different
        offsets with different chunk lengths share the call — ``lens``
        and ``off`` are runtime arrays, only (bb, tb) picks the
        program."""
        import jax.numpy as jnp

        if not self._quant_write_ok():
            return
        take = [min(st.prompt_len - st.filled, self.prefill_chunk)
                for _s, st in rows]
        tb = self.lattice.seq(max(take))
        bb = self.lattice.batch(len(rows))
        toks = onp.zeros((bb, tb), "int32")
        lens = onp.ones((bb,), "int32")
        off = onp.zeros((bb,), "int32")
        sidx = onp.full((bb,), self._alloc.scratch, "int32")
        for i, (slot, st) in enumerate(rows):
            toks[i, :take[i]] = st.tokens[st.filled:st.filled + take[i]]
            lens[i] = take[i]
            off[i] = st.filled
            sidx[i] = slot
        self.metrics.count("padded_tokens", bb * tb - sum(take))
        self.metrics.count("prefill_chunks")
        self._ensure_caches()
        tbl = (self._table_arg(),) if self._paged else ()
        samp = tuple(jnp.asarray(a) for a in self._samp_rows(
            [st.request for _s, st in rows], bb))
        tr = _trace_active()
        t0 = time.monotonic() if tr is not None else 0.0
        res = self._run_step(
            "serving.prefill", ("chunk", bb, tb), self._jit_chunk,
            (self._params(), jnp.asarray(toks), jnp.asarray(lens),
             self._caches, jnp.asarray(sidx), jnp.asarray(off)) + samp
            + tbl,
            [st.request for _s, st in rows])
        first, ok, self._caches = res[0], res[1], res[2]
        if self._parity_caches is not None:
            self._parity_mirror(
                ("parity_chunk", bb, tb), self._jit_parity_chunk,
                (jnp.asarray(toks), jnp.asarray(lens),
                 self._parity_caches, jnp.asarray(sidx),
                 jnp.asarray(off)) + tbl,
                res[3], list(range(len(rows))))
        if tr is not None:
            tr.record_span(
                "serving.prefill_chunk", t0, time.monotonic(),
                trace_ids=tuple(st.request.trace_id for _s, st in rows
                                if st.request.trace_id is not None),
                batch=bb, seq=tb)
        first = onp.asarray(first)
        ok = onp.asarray(ok)
        for i, (slot, st) in enumerate(rows):
            if self.guard_nonfinite and not ok[i]:
                # ANY chunk's non-finite logits mean the activations —
                # and therefore the K/V just written — are poisoned:
                # fail now, not at the final chunk
                self._fail_nonfinite(slot, st, "prefill")
                continue
            st.filled += take[i]
            if st.filled == st.prompt_len:
                self._finish_prefill(slot, st, int(first[i]))

    def _parity_mirror(self, key, jit_fn, args, logits, rows):  # guarded-by: _step_lock
        """debug_parity: run the fp32 gather-arm twin over the same
        tokens/page table and feed the max-abs logit delta of the LIVE
        rows into the ``kv_quant_error`` histogram — the measured side
        of the bounded-divergence contract.  The twin is observability,
        not serving: any twin failure permanently disables parity for
        this engine instead of ever failing a request."""
        try:
            ref, self._parity_caches = self._counted(
                key, jit_fn, self._params(), *args)
        except Exception:
            self._parity_caches = None
            self.debug_parity = False
            return
        if rows:
            d = onp.abs(onp.asarray(logits, dtype="float32")[rows]
                        - onp.asarray(ref, dtype="float32")[rows])
            self.metrics.observe_quant_error(float(d.max()))

    def _quant_write_ok(self) -> bool:  # guarded-by: _step_lock
        """``serving.kv_quant`` containment (docs/resilience.md): a
        quantize-write fault makes the batch sit out THIS cycle — the
        slots, their pages and their table rows are exactly as
        ``_ensure_pages`` left them (injection fires before any device
        dispatch, so no page holds a torn int8 write), and the next
        cycle re-runs the same prefill: a counted recompute, never a
        half-quantized page.  Inert unless ``kv_quant`` is on."""
        if not self.kv_quant:
            return True
        try:
            _inject("serving.kv_quant", scope=self.name)
        except Exception:
            self.metrics.count("kv_quant_faults")
            fr = _fr_active()
            if fr is not None:
                fr.record("serving.kv_quant", engine=self.name,
                          outcome="recompute")
            return False
        return True

    def _finish_prefill(self, slot: int, st: SlotState, token: int):  # guarded-by: _step_lock
        """A request's prefill just completed (full or last chunk).  A
        prefill-role engine with an attached migration target hands the
        request off to its decode-role peer; everything else — unified
        engines, no target attached, or a handoff that faulted — enters
        decode locally via :meth:`_first_token`.  The fallback is the
        degradation contract of the ``serving.migrate_out`` fault site:
        the request is served colocated, never lost."""
        if self.role == "prefill" and self._migrate_target is not None:
            if self._migrate_out(slot, st, token):
                return
        self._first_token(slot, st, token)

    def _migrate_out(self, slot: int, st: SlotState, token: int) -> bool:  # guarded-by: _step_lock
        """Export this slot's KV state and hand the request to the
        migration target.  Returns True iff the peer accepted — the
        request's future now belongs to the decode side and the local
        slot is released.  ANY failure (injected fault, digest refusal,
        peer out of slots/pages, peer dead) returns False and the
        caller finishes the request colocated.  Deliberately NOT routed
        through :meth:`_run_step`: migration is an optimization with a
        built-in fallback, so a fault here must not charge any rider's
        retry budget (``riders=()`` discipline, docs/resilience.md)."""
        from .migration import export_bundle
        req = st.request
        t0 = time.monotonic()
        fr = _fr_active()
        bundle = None
        try:
            # inject BEFORE the host copy: a faulted migration leaves
            # the slot exactly as prefill left it, so the colocated
            # fallback resumes with zero cleanup
            _inject("serving.migrate_out", scope=self.name)
            bundle = export_bundle(self, slot, st, token)
            self._migrate_target(bundle, req.future)
        except Exception as e:
            self.metrics.count("migrate_faults")
            self.metrics.count_migration("out", "fallback")
            if fr is not None:
                fr.record("serving.migrate_out", engine=self.name,
                          request=req.id, outcome="fallback",
                          error=type(e).__name__, trace_id=req.trace_id)
            return False
        # handoff accepted: the decode peer owns the request (and its
        # future) now; the bundle holds host copies, so the local pages
        # can go back to the pool immediately
        self._release(slot)
        self.metrics.count("migrations_out")
        self.metrics.count("migrated_pages", bundle.n_pages)
        self.metrics.count_migration("out", "ok")
        self.metrics.observe_migration(time.monotonic() - t0)
        if fr is not None:
            fr.record("serving.migrate_out", engine=self.name,
                      request=req.id, outcome="ok", pages=bundle.n_pages,
                      bytes=bundle.nbytes(), trace_id=req.trace_id)
        return True

    def _first_token(self, slot: int, st: SlotState, token: int):
        """A request's prefill just completed: record TTFT, donate its
        prefix to the cache (K/V [0, prompt_len) are final — decode
        writes at prompt_len and beyond), release the read pin on its
        own source entry, and enter decode."""
        st.t_first = time.monotonic()
        # release the read pin BEFORE inserting: in a pool at capacity
        # the LRU victim may be this request's own source entry, and a
        # still-held pin would block the insert forever (the source row
        # is no longer read once prefill completed)
        if st.pinned is not None:
            self._prefix.unpin(st.pinned)
            st.pinned = None
        self._prefix_insert(st, slot)
        st.advance(token)
        self._finish_if_done(slot, st)

    def _fail_nonfinite(self, slot: int, st: SlotState, where: str):  # guarded-by: _step_lock
        """One request's logits went NaN/Inf: free its slot and fail it
        typed.  Contained per-request — the rest of the batch, the
        scheduler, and the watchdog are untouched.

        The slot's cache row must be SCRUBBED: a NaN step has already
        written NaN K/V into the row, and unlike the stale-but-finite
        garbage a normal free leaves (which the causal mask renders
        harmless), NaN survives additive masking — ``-inf + NaN`` is
        NaN — so a later tenant of the row would be poisoned through
        positions it never wrote.

        Paged layout: the victim's release frees its pages (last-reader
        drop) and exactly those are scrubbed — shared prefix pages it
        was merely READING hold only clean prefill K/V and stay.  A
        page the victim WROTE that another reader still pins (an entry
        parked over its tail page) cannot be scrubbed now: it is marked
        dirty and scrubbed at its next claim, whichever path frees it."""
        written = list(st.pages[st.pages_shared:]) if self._paged else ()
        tainted: set = set()
        if self.kv_quant and self._caches is not None and st.pages:
            # distinguish a poisoned SCALE (serving.kv_scale rot —
            # detected here, at the first dequant that read it) from
            # ordinary activation NaN: scan the victim's scale sidecar
            # host-side for the exact tainted pages.  Tiny arrays,
            # failure path only.
            pids = onp.asarray(st.pages, "int32")
            for layer in self._caches:
                if "k_scale" not in layer:
                    break
                for key in ("k_scale", "v_scale"):
                    arr = onp.asarray(layer[key][pids])
                    for pid, page in zip(st.pages, arr):
                        if not onp.isfinite(page).all():
                            tainted.add(int(pid))
            if tainted:
                self.metrics.count("kv_dequant_faults")
                fr0 = _fr_active()
                if fr0 is not None:
                    fr0.record("serving.kv_scale", engine=self.name,
                               request=st.request.id,
                               pages=sorted(tainted),
                               outcome="tainted")
        freed = self._release(slot)
        if self._paged:
            if tainted and self._prefix is not None:
                # a NaN scale can sit INSIDE a shared page's
                # [0, length) region — unlike activation NaN, which
                # only ever lands past ``length`` (the donor-writes-
                # only-past-length invariant the mark-dirty path leans
                # on) — so every prefix entry mapping a tainted page is
                # dropped: the family degrades to a counted recompute
                # miss instead of failing each future sharer in turn
                for entry in [e for e in self._prefix._entries
                              if e.pages
                              and tainted.intersection(e.pages)]:
                    self._tier_pending.pop(entry, None)
                    self._prefix.remove(entry)
            # scrub whatever is now claimable — the victim's own freed
            # pages plus tainted pages the entry drops just released;
            # still-referenced ones (a live sharer mid-read, who fails
            # typed here too when it reads the NaN) go dirty and are
            # scrubbed at their next claim
            scrub = set(freed) | {p for p in tainted
                                  if self._pool._refs[p] == 0}
            self._scrub_pages(sorted(scrub))
            self._pool.mark_dirty((set(written) | tainted) - scrub)
        elif self._caches is not None:
            import jax
            self._caches = self._place_caches(jax.tree_util.tree_map(
                lambda a: a.at[slot].set(0), self._caches))
        self.metrics.count("nonfinite_outputs")
        fr = _fr_active()
        if fr is not None:
            # burst detection lives in the recorder: one NaN request is
            # that request's problem, a burst triggers a bundle
            fr.nonfinite(engine=self.name, request=st.request.id,
                         where=where, trace_id=st.request.trace_id)
        self._fail(st.request, NonFiniteOutputError(
            f"request {st.request.id}: non-finite logits in {where} "
            f"after {len(st.generated)} generated tokens — the model "
            "produced NaN/Inf for this input"))

    def _finish_if_done(self, slot: int, st: SlotState):
        if st.done or (st.request.eos_id is not None
                       and st.last_token == st.request.eos_id):
            self._release(slot)
            self._complete(st)

    def _decode_rows(self):  # guarded-by: _step_lock
        """The fixed-shape per-slot decode arrays (tokens, positions,
        sampling params) plus the riding (slot, state) pairs — shared
        by the plain step and the speculative draft/verify cycle.

        Idle rows (free slots, the scratch row, and slots still mid-
        chunked-prefill) park at position Tmax: their fixed-shape K/V
        write becomes an out-of-bounds scatter, which jax DROPS — they
        must not write at position 0, where a mid-prefill slot already
        holds real (copied or chunk-prefilled) prefix K/V."""
        s1 = self.num_slots + 1
        tok = onp.zeros((s1,), "int32")
        pos = onp.full((s1,), self.max_length, "int32")
        temp = onp.zeros((s1,), "float32")
        topk = onp.zeros((s1,), "int32")
        topp = onp.ones((s1,), "float32")
        keys = onp.zeros((s1, 2), "uint32")
        riders = []
        for slot, st in self._alloc.items():
            if st.prefilling or st.waiting:
                continue             # waiting = page allocation deferred
            r = st.request
            tok[slot] = st.last_token
            pos[slot] = st.pos
            temp[slot] = r.temperature
            topk[slot] = r.top_k
            topp[slot] = r.top_p
            keys[slot] = r.key
            riders.append((slot, st))
        return tok, pos, (temp, topk, topp, keys), riders

    def _decode_step(self):  # guarded-by: _step_lock
        import jax.numpy as jnp

        alloc = self._alloc
        tok, pos, samp, slot_riders = self._decode_rows()
        riders = [st.request for _s, st in slot_riders]
        if self._paged and self.spec_tokens:
            # a degraded/fallback cycle RETURNS the soft window claims
            # _grow_pages made for the speculation that is not running:
            # holding them under pool pressure would let an
            # optimization's claims force real work to park (the
            # documented never-parks-a-victim contract).  Never written
            # (no verify ran past the trim boundary), so no scrub.
            for slot, st in slot_riders:
                self._rewind_pages(slot, st, scrub=False)
        self.metrics.count("decode_steps")
        tbl = (self._table_arg(),) if self._paged else ()
        tr = _trace_active()
        t0 = time.monotonic() if tr is not None else 0.0
        res = self._run_step(
            "serving.decode_step", ("decode",), self._jit_step,
            (self._params(), jnp.asarray(tok), self._caches,
             jnp.asarray(pos))
            + tuple(jnp.asarray(a) for a in samp) + tbl, riders)
        nxt, ok, self._caches = res[0], res[1], res[2]
        if self._parity_caches is not None:
            self._parity_mirror(
                ("parity_decode",), self._jit_parity_step,
                (jnp.asarray(tok), self._parity_caches,
                 jnp.asarray(pos)) + tbl,
                res[3], [s for s, _st in slot_riders])
        if tr is not None:
            tr.record_span(
                "serving.decode_step", t0, time.monotonic(),
                trace_ids=tuple(r.trace_id for r in riders
                                if r.trace_id is not None),
                riders=len(riders))
        nxt = onp.asarray(nxt)
        ok = onp.asarray(ok)
        for slot, st in alloc.items():
            if st.prefilling or st.waiting:
                continue
            if self.guard_nonfinite and not ok[slot]:
                self._fail_nonfinite(slot, st, "decode")
                continue
            st.advance(int(nxt[slot]))
            self._finish_if_done(slot, st)

    # ------------------------------------------------------- speculative
    def _spec_fault(self, where: str):  # guarded-by: _step_lock
        """Contain a fault at a serving.draft/serving.verify site:
        speculation is an optimization layer and must never fail a
        request — the cycle degrades to plain one-token decode and the
        riders lose nothing but speed."""
        self.metrics.count("spec_faults")
        self.metrics.mark("spec_fault", where)

    def _spec_step(self):  # guarded-by: _step_lock
        """One speculative decode cycle (docs/serving.md "Speculative
        decode"): ONE compiled draft call proposes ``k`` tokens per
        slot (early-exit drafter, read-only on the caches), ONE
        batched verify forward writes the window's K/V and samples the
        model's own token at every window position, and the host
        accepts each slot's longest draft prefix that matches the
        verify samples plus the first non-matching verify token — so
        every accepted token is EXACTLY the token the non-speculative
        engine would have produced (greedy: longest argmax match), and
        a cycle banks between 1 and k+1 tokens for two dispatches.

        Rejected tokens rewind by bookkeeping: ``pos`` simply stops at
        the last accepted token, the stale K/V beyond it is rewritten
        before it can be attended (the chunk-padding argument), and
        under the paged layout any page claimed past the rewound
        boundary is scrubbed and released back to the pool
        (:meth:`_rewind_pages`)."""
        import jax.numpy as jnp

        alloc = self._alloc
        k = self.spec_tokens
        tok, pos, samp, slot_riders = self._decode_rows()
        if not slot_riders or all(st.remaining <= 1
                                  for _s, st in slot_riders):
            # nothing to speculate ON: every rider needs exactly one
            # more token, so a verify window would be pure overhead
            self._decode_step()
            return
        riders = [st.request for _s, st in slot_riders]
        samp_j = tuple(jnp.asarray(a) for a in samp)
        tbl = (self._table_arg(),) if self._paged else ()
        tr = _trace_active()
        tids = tuple(r.trace_id for r in riders
                     if r.trace_id is not None)
        # NaN-poisoned drafter (chaos spec_storm): the poison rides the
        # draft program as a traced scalar, so the splice recompiles
        # nothing and the garbage proposals flow through the REAL
        # rejection path
        bad = _poison("serving.draft_logits")
        pois = jnp.asarray(bad if bad is not None else 0.0, jnp.float32)
        t0 = time.monotonic() if tr is not None else 0.0
        try:
            # riders=() — like the prefix copy, the draft must degrade
            # on a retryable fault immediately, never spend the
            # requests' retry budgets (which the mandatory verify or a
            # fallback decode step may later need)
            draft = self._run_step(
                "serving.draft", ("draft",), self._jit_draft,
                (self._params(), jnp.asarray(tok), self._caches,
                 jnp.asarray(pos)) + samp_j + (pois,) + tbl, ())
        except Exception:
            # injection fires BEFORE dispatch and the draft is
            # read-only on every shared buffer either way: plain
            # decode this cycle is always safe
            self._spec_fault("draft")
            self._decode_step()
            return
        if tr is not None:
            tr.record_span("serving.draft", t0, time.monotonic(),
                           trace_ids=tids, tokens=k)
        # window = [last_token, d_1..d_k]; stays on device for the
        # verify, comes to host only for the acceptance scan
        toks2 = jnp.concatenate(
            [jnp.asarray(tok)[:, None], draft], axis=1)
        t0 = time.monotonic() if tr is not None else 0.0
        try:
            vt, ok, self._caches = self._run_step(
                "serving.verify", ("verify",), self._jit_verify,
                (self._params(), toks2, self._caches,
                 jnp.asarray(pos)) + samp_j + tbl, ())
        except Exception:
            self._spec_fault("verify")
            self._decode_step()
            return
        if tr is not None:
            tr.record_span("serving.verify", t0, time.monotonic(),
                           trace_ids=tids, tokens=k + 1)
        self.metrics.count("spec_cycles")
        draft = onp.asarray(draft)
        vt = onp.asarray(vt)
        ok = onp.asarray(ok)
        n_prop = n_acc = 0
        for slot, st in slot_riders:
            req = st.request
            if self.guard_nonfinite and not ok[slot]:
                # non-finite logits anywhere in the window: the verify
                # wrote that window's K/V, so the standard scrub-on-NaN
                # release covers exactly the poisoned pages
                self._fail_nonfinite(slot, st, "decode")
                continue
            # a slot whose budget caps the window could never accept
            # more than `remaining` drafts: counting the full k as
            # proposed would bias the acceptance rate — the documented
            # drafter-quality signal — low on short-budget traffic
            n_prop += min(k, st.remaining)
            accepted = []
            for i in range(k + 1):
                if st.remaining - len(accepted) <= 0:
                    break
                t = int(vt[slot, i])
                accepted.append(t)
                matched = i < k and int(draft[slot, i]) == t
                if matched:
                    n_acc += 1       # draft token i confirmed — counts
                    #                  even when it is the eos below
                if req.eos_id is not None and t == req.eos_id:
                    # matched-draft eos still ends the request — the
                    # non-speculative engine would have stopped here
                    break
                if not matched:
                    # v[i+1] conditioned on a rejected draft token:
                    # invalid, stop at the correction/bonus token
                    break
            st.advance_many(accepted)
            if self._paged:
                self._rewind_pages(slot, st)
            self._finish_if_done(slot, st)
        self.metrics.count("spec_tokens_proposed", n_prop)
        self.metrics.count("spec_tokens_accepted", n_acc)

    def _rewind_pages(self, slot, st, scrub=True):  # guarded-by: _step_lock
        """Release pages claimed past the rewound speculation boundary:
        the slot needs coverage through its next write position
        (``st.pos``) only.  After a verify ran, freed pages are
        SCRUBBED before they return to the pool — their window K/V is
        finite whenever the verify's guard passed, but a page crossing
        tenants carries no provenance, and zeroing the rare
        rejected-boundary page is cheaper than reasoning about it ever
        after.  ``scrub=False`` is the degraded-cycle return path: the
        claims were never written, and a still-dirty page from an older
        NaN tenant keeps its mark (scrubbed lazily at its next claim,
        as ever)."""
        keep = self._pool.pages_for(st.pos + 1)
        if len(st.pages) <= keep:
            return
        tail = st.pages[keep:]
        del st.pages[keep:]
        self._page_table[slot, keep:keep + len(tail)] = \
            self._pool.scratch
        self._table_dirty()
        freed = self._pool.release(tail)
        if freed:
            if scrub:
                self._scrub_pages(freed, count=False)
                self._pool.dirty.difference_update(freed)
            self.metrics.count("spec_pages_rewound", len(freed))

    # ----------------------------------------------------------- forward path
    def _forward_cycle(self):
        import jax.numpy as jnp

        self._overload_tick(time.monotonic())
        reqs = self._batcher.get_batch(
            self.max_batch, self.max_wait_us,
            compatible=lambda r: r.shape_key, wait=False)
        if not reqs:
            return
        live = self._filter_expired(reqs)
        if not live:
            return
        now = time.monotonic()
        for r in live:
            r.t_schedule = now
        bb = self.lattice.batch(len(live))
        xs = onp.stack([r.payload for r in live] +
                       [onp.zeros_like(live[0].payload)] *
                       (bb - len(live)))
        self.metrics.count("admitted", len(live))
        self.metrics.count("forward_batches")
        self.metrics.mark("admit", len(live))
        key = ("forward", bb) + live[0].shape_key
        # the popped batch lives in neither the batcher nor the slot
        # allocator — publish it so a watchdog trip during a hung
        # forward can still fail these futures
        self._inflight_fwd = tuple(live)
        tr = _trace_active()
        t0 = time.monotonic() if tr is not None else 0.0
        try:
            outs = self._run_step("serving.forward", key,
                                  self._jit_forward,
                                  (self._params(), jnp.asarray(xs)), live)
            outs = [onp.asarray(o) for o in outs]
            if tr is not None:
                tr.record_span(
                    "serving.forward", t0, time.monotonic(),
                    trace_ids=tuple(r.trace_id for r in live
                                    if r.trace_id is not None),
                    batch=bb)
        except BaseException as e:
            # fail the popped batch HERE or the futures hang forever;
            # the rest of the queue is untouched (no shared state to
            # poison)
            for r in live:
                self._fail(r, e)
            return
        finally:
            self._inflight_fwd = ()
        done = time.monotonic()
        for i, r in enumerate(live):
            if self.guard_nonfinite and any(
                    onp.issubdtype(o.dtype, onp.floating)
                    and not onp.isfinite(o[i]).all() for o in outs):
                self.metrics.count("nonfinite_outputs")
                self._fail(r, NonFiniteOutputError(
                    f"request {r.id}: non-finite forward output — the "
                    "model produced NaN/Inf for this input"))
                continue
            res = outs[0][i] if self._fwd_single else \
                tuple(o[i] for o in outs)
            self.metrics.observe_request(r.t_schedule - r.t_submit,
                                         done - r.t_schedule)
            self.metrics.count("completed")
            self.metrics.count_served(r.priority_name)
            if tr is not None and r.trace_id is not None:
                root = tr.new_span_id()
                tr.record_span("serving.queue", r.t_submit, r.t_schedule,
                               trace_id=r.trace_id, parent=root)
                tr.record_span("serving.request", r.t_submit, done,
                               trace_id=r.trace_id, span_id=root,
                               request=r.id)
                tr.event("serving.complete", trace_id=r.trace_id)
            r.future.set_result(res)
