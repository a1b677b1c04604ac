"""mxnet_tpu.fleet — multi-replica serving router.

Contracts under test: rendezvous routing is stable under fleet resize
(~1/N keys remap); affinity falls back to least-loaded under a
saturated target; greedy outputs THROUGH the router are token-identical
to a single engine with per-replica compile freeze after warmup;
failover respects the request's budget and original deadline; a dead
replica is probation-gated and re-admitted rebuilt; rolling restart and
fleet stop never strand a request; a replica hanging in drain is
condemned rather than wedging shutdown.
"""
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.fleet import (FleetRouter, RoutingPolicy, rendezvous_rank)
from mxnet_tpu.models import get_gpt2
from mxnet_tpu.serving import (InferenceEngine, NoHealthyReplicaError,
                               QueueFullError, RequestTimeoutError,
                               ServingError)


@pytest.fixture(scope="module")
def net():
    onp.random.seed(0)
    n = get_gpt2("gpt2_124m", vocab_size=61, units=16, num_layers=1,
                 num_heads=2, max_length=32, dropout=0.0)
    n.initialize()
    return n


def _prompts(lens, seed=1, vocab=61):
    rs = onp.random.RandomState(seed)
    return [rs.randint(0, vocab, (l,)).astype("int32") for l in lens]


def _family(n, shared_len=10, tail_len=3, seed=2, vocab=61):
    rs = onp.random.RandomState(seed)
    shared = rs.randint(0, vocab, (shared_len,)).astype("int32")
    return [onp.concatenate(
        [shared, rs.randint(0, vocab, (tail_len,)).astype("int32")])
        for _ in range(n)]


def _factory(net, **kw):
    def factory(name):
        kw.setdefault("num_slots", 2)
        kw.setdefault("max_batch", 2)
        kw.setdefault("seq_buckets", (8,))
        kw.setdefault("default_max_new_tokens", 4)
        kw.setdefault("prefix_pool_rows", 2)
        kw.setdefault("prefix_min_tokens", 2)
        kw.setdefault("watchdog_interval", 0.05)
        kw.setdefault("retry_backoff", 0.001)
        return InferenceEngine(net, name=name, **kw)
    return factory


def _refs(net, prompts, max_new):
    return [net.generate(mx.nd.array(p[None], dtype="int32"), max_new,
                         temperature=0).asnumpy()[0] for p in prompts]


# ------------------------------------------------------------ policy units

def test_rendezvous_hash_stability():
    """HRW: growing a 3-replica fleet to 4 remaps only ~1/4 of keys
    (every key whose winner survives keeps it), and removing a replica
    remaps EXACTLY the keys it owned."""
    names = [f"r{i}" for i in range(3)]
    keys = [f"key-{i}".encode() for i in range(400)]
    w3 = {k: rendezvous_rank(k, names)[0] for k in keys}
    w4 = {k: rendezvous_rank(k, names + ["r3"])[0] for k in keys}
    moved = [k for k in keys if w3[k] != w4[k]]
    # expected 1/4 = 100; generous band, but far below a modulo-hash
    # reshuffle (~3/4) and above zero
    assert 50 <= len(moved) <= 160, len(moved)
    assert all(w4[k] == "r3" for k in moved)   # moves only TO the newcomer
    w2 = {k: rendezvous_rank(k, names[:2])[0] for k in keys}
    for k in keys:
        if w3[k] != "r2":                      # survivor-owned keys stay put
            assert w2[k] == w3[k]
        else:
            assert w2[k] in ("r0", "r1")
    # determinism across calls (process-salt-free hashing)
    assert rendezvous_rank(b"abc", names) == rendezvous_rank(b"abc", names)


def test_routing_policy_affinity_key_convergence():
    """A prompt family sharing a >= window prefix keys identically from
    the FIRST request on (the window cap is what makes the opener and
    its followers agree); distinct families key apart; prompts shorter
    than min_tokens have no affinity key."""
    pol = RoutingPolicy(min_tokens=4, affinity_window=8)
    fam_a = _family(4, shared_len=12, tail_len=3, seed=5)
    fam_b = _family(4, shared_len=12, tail_len=3, seed=6)
    keys_a = [pol.affinity_key(p) for p in fam_a]
    keys_b = [pol.affinity_key(p) for p in fam_b]
    assert len(set(keys_a)) == 1 and len(set(keys_b)) == 1
    assert keys_a[0] != keys_b[0]
    assert pol.affinity_key([1, 2]) is None            # below min_tokens
    # a SHORT shared prefix (between min and window) converges from the
    # second request on — the radix walk finds the true sharing boundary
    pol2 = RoutingPolicy(min_tokens=4, affinity_window=16)
    fam_c = _family(4, shared_len=6, tail_len=4, seed=7)
    keys_c = [pol2.affinity_key(p) for p in fam_c]
    assert len(set(keys_c[1:])) == 1


def test_affinity_fallback_to_least_loaded_when_saturated(net):
    """The affinity target stops receiving traffic once its admission
    queue crosses spill_queue_depth: candidates reorder least-loaded
    first with the hot replica LAST, and the spill is counted."""
    fac = _factory(net, queue_depth=16)
    fleet = FleetRouter(factory=fac, num_replicas=2, name="spill_fleet",
                        spill_queue_depth=3)
    p = _family(1, shared_len=12, tail_len=3, seed=9)[0]
    # unstarted engines: submits queue up deterministically
    order0, _ = fleet._order_candidates(p)
    target = order0[0]
    for _ in range(3):
        target.engine.submit(p, max_new_tokens=2)
    order1, _ = fleet._order_candidates(p)
    assert order1[-1] is target and order1[0] is not target
    with fleet._counters_lock:
        c = dict(fleet._counters)
    assert c["affinity_spills"] == 1 and c["affinity_routed"] == 1
    for h in fleet._handles:              # resolve the parked futures
        h.engine.stop(drain=False)


def test_router_greedy_parity_and_per_replica_compile_freeze(net):
    """Acceptance: greedy outputs through a 3-replica router are
    token-identical to a single engine (= net.generate) for the same
    request stream, and after warmup() NO replica compiles on traffic."""
    fams = _family(4, seed=11) + _family(4, seed=12) + \
        _prompts((3, 5, 7), seed=13)
    refs = _refs(net, fams, 4)
    fleet = FleetRouter(factory=_factory(net), num_replicas=3,
                        name="parity_fleet")
    warm = fleet.warmup()
    assert set(warm) == {"parity_fleet-r0", "parity_fleet-r1",
                         "parity_fleet-r2"}
    with fleet:
        futs = [fleet.submit(p, max_new_tokens=4) for p in fams]
        outs = [f.result(timeout=120) for f in futs]
    for r, o in zip(refs, outs):
        onp.testing.assert_array_equal(r, o)
    s = fleet.stats()
    assert s["aggregate"]["completed"] == len(fams)
    for name, rep in s["replicas"].items():
        cc = rep["stats"]["compile_cache"]
        assert cc["compiles"] == warm[name], (name, cc)   # frozen
    # every family request took an affinity decision (routed to the
    # target, or counted as a spill when the target was momentarily hot)
    affinity_decisions = s["router"]["affinity_routed"] + \
        s["router"].get("affinity_spills", 0)
    assert affinity_decisions >= 8
    assert s["aggregate"]["prefix_hits"] >= 4


def test_failover_respects_deadline_and_budget(net):
    """A request failed by a crashed replica is resubmitted to a
    healthy one — but never past its ORIGINAL deadline, and never more
    than max_failovers times."""
    from mxnet_tpu.fleet.router import _FleetRequest
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="fo_fleet", max_failovers=1,
                        health_interval=10.0)   # monitor out of the way
    fleet.start()
    try:
        p = _prompts((5,), seed=21)[0]
        ref = _refs(net, [p], 4)[0]
        fut = fleet.submit(p, max_new_tokens=4)
        assert len(fut.result(timeout=60)) == len(p) + 4
        # find the replica that served it and condemn it mid-fleet
        served = [h for h in fleet._handles if h.routed > 0][0]
        served.engine.condemn("test-induced crash")
        fut2 = fleet.submit(p, max_new_tokens=4)   # placed on the survivor
        onp.testing.assert_array_equal(ref, fut2.result(timeout=60))
        # deadline already blown: failover must raise the TIMEOUT, not
        # resubmit
        req = _FleetRequest(p, "decode", 4, None,
                            time.monotonic() - 1.0, 5)
        with pytest.raises(RequestTimeoutError):
            fleet._failover(req, ServingError("crashed"))
        # budget exhausted: the ORIGINAL cause surfaces
        req2 = _FleetRequest(p, "decode", 4, None, None, 0)
        cause = ServingError("original crash")
        with pytest.raises(ServingError, match="original crash"):
            fleet._failover(req2, cause)
    finally:
        fleet.stop(timeout=30)


def test_crashed_replica_fails_over_and_readmits(net):
    """Kill one of two replicas mid-traffic: its in-flight requests
    fail over to the survivor (zero lost), the corpse is probation-
    gated, and after the window the monitor rebuilds it and traffic
    returns — the prefix hit rate recovers with it."""
    from mxnet_tpu.resilience import FaultPlan
    fams = _family(8, seed=31)
    refs = _refs(net, fams, 3)
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="kill_fleet", probation=0.3,
                        health_interval=0.03)
    fleet.warmup()
    plan = FaultPlan().raise_at("serving.scheduler", at=4)
    with plan:
        with fleet:
            futs = [fleet.submit(p, max_new_tokens=3) for p in fams]
            outs = [f.result(timeout=120) for f in futs]
            for r, o in zip(refs, outs):
                onp.testing.assert_array_equal(r, o)
            s = fleet.stats()
            assert s["router"].get("replica_deaths", 0) >= 1
            # wait out probation: the monitor rebuilds the dead replica
            deadline = time.monotonic() + 15
            while len(fleet._healthy()) < 2:
                assert time.monotonic() < deadline, fleet.health()
                time.sleep(0.05)
            h = fleet.health()
            assert h["healthy"] == 2
            assert any(r["restarts"] >= 1 for r in h["replicas"].values())
            # the reborn replica serves again, correctly
            outs2 = [fleet.infer(p, max_new_tokens=3) for p in fams]
            for r, o in zip(refs, outs2):
                onp.testing.assert_array_equal(r, o)
            assert fleet.stats()["aggregate"]["prefix_hits"] >= 1
    assert plan.fired("serving.scheduler") == 1


def test_rolling_restart_keeps_serving(net):
    """drain + rebuild each replica in sequence: every replica cycles
    (restarts == 1 each) and the fleet serves correctly before, during
    and after."""
    fams = _family(4, seed=41)
    refs = _refs(net, fams, 3)
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="roll_fleet")
    fleet.warmup()
    with fleet:
        for p, r in zip(fams, refs):
            onp.testing.assert_array_equal(
                r, fleet.infer(p, max_new_tokens=3))
        fleet.rolling_restart(timeout=60)
        s = fleet.stats()
        assert all(rep["restarts"] == 1 for rep in s["replicas"].values())
        assert s["fleet"]["healthy"] == 2
        # metrics identity FOLLOWS the replica across a rebuild: the
        # corpse released its claimed name, so the replacement engine
        # reclaimed the plain one (no drift to "<name>-2")
        for name, rep in s["replicas"].items():
            assert rep["stats"]["engine"]["name"] == name
        for p, r in zip(fams, refs):
            onp.testing.assert_array_equal(
                r, fleet.infer(p, max_new_tokens=3))


@pytest.mark.chaos
def test_rewarm_while_siblings_serve_no_tracer_leak(net):
    """Regression: rebuilding + re-warming a replica TRACES fresh jit
    programs over the SHARED net while sibling replicas keep serving.
    The trace swaps tracer values into the net's parameter payloads;
    without the cached_op param-swap lock a sibling's concurrent
    ``_params()`` snapshot captures those tracers and its next dispatch
    dies with UnexpectedTracerError.  Contract: continuous traffic
    through a rolling restart sees zero errors and stays
    token-correct."""
    fams = _family(6, seed=55)
    refs = _refs(net, fams, 3)
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="trace_fleet")
    fleet.warmup()
    errs = []
    stop = threading.Event()

    def pump():
        i = 0
        while not stop.is_set():
            p, r = fams[i % len(fams)], refs[i % len(fams)]
            try:
                if not onp.array_equal(
                        fleet.infer(p, max_new_tokens=3), r):
                    errs.append("token mismatch")
            except Exception as e:
                errs.append(repr(e))
            i += 1

    with fleet:
        t = threading.Thread(target=pump, daemon=True)
        t.start()
        time.sleep(0.1)
        fleet.rolling_restart(timeout=60)   # re-warm = traces under load
        time.sleep(0.1)
        stop.set()
        t.join(30)
    assert not errs, errs[:3]
    assert all(rep["restarts"] == 1
               for rep in fleet.stats()["replicas"].values())


def test_no_healthy_replica_typed_error(net):
    """Every replica dead and no factory: submit fails with
    NoHealthyReplicaError (not a hang, not a bare crash error)."""
    eng = _factory(net)("lonely-r0")
    fleet = FleetRouter(engines=[eng], name="lonely_fleet",
                        health_interval=10.0)
    fleet.start()
    try:
        eng.condemn("test-induced crash")
        with pytest.raises(NoHealthyReplicaError):
            fleet.submit(_prompts((5,), seed=51)[0], max_new_tokens=2)
        assert fleet.stats()["router"]["no_healthy"] >= 1
        assert not fleet.health()["ready"]
    finally:
        fleet.stop(timeout=30)


def test_all_replicas_saturated_sheds_with_queue_full(net):
    """Healthy replicas exist but every queue is at depth: the router
    sheds with QueueFullError — 'back off' is a different signal than
    'no healthy replica'."""
    fleet = FleetRouter(factory=_factory(net, queue_depth=1),
                        num_replicas=2, name="shed_fleet")
    p = _prompts((5,), seed=61)[0]
    futs = [fleet.submit(p, max_new_tokens=2) for _ in range(2)]
    with pytest.raises(QueueFullError):
        fleet.submit(p, max_new_tokens=2)
    assert fleet.stats()["router"]["sheds"] >= 2
    for h in fleet._handles:
        h.engine.stop(drain=False)
    del futs


@pytest.mark.chaos
def test_hung_drain_is_condemned_not_wedged(net):
    """Satellite contract: a replica that HANGS in drain (injected
    delay at the fleet.drain site) must be watchdog-killed — condemned,
    its futures failed typed — instead of wedging fleet stop() past its
    deadline."""
    from mxnet_tpu.resilience import FaultPlan
    from mxnet_tpu.serving import EngineCrashedError
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="wedge_fleet")
    fleet.warmup()
    plan = FaultPlan().delay_at("fleet.drain", 4.0, at=1)
    prompts = _prompts((4, 5, 6, 7), seed=71)
    with plan:
        fleet.start()
        futs = [fleet.submit(p, max_new_tokens=3) for p in prompts]
        time.sleep(0.2)                   # let some work land
        t0 = time.monotonic()
        fleet.stop(drain=True, timeout=1.0)
        elapsed = time.monotonic() - t0
    assert elapsed < 3.0, elapsed         # deadline + slack, NOT 4s+
    assert plan.fired("fleet.drain") == 1
    assert fleet.stats()["router"].get("forced_stops", 0) >= 1
    # nothing stranded: every future resolved — result or typed error
    resolved = 0
    for f in futs:
        try:
            f.result(timeout=10)
            resolved += 1
        except (EngineCrashedError, ServingError):
            resolved += 1
    assert resolved == len(prompts)


@pytest.mark.chaos
def test_route_and_failover_fault_sites_contained(net):
    """Faults at fleet.route degrade to least-loaded placement (the
    request still serves, token-correct); faults at fleet.failover
    abort that failover attempt and surface the original cause."""
    from mxnet_tpu.fleet.router import _FleetRequest
    from mxnet_tpu.resilience import FaultPlan
    fams = _family(4, seed=81)
    refs = _refs(net, fams, 3)
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="site_fleet")
    fleet.warmup()
    plan = FaultPlan().raise_at("fleet.route", every=2)
    with plan:
        with fleet:
            for p, r in zip(fams, refs):
                onp.testing.assert_array_equal(
                    r, fleet.infer(p, max_new_tokens=3))
            s = fleet.stats()
            assert s["router"]["route_faults"] == 2
            assert s["aggregate"]["completed"] == len(fams)
            # failover site: the injected fault must abort the
            # resubmission and re-raise the cause, spending nothing
            req = _FleetRequest(fams[0], "decode", 2, None, None, 5)
            cause = ServingError("replica went away")
            with FaultPlanSwap(plan,
                               FaultPlan().raise_at("fleet.failover",
                                                    at=1)):
                with pytest.raises(ServingError, match="went away"):
                    fleet._failover(req, cause)
            assert req.failovers_left == 5     # budget untouched
            # ... and so is the fleet-wide retry token bucket: a
            # faulted attempt must not starve other requests' retries
            assert fleet._retry_budget.available \
                == fleet._retry_budget.burst
            assert fleet.stats()["router"]["failover_faults"] == 1


class FaultPlanSwap:
    """Temporarily swap the active FaultPlan (plans do not nest)."""

    def __init__(self, outer, inner):
        self.outer, self.inner = outer, inner

    def __enter__(self):
        self.outer.__exit__()
        self.inner.__enter__()
        return self.inner

    def __exit__(self, *exc):
        self.inner.__exit__()
        self.outer.__enter__()


def test_hedged_request_completes_on_second_replica(net):
    """With hedge_after set, a request stuck on a slow primary is
    duplicated onto another healthy replica and the first completion
    wins — greedy decode is deterministic, so the result is identical
    either way."""
    from mxnet_tpu.resilience import FaultPlan
    p = _prompts((5,), seed=91)[0]
    ref = _refs(net, [p], 3)[0]
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="hedge_fleet", hedge_after=0.15)
    fleet.warmup()
    plan = FaultPlan().delay_at("serving.prefill", 2.5, at=1)
    with plan:
        with fleet:
            t0 = time.monotonic()
            out = fleet.infer(p, max_new_tokens=3)
            elapsed = time.monotonic() - t0
    onp.testing.assert_array_equal(ref, out)
    assert elapsed < 2.0, elapsed          # did not wait out the delay
    assert fleet.stats()["router"].get("hedges", 0) == 1


@pytest.mark.fleet
@pytest.mark.slow
def test_affinity_beats_random_routing_ttft():
    """Perf contract (a CPU timing, never a device number): on a repeated-
    system-prompt workload over 3 replicas, prefix-affinity routing
    yields a strictly higher fleet prefix hit rate than seeded random
    routing, and cuts mean TTFT.  Needs a compute-bound prefill, so it
    builds its own net; excluded from tier-1 via the slow marker."""
    big = get_gpt2("gpt2_124m", vocab_size=512, units=256, num_layers=4,
                   num_heads=8, max_length=144, dropout=0.0)
    big.initialize()
    rs = onp.random.RandomState(7)
    families = []
    for g in range(3):
        shared = rs.randint(0, 512, (120,)).astype("int32")
        families.append([onp.concatenate(
            [shared, rs.randint(0, 512, (8,)).astype("int32")])
            for _ in range(8)])
    stream = [p for trio in zip(*families) for p in trio]   # interleaved

    def run(routing):
        def fac(name):
            return InferenceEngine(
                big, num_slots=1, max_batch=1, seq_buckets=(32, 128),
                default_max_new_tokens=2, prefix_pool_rows=4,
                prefix_min_tokens=8, name=name)
        fleet = FleetRouter(factory=fac, num_replicas=3, routing=routing,
                            name=f"perf_{routing}")
        fleet.warmup()
        with fleet:
            for p in stream:
                fleet.infer(p, max_new_tokens=2)
            s = fleet.stats()
        ttfts = [rep["stats"]["ttft"]["mean_ms"]
                 for rep in s["replicas"].values()
                 if rep["stats"]["ttft"]["count"]]
        n = sum(rep["stats"]["ttft"]["count"]
                for rep in s["replicas"].values())
        mean = sum(rep["stats"]["ttft"]["mean_ms"] *
                   rep["stats"]["ttft"]["count"]
                   for rep in s["replicas"].values()) / n
        return s["aggregate"]["prefix_hit_rate"], mean, ttfts

    hit_r, ttft_r, _ = run("random")
    hit_a, ttft_a, _ = run("affinity")
    assert hit_a > hit_r, (hit_a, hit_r)
    assert hit_a >= 0.8, hit_a
    assert ttft_a < ttft_r, (ttft_a, ttft_r)


# -------------------------------------------------- gray-failure ejection


class _FakeEngine:
    """Just enough engine surface for ReplicaHandle state-machine units."""

    def __init__(self, live=True):
        self.live = live

    def health(self):
        return {"live": self.live, "crashed": None if self.live
                else "test-induced"}


def test_suspect_state_machine_ladder_and_death():
    """ReplicaHandle units (docs/integrity.md): mark_suspect uses the
    probation/backoff ladder keyed on consecutive ejections; unsuspect
    resets the latency window; a SUSPECT that fails health() goes DEAD
    normally; DEAD/DRAINING replicas cannot be marked suspect."""
    from mxnet_tpu.fleet import SUSPECT, ReplicaHandle
    from mxnet_tpu.fleet.replica import DEAD, HEALTHY
    h = ReplicaHandle("r0", _FakeEngine(), probation=0.5,
                      probation_backoff=2.0, probation_max=30.0)
    for s in (0.01, 0.02, 0.5):
        h.observe_latency(s)
    assert h.latency.snapshot()["count"] == 3
    assert h.mark_suspect("slow", now=100.0)
    assert h.state == SUSPECT and not h.routable()
    assert h.suspect_until == 100.5                  # ladder rung 1
    assert not h.mark_suspect("again", now=100.1)    # already suspect
    assert not h.due_for_unsuspect(now=100.4)
    assert h.due_for_unsuspect(now=100.6)
    assert h.unsuspect()
    assert h.state == HEALTHY
    assert h.latency.snapshot()["count"] == 0        # window cleared
    assert h.mark_suspect("still slow", now=200.0)
    assert h.suspect_until == 201.0                  # rung 2: doubled
    # a suspect whose engine actually dies goes DEAD through probe()
    h.engine.live = False
    assert h.probe(now=200.1)
    assert h.state == DEAD and h.probation_until is not None
    assert not h.mark_suspect("dead now", now=200.2)
    assert h.total_suspects == 2 and h.total_deaths == 1


def test_gray_detector_two_replica_fleet_ejects_outlier():
    """_gray_check judges each replica against the median of its PEERS'
    EWMAs (self-excluded).  Regression: with the candidate included in
    its own median, a 2-replica fleet could NEVER eject — the bar is
    m*(f+s)/2 and s >= m*(f+s)/2 has no positive solution for any
    multiplier >= 2, so the outlier inflated its own bar forever."""
    import threading as _threading
    from mxnet_tpu.fleet import FleetRouter, SUSPECT, ReplicaHandle
    from mxnet_tpu.fleet.replica import HEALTHY
    r = FleetRouter.__new__(FleetRouter)   # only what _gray_check reads
    r.gray_ejection = True
    r.gray_multiplier = 4.0
    r.gray_min_samples = 4
    r._counters = {}
    r._counters_lock = _threading.Lock()
    fast = ReplicaHandle("r0", _FakeEngine())
    slow = ReplicaHandle("r1", _FakeEngine())
    r._handles = [fast, slow]
    for _ in range(6):
        fast.observe_latency(0.01)
        slow.observe_latency(0.5)          # 50x its only peer
    r._gray_check(now=100.0)
    assert slow.state == SUSPECT
    assert fast.state == HEALTHY           # judged vs the SLOW peer's
    assert fast.suspects == 0              # median: far under, ladder reset
    assert r._counters["gray_ejections"] == 1


def test_timed_out_request_feeds_gray_latency_evidence():
    """A replica that holds a request past its deadline must feed the
    gray detector a latency sample — otherwise a replica slow enough
    that EVERYTHING times out contributes zero samples and keeps its
    keyspace forever (the worst gray regime, invisible).  Admission-time
    DeadlineInfeasibleError stays excluded: its near-instant rejection
    is not latency evidence and would dilute the window."""
    from mxnet_tpu.fleet.router import FleetFuture
    from mxnet_tpu.serving.errors import (DeadlineInfeasibleError,
                                          RequestTimeoutError)

    class _StubRouter:
        def __init__(self):
            self.samples = []

        def _observe_completion(self, handle, seconds):
            self.samples.append((handle, seconds))

    class _TimedOutFut:
        trace_id = None
        t_done = None

        def __init__(self, exc):
            self._exc = exc

        def done(self):
            return True

        def result(self, timeout=None):
            raise self._exc

    router = _StubRouter()
    handle = object()
    fut = FleetFuture(router, object(), handle, _TimedOutFut(
        RequestTimeoutError("deadline exceeded fleet-side")))
    with pytest.raises(RequestTimeoutError):
        fut.result(1.0)
    assert len(router.samples) == 1 and router.samples[0][0] is handle

    router2 = _StubRouter()
    fut2 = FleetFuture(router2, object(), handle, _TimedOutFut(
        DeadlineInfeasibleError("infeasible on arrival")))
    with pytest.raises(DeadlineInfeasibleError):
        fut2.result(1.0)
    assert router2.samples == []           # admission reject: no sample


def test_suspect_is_not_saturation_evidence(net):
    """A SUSPECT replica is skipped by placement WITHOUT counting as a
    shed: traffic flows to the healthy rest, no FleetSaturatedError, no
    coordinated brownout; all-SUSPECT surfaces NoHealthyReplicaError
    (typed apart from saturation)."""
    from mxnet_tpu.serving import FleetSaturatedError
    fleet = FleetRouter(factory=_factory(net), num_replicas=2,
                        name="graysat_fleet", health_interval=10.0)
    fleet.warmup()
    p = _prompts((6,), seed=101)[0]
    ref = _refs(net, [p], 3)[0]
    with fleet:
        ha, hb = fleet._handles
        assert ha.mark_suspect("test: gray")
        for _ in range(3):
            onp.testing.assert_array_equal(
                ref, fleet.infer(p, max_new_tokens=3))
        s = fleet.stats()
        assert s["router"].get("sheds", 0) == 0
        assert s["router"].get("fleet_brownouts", 0) == 0
        assert ha.routed == 0 and hb.routed == 3
        assert s["replicas"][ha.name]["state"] == "suspect"
        # every replica suspect: typed NoHealthyReplica, never a shed
        assert hb.mark_suspect("test: gray too")
        with pytest.raises(NoHealthyReplicaError):
            fleet.submit(p, max_new_tokens=3)
        with pytest.raises(NoHealthyReplicaError):
            try:
                fleet.submit(p, max_new_tokens=3)
            except FleetSaturatedError:        # would be the WRONG type
                pytest.fail("SUSPECT read as saturation")
        assert ha.unsuspect() and hb.unsuspect()
        onp.testing.assert_array_equal(ref,
                                       fleet.infer(p, max_new_tokens=3))


@pytest.mark.chaos
def test_gray_replica_ejected_and_readmitted_no_rebuild(net):
    """THE gray-failure contract (docs/integrity.md): one replica of
    three serves ~10x slow (scoped delay fault at ITS decode-step site)
    while still answering health().  The router must SUSPECT-eject it
    off the completion-latency outlier signal (zero lost requests, its
    HRW keyspace remapping onto the healthy rest), keep it unroutable
    while suspect, then re-admit it WITHOUT a rebuild once the window
    clears — zero compiles on traffic, warm caches — and never read the
    ejection as fleet saturation."""
    from mxnet_tpu.fleet import SUSPECT
    from mxnet_tpu.resilience import FaultPlan
    fleet = FleetRouter(factory=_factory(net), num_replicas=3,
                        name="gray_fleet", routing="least_loaded",
                        health_interval=0.02, gray_min_samples=4,
                        gray_multiplier=3.0, probation=1.0)
    n_warm = sum(fleet.warmup().values())
    prompts = _prompts((5, 6, 7, 5, 6, 7), seed=111)
    refs = _refs(net, prompts, 3)
    slow = fleet._by_name["gray_fleet-r1"]
    plan = FaultPlan().delay_at(
        "serving.decode_step@gray_fleet-r1", 0.1, every=1)
    lost = 0
    with fleet:
        plan.__enter__()
        try:
            for _burst in range(8):
                futs = [fleet.submit(p, max_new_tokens=3, timeout=30.0)
                        for p in prompts]
                for ref, f in zip(refs, futs):
                    try:
                        onp.testing.assert_array_equal(ref, f.result(60))
                    except AssertionError:
                        raise
                    except Exception:
                        lost += 1
                if fleet.stats()["router"].get("gray_ejections", 0):
                    break
        finally:
            plan.__exit__(None, None, None)
        assert lost == 0
        s = fleet.stats()
        assert s["router"].get("gray_ejections", 0) >= 1
        assert slow.state == SUSPECT and "gray failure" in slow.last_error
        # keyspace: the suspect's HRW share remaps onto the healthy two
        # — every key it did NOT own keeps its winner (~1/N move)
        names = [h.name for h in fleet._handles]
        healthy = [h.name for h in fleet._healthy()]
        keys = [f"fam-{i}".encode() for i in range(300)]
        moved = 0
        for k in keys:
            w3 = rendezvous_rank(k, names)[0]
            w2 = rendezvous_rank(k, healthy)[0]
            if w3 == slow.name:
                moved += 1
            else:
                assert w2 == w3                  # survivors keep keys
        assert 60 <= moved <= 140, moved         # ~1/3 of 300
        # while suspect: no traffic lands on it, and the skip is not
        # saturation evidence
        routed0 = slow.routed
        for p, ref in zip(prompts, refs):
            onp.testing.assert_array_equal(
                ref, fleet.infer(p, max_new_tokens=3))
        assert slow.routed == routed0
        assert fleet.stats()["router"].get("fleet_brownouts", 0) == 0
        # fault lifted: suspension elapses, the monitor re-admits with
        # NO rebuild and traffic returns
        deadline = time.monotonic() + 20
        while slow.state == SUSPECT and time.monotonic() < deadline:
            time.sleep(0.05)
        assert slow.state == "healthy"
        assert fleet.stats()["router"].get("gray_readmissions", 0) >= 1
        for _ in range(3):
            # burst submits so least-loaded placement SPREADS (a
            # sequential infer always ties onto the first replica)
            futs = [fleet.submit(p, max_new_tokens=3, timeout=30.0)
                    for p in prompts]
            for ref, f in zip(refs, futs):
                onp.testing.assert_array_equal(ref, f.result(60))
        assert slow.routed > routed0             # back in rotation
        s = fleet.stats()
        assert s["replicas"][slow.name]["restarts"] == 0   # no rebuild
        compiles = sum(rep["stats"]["compile_cache"]["compiles"]
                       for rep in s["replicas"].values())
        assert compiles == n_warm                # zero compiles on traffic
