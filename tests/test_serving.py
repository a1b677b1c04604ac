"""mxnet_tpu.serving — online inference engine.

Contracts under test: batched continuous decoding is TOKEN-IDENTICAL to
per-request ``net.generate``; compiles are bounded by the bucket
lattice; backpressure sheds, deadlines fire, shutdown drains.
"""
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.models import get_gpt2
from mxnet_tpu.serving import (BucketLattice, EngineStoppedError,
                               InferenceEngine, InvalidRequestError,
                               LatencyHistogram, QueueFullError,
                               RequestTimeoutError)


@pytest.fixture(scope="module")
def net():
    onp.random.seed(0)
    n = get_gpt2("gpt2_124m", vocab_size=97, units=32, num_layers=2,
                 num_heads=4, max_length=64, dropout=0.0)
    n.initialize()
    return n


def _prompts(lens, seed=1):
    rs = onp.random.RandomState(seed)
    return [rs.randint(0, 97, (l,)).astype("int32") for l in lens]


def _engine(net, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("default_max_new_tokens", 8)
    return InferenceEngine(net, **kw)


# ------------------------------------------------------------------ parity

def test_batched_greedy_parity_and_bounded_compiles(net):
    """The acceptance contract: a mixed-length concurrent workload decoded
    by the engine is token-identical to per-request net.generate, and the
    number of XLA programs stays <= twice the bucket lattice (full +
    chunked prefill variants) + decode step + prefix row copy."""
    prompts = _prompts((3, 5, 9, 12, 5, 7, 16, 2))
    refs = [net.generate(mx.nd.array(p[None], dtype="int32"), 8,
                         temperature=0).asnumpy()[0] for p in prompts]
    eng = _engine(net)
    n_warm = eng.warmup()
    bound = 2 * len(eng.lattice) + 2       # full+chunk lattices, decode, copy
    assert n_warm <= bound
    with eng:
        futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
    for r, o in zip(refs, outs):
        onp.testing.assert_array_equal(r, o)
    s = eng.stats()
    # mixed-shape traffic after warmup NEVER compiles: all bucket hits
    assert s["compile_cache"]["compiles"] == n_warm
    assert s["compile_cache"]["compiles"] <= bound
    assert s["compile_cache"]["bucket_hits"] > 0
    assert s["requests"]["completed"] == len(prompts)
    assert s["tokens"]["tokens_generated"] == 8 * len(prompts)


def test_single_request_sync_infer(net):
    p = _prompts((6,), seed=3)[0]
    ref = net.generate(mx.nd.array(p[None], dtype="int32"), 5,
                       temperature=0).asnumpy()[0]
    with _engine(net) as eng:
        out = eng.infer(p, max_new_tokens=5)
    onp.testing.assert_array_equal(ref, out)
    assert out.dtype == onp.int32


def test_eos_stops_generation_early(net):
    p = _prompts((6,), seed=4)[0]
    ref = net.generate(mx.nd.array(p[None], dtype="int32"), 8,
                       temperature=0).asnumpy()[0]
    gen = ref[len(p):]
    eos = int(gen[2])                # a token greedy decoding DOES emit
    stop_at = int(onp.argmax(gen == eos))    # first occurrence
    with _engine(net) as eng:
        out = eng.infer(p, max_new_tokens=8, eos_id=eos)
    assert len(out) == len(p) + stop_at + 1 and out[-1] == eos
    onp.testing.assert_array_equal(ref[:len(out)], out)


# ------------------------------------------------------------- edge cases

def test_queue_overflow_sheds(net):
    eng = _engine(net, queue_depth=3)       # NOT started: queue only fills
    p = _prompts((4,), seed=5)[0]
    futs = [eng.submit(p) for _ in range(3)]
    with pytest.raises(QueueFullError):
        eng.submit(p)
    s = eng.stats()
    assert s["requests"]["rejected_queue_full"] == 1
    assert s["requests"]["submitted"] == 4
    eng.stop(drain=False)                   # sheds the queued three
    for f in futs:
        with pytest.raises(EngineStoppedError):
            f.result(timeout=5)


def test_request_timeout_in_queue(net):
    eng = _engine(net)                       # not yet started
    p = _prompts((4,), seed=6)[0]
    fut = eng.submit(p, timeout=0.01)
    ok = eng.submit(p, max_new_tokens=2)     # no deadline — must survive
    time.sleep(0.05)
    with eng.start():
        with pytest.raises(RequestTimeoutError):
            fut.result(timeout=60)
        assert len(ok.result(timeout=120)) == len(p) + 2
    assert eng.stats()["requests"]["timeouts"] == 1


def test_invalid_requests_rejected(net):
    eng = _engine(net)
    with pytest.raises(InvalidRequestError):
        # prompts longer than the largest seq bucket are admissible now
        # (chunked prefill) — but prompt + generation must fit the KV rows
        eng.submit(onp.arange(60, dtype="int32"))        # 60 + 8 > 64
    with pytest.raises(InvalidRequestError):
        eng.submit(onp.arange(16, dtype="int32"),
                   max_new_tokens=64)                     # KV overflow
    with pytest.raises(InvalidRequestError):
        eng.submit(onp.zeros((0,), "int32"))
    with pytest.raises(InvalidRequestError):
        eng.submit(onp.zeros((2, 8), "int32"))   # a BATCH is not a prompt
    with pytest.raises(InvalidRequestError):
        eng.submit(onp.arange(4, dtype="int32"),
                   max_new_tokens=0)     # explicit 0 is an error, not default
    with pytest.raises(mx.MXNetError):
        _engine(net, max_length=128)     # beyond the net's position table
    assert eng.stats()["requests"]["rejected_invalid"] == 5


def test_mixed_length_prompts_share_buckets(net):
    """Prompts landing in different buckets batch independently and all
    complete; per-bucket padding is accounted."""
    prompts = _prompts((2, 3, 15, 16, 8, 4), seed=7)
    with _engine(net) as eng:
        outs = [f.result(timeout=120)
                for f in [eng.submit(p, max_new_tokens=4) for p in prompts]]
    for p, o in zip(prompts, outs):
        assert len(o) == len(p) + 4
        onp.testing.assert_array_equal(o[:len(p)], p)
    s = eng.stats()
    assert s["requests"]["completed"] == 6
    assert s["tokens"]["prompt_tokens"] == sum(len(p) for p in prompts)
    assert s["tokens"]["padded_tokens"] > 0


def test_deadline_expiry_racing_drain(net):
    """A request that expires while QUEUED during a drain must resolve
    with DeadlineExceededError (== RequestTimeoutError) — not hang, not
    silently vanish: stop(drain=True) only returns once every future is
    resolved."""
    from mxnet_tpu.serving import DeadlineExceededError
    eng = _engine(net, num_slots=1, max_batch=1).start()
    # occupy the only slot so the racer stays queued while draining
    long_fut = eng.submit(_prompts((6,), seed=20)[0], max_new_tokens=8)
    racer = eng.submit(_prompts((4,), seed=21)[0], max_new_tokens=8,
                       timeout=0.01)
    time.sleep(0.05)                  # deadline blows while still queued
    eng.stop(drain=True, timeout=300)
    assert racer.done() and long_fut.done()   # nothing outlives stop()
    with pytest.raises(DeadlineExceededError):
        racer.result(timeout=1)
    assert len(long_fut.result(timeout=1)) == 6 + 8
    assert eng.stats()["requests"]["timeouts"] == 1


def test_shutdown_drains_cleanly(net):
    prompts = _prompts((5, 9, 3, 6, 11, 2), seed=8)
    eng = _engine(net).start()
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.stop(drain=True, timeout=300)        # returns only once drained
    for p, f in zip(prompts, futs):
        out = f.result(timeout=1)            # must already be done
        assert len(out) == len(p) + 6
    with pytest.raises(EngineStoppedError):
        eng.submit(prompts[0])
    from mxnet_tpu.serving import ServingError
    with pytest.raises(ServingError):
        eng.start()                          # no restart: build a new one


# ------------------------------------------------------------ forward path

def test_forward_mode_batching_parity(net):
    from mxnet_tpu.gluon import nn
    dense = nn.Dense(8, in_units=16)
    dense.initialize()
    xs = onp.random.RandomState(9).randn(5, 16).astype("float32")
    ref = dense(mx.nd.array(xs)).asnumpy()
    eng = InferenceEngine(dense, max_batch=4)
    assert eng.mode == "forward"
    n_warm = eng.warmup(example_shape=(16,))
    assert n_warm == len(eng.lattice.batch_buckets)
    with eng:
        outs = [f.result(timeout=60) for f in
                [eng.submit(x) for x in xs]]
    onp.testing.assert_allclose(onp.stack(outs), ref, rtol=1e-5, atol=1e-6)
    s = eng.stats()
    assert s["compile_cache"]["compiles"] == n_warm
    assert s["requests"]["completed"] == 5
    # forward mode has no token phases: compute lands in "prefill",
    # the decode and TTFT histograms stay EMPTY (not padded with zeros)
    assert s["latency"]["prefill"]["count"] == 5
    assert s["latency"]["decode"]["count"] == 0
    assert s["ttft"]["count"] == 0


# ------------------------------------------------------- prefix cache

def _shared_prefix_prompts(n, shared_len=10, tail_len=4, seed=9):
    rs = onp.random.RandomState(seed)
    shared = rs.randint(0, 97, (shared_len,)).astype("int32")
    return [onp.concatenate([shared,
                             rs.randint(0, 97, (tail_len,)).astype("int32")])
            for _ in range(n)]


def test_prefix_cache_parity_on_vs_off(net):
    """THE acceptance contract: greedy decode through the engine is
    token-identical with the prefix cache enabled vs disabled (and vs
    per-request generate), while the cache actually hits and the
    compiles counter stays frozen after warmup."""
    prompts = _shared_prefix_prompts(6)
    refs = [net.generate(mx.nd.array(p[None], dtype="int32"), 8,
                         temperature=0).asnumpy()[0] for p in prompts]
    off = _engine(net)
    off.warmup()
    with off:
        outs_off = [off.infer(p, max_new_tokens=8) for p in prompts]
    on = _engine(net, prefix_pool_rows=4, prefix_min_tokens=2)
    n_warm = on.warmup()
    with on:
        # serial submits so every later request can hit the first insert
        outs_on = [on.infer(p, max_new_tokens=8) for p in prompts]
    for r, o_off, o_on in zip(refs, outs_off, outs_on):
        onp.testing.assert_array_equal(r, o_off)
        onp.testing.assert_array_equal(r, o_on)
    s = on.stats()
    assert s["compile_cache"]["compiles"] == n_warm   # frozen after warmup
    pc = s["prefix_cache"]
    assert pc["prefix_hits"] >= len(prompts) - 1
    assert pc["prefix_tokens_saved"] >= (len(prompts) - 1) * 9
    assert pc["prefix_inserts"] >= 1
    assert off.stats()["prefix_cache"]["prefix_hits"] == 0


def test_prefix_cache_eviction_under_slot_pressure(net):
    """A 1-row pool under a stream of distinct prompts must LRU-evict
    (zero-reader entries only) and keep serving correct tokens."""
    prompts = _prompts((12, 13, 14, 12, 11), seed=23)
    refs = [net.generate(mx.nd.array(p[None], dtype="int32"), 6,
                         temperature=0).asnumpy()[0] for p in prompts]
    eng = _engine(net, prefix_pool_rows=1, prefix_min_tokens=2)
    eng.warmup()
    with eng:
        outs = [eng.infer(p, max_new_tokens=6) for p in prompts]
    for r, o in zip(refs, outs):
        onp.testing.assert_array_equal(r, o)
    pc = eng.stats()["prefix_cache"]
    assert pc["prefix_evictions"] >= 3       # 5 distinct prompts, 1 row
    assert eng.stats()["engine"]["prefix_entries"] == 1


def test_prefix_cache_radix_and_refcounts():
    """PrefixCache unit semantics: longest-common-prefix lookup across
    entries (any prefix of a cached row is usable), LRU eviction under
    pool pressure, and pinned (refcounted) entries are NEVER evicted —
    shared prefixes are freed only at zero readers."""
    from mxnet_tpu.serving import PrefixCache
    pc = PrefixCache(pool_rows=2, row_base=100, min_tokens=2)
    a = pc.insert([1, 2, 3, 4, 5, 6])
    assert a is not None and a.row == 100 and a.length == 6
    # partial match against a longer entry: [1,2,3,9] shares [1,2,3)
    m = pc.lookup([1, 2, 3, 9, 9])
    assert m is not None and m[0] == 3 and m[1] is a
    # exact re-insert is a no-op (touched, not duplicated)
    assert pc.insert([1, 2, 3, 4, 5, 6]) is None and len(pc) == 1
    b = pc.insert([7, 8, 9])
    assert b is not None and len(pc) == 2 and pc.free_rows == 0
    # pool full: next insert evicts the LRU zero-reader entry (a)
    c = pc.insert([5, 5, 5, 5])
    assert c is not None and c.row == a.row and pc.evictions == 1
    assert pc.lookup([1, 2, 3, 4]) is None           # a is gone
    # pin both survivors: NOTHING is evictable, insert must refuse —
    # and a refused insert must not leak radix nodes (regression: a
    # pool pinned full used to grow one dead node per refusal)
    def n_nodes():
        stack, n = [pc._root], 0
        while stack:
            cur = stack.pop()
            n += 1
            stack.extend(cur.children.values())
        return n
    pc.pin(b), pc.pin(c)
    before = n_nodes()
    for _ in range(5):
        assert pc.insert([6, 6, 6]) is None
    assert pc.evictions == 1 and n_nodes() == before
    # one unpin frees exactly that entry for eviction
    pc.unpin(c)
    d = pc.insert([6, 6, 6])
    assert d is not None and d.row == c.row and pc.evictions == 2
    assert pc.lookup([7, 8, 9])[1] is b              # pinned b survived
    with pytest.raises(RuntimeError):
        pc.unpin(c)                                  # already at zero refs
    # reset forgets everything (engine calls it when device caches drop)
    pc.reset()
    assert len(pc) == 0 and pc.free_rows == 2
    assert pc.lookup([7, 8, 9]) is None


def test_chunked_prefill_longer_than_largest_bucket(net):
    """A prompt LONGER than the largest seq bucket prefills in chunks
    (token-identical to generate) and never stalls an in-flight short
    decode: both complete, compiles stay frozen."""
    long_p = _prompts((40,), seed=33)[0]       # largest bucket is 16
    short_p = _prompts((5,), seed=34)[0]
    ref_long = net.generate(mx.nd.array(long_p[None], dtype="int32"), 8,
                            temperature=0).asnumpy()[0]
    ref_short = net.generate(mx.nd.array(short_p[None], dtype="int32"), 8,
                             temperature=0).asnumpy()[0]
    eng = _engine(net, prefill_chunk=16)
    n_warm = eng.warmup()
    with eng:
        f_long = eng.submit(long_p, max_new_tokens=8)
        f_short = eng.submit(short_p, max_new_tokens=8)
        onp.testing.assert_array_equal(ref_long, f_long.result(timeout=120))
        onp.testing.assert_array_equal(ref_short,
                                       f_short.result(timeout=120))
    s = eng.stats()
    assert s["compile_cache"]["compiles"] == n_warm
    assert s["batches"]["prefill_chunks"] >= 3     # 40 tokens / 16-chunks
    # chunking also composes with the prefix cache: a second engine
    # serving the same long prompt twice hits on the whole prefix
    eng2 = _engine(net, prefill_chunk=16, prefix_pool_rows=2,
                   prefix_min_tokens=2)
    eng2.warmup()
    with eng2:
        o1 = eng2.infer(long_p, max_new_tokens=8)
        o2 = eng2.infer(long_p, max_new_tokens=8)
    onp.testing.assert_array_equal(ref_long, o1)
    onp.testing.assert_array_equal(ref_long, o2)
    pc = eng2.stats()["prefix_cache"]
    assert pc["prefix_hits"] == 1 and pc["prefix_tokens_saved"] == 39


def test_prefix_fault_injection_keeps_serving(net):
    """Faults at the serving.prefix_* sites degrade to cache misses —
    tokens stay correct, nothing is stranded — and repeated faults
    disable the cache for the engine's lifetime."""
    from mxnet_tpu.resilience import FaultPlan
    prompts = _shared_prefix_prompts(6, seed=41)
    refs = [net.generate(mx.nd.array(p[None], dtype="int32"), 6,
                         temperature=0).asnumpy()[0] for p in prompts]
    eng = _engine(net, prefix_pool_rows=4, prefix_min_tokens=2,
                  prefix_fault_limit=3)
    eng.warmup()
    plan = (FaultPlan()
            .raise_at("serving.prefix_copy", at=2)
            .raise_at("serving.prefix_lookup", every=1, max_fires=8))
    with plan:
        with eng:
            outs = [eng.infer(p, max_new_tokens=6) for p in prompts]
    for r, o in zip(refs, outs):
        onp.testing.assert_array_equal(r, o)
    s = eng.stats()
    assert s["requests"]["completed"] == len(prompts)
    assert s["prefix_cache"]["prefix_faults"] >= 3
    assert s["engine"]["prefix_disabled"]          # tripped the limit
    assert plan.fired("serving.prefix_lookup") >= 3


def test_prefix_copy_fault_streak_disables(net):
    """A permanently failing COPY path must trip the disable limit even
    though every copy is preceded by a clean lookup (per-site streaks),
    and copy faults must not spend the request's retry budget — tokens
    stay correct throughout."""
    from mxnet_tpu.resilience import FaultPlan
    prompts = _shared_prefix_prompts(6, seed=71)
    refs = [net.generate(mx.nd.array(p[None], dtype="int32"), 6,
                         temperature=0).asnumpy()[0] for p in prompts]
    eng = _engine(net, prefix_pool_rows=4, prefix_min_tokens=2,
                  prefix_fault_limit=3)
    eng.warmup()
    plan = FaultPlan().raise_at("serving.prefix_copy", every=1,
                                retryable=True)
    with plan:
        with eng:
            outs = [eng.infer(p, max_new_tokens=6) for p in prompts]
    for r, o in zip(refs, outs):
        onp.testing.assert_array_equal(r, o)
    s = eng.stats()
    assert s["requests"]["completed"] == len(prompts)
    assert s["engine"]["prefix_disabled"]
    assert s["prefix_cache"]["prefix_inserts"] == 0
    # retryable copy faults degrade immediately — no budgeted retries
    assert s["resilience"]["retries"] == 0


def test_phase_latency_and_ttft_reported(net):
    with _engine(net, prefix_pool_rows=2) as eng:
        eng.infer(_prompts((6,), seed=50)[0], max_new_tokens=4)
    s = eng.stats()
    lat = s["latency"]
    for phase in ("queue", "prefill", "decode", "total"):
        assert lat[phase]["count"] == 1
    assert s["ttft"]["count"] == 1
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert s["ttft"][k] >= 0
    # decode happened after the first token: total >= prefill component
    assert lat["total"]["mean_ms"] >= lat["prefill"]["mean_ms"]


@pytest.mark.slow
@pytest.mark.serving_perf
def test_prefix_cache_cuts_ttft():
    """Perf contract (a CPU timing, never a device number): on a
    repeated-system-prompt workload the cache cuts median TTFT >= 25%
    at a >= 80% hit rate.  Needs a COMPUTE-bound prefill (the module
    fixture's model is dispatch-bound — a 120-token prefill there costs
    less than the row copy it avoids), so it builds its own net;
    excluded from the tier-1 smoke run via the slow marker."""
    big = get_gpt2("gpt2_124m", vocab_size=512, units=256, num_layers=4,
                   num_heads=8, max_length=144, dropout=0.0)
    big.initialize()
    rs = onp.random.RandomState(7)
    shared = rs.randint(0, 512, (120,)).astype("int32")
    prompts = [onp.concatenate(
        [shared, rs.randint(0, 512, (8,)).astype("int32")])
        for _ in range(12)]

    def run(**kw):
        eng = InferenceEngine(big, num_slots=2, max_batch=2,
                              seq_buckets=(16, 32, 64, 128),
                              default_max_new_tokens=2, **kw)
        eng.warmup()
        with eng:
            for p in prompts:
                eng.infer(p, max_new_tokens=2)
        return eng.stats()

    s_off = run()
    s_on = run(prefix_pool_rows=2, prefix_min_tokens=8)
    ttft_off = s_off["ttft"]["p50_ms"]
    ttft_on = s_on["ttft"]["p50_ms"]
    assert s_on["prefix_cache"]["hit_rate"] >= 0.8
    assert ttft_on <= 0.75 * ttft_off, (ttft_off, ttft_on)


# ------------------------------------------------------- component units

def test_bucket_lattice_rounding():
    lat = BucketLattice(batch_buckets=(1, 2, 4), seq_buckets=(8, 32))
    assert lat.batch(1) == 1 and lat.batch(3) == 4
    assert lat.seq(5) == 8 and lat.seq(9) == 32
    with pytest.raises(mx.MXNetError):
        lat.seq(33)
    assert len(lat) == 6
    assert len(lat.prefill_points()) == 6


def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in (1, 2, 3, 4, 100):
        h.observe(ms / 1e3)
    s = h.summary()
    assert s["count"] == 5
    assert 0.5 < s["p50_ms"] < 5
    assert s["p99_ms"] <= s["max_ms"] * 1.001
    assert h.percentile(0) <= h.percentile(99.9)
