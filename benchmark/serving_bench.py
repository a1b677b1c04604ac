"""Online-serving benchmark: concurrent dynamic-batched decode through
``mxnet_tpu.serving.InferenceEngine`` vs sequential per-request
``net.generate()`` on the same host.

Prints bench.py-schema JSON lines (metric/value/unit/vs_baseline/
platform/trials/spread_pct), one for the sequential baseline and one for
the engine:

- ``serving_sequential_decode``: tokens/sec decoding N requests one at a
  time with the fused-loop ``generate`` (``vs_baseline: null`` — it IS
  the baseline);
- ``serving_engine_decode_c<N>``: tokens/sec with all N requests in
  flight through the engine (continuous batching + shape buckets).
  ``vs_baseline`` is the speedup over the sequential line measured in
  the SAME process — meaningful on any platform, unlike the MFU-derived
  ratios in bench.py.  The record also carries the engine's p50/p95
  total-latency milliseconds.

``--workload prefix`` instead runs the repeated-system-prompt workload
(docs/serving.md): every request shares a long common prefix and
carries a short unique tail — the shape of few-shot/system-prompt
traffic.  It emits ``serving_prefix_ttft_cache_off`` (the baseline:
full prefill per request) and ``serving_prefix_ttft_cache_on`` (prefix
cache enabled; ``vs_baseline`` is the median-TTFT speedup, and the
record carries the measured hit rate, tokens saved, and the TTFT
reduction percentage).

``--workload fleet`` runs the 1-vs-3-replica comparison (docs/fleet.md):
G prompt families (distinct long system prompts, short unique tails)
interleaved through a single engine, a 3-replica fleet with seeded
RANDOM routing (the control: every replica ends up paying every
family's prefill), and a 3-replica fleet with prefix-AFFINITY routing
(each family rendezvous-hashes onto one replica).  It emits
``serving_fleet_ttft_single`` (the baseline),
``serving_fleet_ttft_random_r3`` and ``serving_fleet_ttft_affinity_r3``
(``vs_baseline`` is the mean-TTFT speedup over the RANDOM fleet — the
number affinity routing exists to win), with fleet/per-replica prefix
hit rates and the fleet-aggregated ``mxtpu_fleet_*`` registry snapshot
embedded in the affinity record.

``--workload overload`` runs the mixed-priority sustained-overload
comparison (docs/overload.md): the same ~3x-capacity storm of
``interactive``/``batch``/``best_effort`` requests with per-class
deadlines is pushed through a BLIND engine (no priorities, no deadline
admission, no brownout, no preemption — the bounded queue sheds
whatever arrives when full) and through the overload-controlled
engine.  It emits ``serving_overload_interactive_hit_blind`` (the
baseline) and ``serving_overload_interactive_hit_controlled``
(``vs_baseline`` is the interactive deadline-hit-rate ratio — the
number overload control exists to win; ``best_effort`` absorbing the
damage is the design, not a regression), where goodput counts ONLY
tokens of requests that completed within their deadline; each record
carries per-class goodput and deadline-hit-rate, and the controlled
record adds the shed breakdown by reason/class, preemption and
brownout counts.

``--workload paged`` runs the paged-vs-dense KV comparison
(docs/serving.md "Paged KV"): the same mixed short/long-prompt burst is
pushed through a DENSE engine and through a PAGED engine provisioned
with exactly the same KV positions (``num_pages * page_size ==
dense_slots * Tmax``) but many more slots — the dense engine's
concurrency is capped by worst-case rows, the paged engine's by live
tokens.  It emits ``serving_paged_dense`` (the baseline) and
``serving_paged`` (``vs_baseline`` is the tokens/s speedup; the record
carries ``max_concurrent`` per arm and ``concurrency_ratio`` — the
headline: max sustainable concurrency at fixed KV memory, the number
paging exists to win — plus page-pool occupancy/fault/sharing stats).
Greedy outputs are asserted token-identical between the arms.

``--workload quantized`` runs the four-arm quantized-KV comparison
(docs/serving.md "Quantized KV + paged attention kernel") at a FIXED
KV byte budget: ``dense_fp32`` (the reference arm and baseline),
``paged_gather_fp32`` (PR 11's dense-row gather), ``paged_kernel_fp32``
(the Pallas in-place page reader — same dtype as gather, so
``kernel_vs_gather_x`` is a pure read-arm cost ratio), and
``paged_kernel_int8`` (int8 pages + fp32 scale sidecars, provisioned
with as many MORE pages as the byte budget buys).  The divergence
contract is enforced every trial: both fp32 paged arms are asserted
token-identical to dense, the int8 arm is asserted exact through the
match horizon AND runs under ``debug_parity`` with its max-abs logit
delta bounded.  The headline is ``concurrency_per_mb`` — max
sustained concurrency per KV megabyte, the number quantization exists
to win (``vs_baseline`` on the int8 record is its ratio over
``paged_kernel_fp32``).

``--workload speculative`` runs the speculative-vs-plain decode
comparison (docs/serving.md "Speculative decode"): the same mixed
greedy/sampled concurrent burst at IDENTICAL per-request sampling
params through a plain engine and through one with ``spec_tokens=k``
(early-exit drafter + one batched verify forward per cycle).  Output
streams are asserted identical between the arms every trial —
speculation's contract is same tokens, fewer weight-streaming passes —
and it emits ``serving_speculative_plain`` (baseline) and
``serving_speculative`` (``vs_baseline`` is the tokens/s speedup; the
record carries the measured acceptance rate, the spec counters, and
the live registry snapshot).

``--workload sharded`` runs the 1-device vs N-virtual-device GSPMD
comparison (docs/serving.md "Sharded decode"): the same concurrent
greedy+sampled burst through an unsharded engine and through one with
``mesh=N`` (tensor-parallel over
``--xla_force_host_platform_device_count`` CPU devices).  Output
streams are asserted token-identical between the arms EVERY trial —
sharding's contract is bytes moved, math unchanged — and the compile
counter is asserted frozen per (bucket, mesh) point.  It emits
``serving_sharded_1dev`` (baseline) and ``serving_sharded_mesh<N>``
(``vs_baseline`` is the tokens/s ratio; on CPU the N "devices" share
the same cores, so the ratio measures GSPMD partition overhead — the
CPU run exists to pin parity and the freeze, the TPU run reuses it
unchanged for real speedups; the record carries the mesh stats section
and the live registry snapshot).

``--workload disagg`` runs the disaggregated 1-prefill+1-decode pair
against a colocated engine (docs/serving.md "Disaggregated serving")
on the interference workload disaggregation exists for: a chatty
decode background (short prompts, long generations) with long-prefill
TTFT probes interleaved.  Each probe generates exactly ONE token, so
its wall time IS its TTFT — in the colocated arm long prefills share
the scheduler with the decode batch; in the disagg arm the prefill
engine is dedicated and hands the KV pages to the decode engine at
the first token.  Every output (probes and background) is asserted
token-identical between the arms per trial.  It emits
``serving_disagg_colocated_ttft`` (baseline) and
``serving_disagg_1p1d_ttft`` (``vs_baseline`` is the TTFT ratio
colocated/disagg, > 1 means disagg answered faster; on CPU both
engines share the same cores, so the ratio measures the handoff
overhead — host-numpy export, digest, adopt — that a real deployment
pays for its interference win; the CPU run exists to pin parity and
the freeze, the TPU run reuses it unchanged.  The record carries
decode tokens/s, migration counters + latency, and the live registry
snapshot).

``--workload tiered`` runs the warm-family TTFT comparison for the
tiered prefix cache (docs/serving.md "Tiered prefix cache"): a
working set of shared-prefix families ~8-10x the device page pool,
revisited with fresh tails after the pool thrashed them out.  Three
arms — ``hbm`` (pool big enough to hold everything: the floor),
``tiered`` (starved pool + host tier: revisits promote, with
verify-on-promote inside the measured time), ``recompute`` (starved
pool, tier off: revisits pay the shared-prefix prefill again).
Greedy outputs are asserted token-identical across all three arms
every trial, and each arm's compile counter is asserted frozen
post-warmup.  It emits ``serving_tiered_ttft_{hbm,recompute,tiered}``
(``vs_baseline`` on the tiered record is hbm/tiered; the record also
carries ``vs_hbm_x`` / ``vs_recompute_x`` and the tier counters).

Both paths pay their compiles during warmup (generate's jit cache /
``engine.warmup()``), then run >= 3 timed trials; the reported value is
the median (bench.py trial hygiene).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        name = "gpt2_124m"
        prompt_lens = (64, 96, 128, 192)
        seq_buckets = (64, 128, 256)
        max_new = 64
    else:   # CPU sanity: reduced model, same code path.  Large enough
        # that a decode step is compute- (not dispatch-) bound, else the
        # measured ratio reflects Python overhead, not batching
        name = "gpt2_124m"
        cfg = dict(vocab_size=2048, units=256, num_layers=4, num_heads=8,
                   max_length=256, dropout=0.0)
        prompt_lens = (8, 12, 16, 24)
        seq_buckets = (8, 16, 32)
        max_new = 32
    net = get_gpt2(name, **cfg)
    net.initialize()
    return net, prompt_lens, seq_buckets, max_new


def _prompts(concurrency, prompt_lens, vocab):
    import numpy as onp
    rs = onp.random.RandomState(0)
    return [rs.randint(0, vocab, (prompt_lens[i % len(prompt_lens)],))
            .astype("int32") for i in range(concurrency)]


def _record(metric, vals, unit, vs_baseline, extra=None):
    import jax
    d = jax.devices()[0]
    value = statistics.median(vals)
    rec = {"metric": metric, "value": round(value, 1), "unit": unit,
           "vs_baseline": vs_baseline, "platform": d.platform,
           "device_kind": d.device_kind, "device_count": len(jax.devices()),
           "trials": [round(v, 1) for v in vals],
           "spread_pct": round(100.0 * (max(vals) - min(vals)) / value, 2)
           if value else None}
    if extra:
        rec.update(extra)
    return rec


def bench_serving_decode(concurrency: int = 16, max_new: int = None,
                         trials: int = 3):
    import mxnet_tpu as mx
    from mxnet_tpu.serving import InferenceEngine

    import jax
    on_tpu = jax.default_backend() == "tpu"
    net, prompt_lens, seq_buckets, default_new = _build_net(on_tpu)
    max_new = max_new or default_new
    prompts = _prompts(concurrency, prompt_lens, net.vocab_size)
    total_tokens = concurrency * max_new

    # ---- sequential baseline: per-request fused generate ----------------
    def seq_pass():
        for p in prompts:
            net.generate(mx.nd.array(p[None], dtype="int32"), max_new,
                         temperature=0).asnumpy()
    seq_pass()                                   # warmup: pays the compiles
    seq_vals = []
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        seq_pass()
        seq_vals.append(total_tokens / (time.perf_counter() - t0))

    # ---- engine: all requests in flight ---------------------------------
    eng = InferenceEngine(net, num_slots=concurrency,
                          max_batch=concurrency, seq_buckets=seq_buckets,
                          queue_depth=4 * concurrency,
                          default_max_new_tokens=max_new,
                          name=f"serving_bench_c{concurrency}")
    eng.warmup()
    eng_vals = []
    with eng:
        for _ in range(max(1, trials)):
            t0 = time.perf_counter()
            futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
            for f in futs:
                f.result(timeout=1800)
            eng_vals.append(total_tokens / (time.perf_counter() - t0))
        lat = eng.stats()["latency"]["total"]

    speedup = round(statistics.median(eng_vals) /
                    statistics.median(seq_vals), 4)
    yield _record("serving_sequential_decode", seq_vals, "tokens/sec",
                  None, {"concurrency": 1, "max_new_tokens": max_new})
    yield _record(f"serving_engine_decode_c{concurrency}", eng_vals,
                  "tokens/sec", speedup,
                  {"concurrency": concurrency, "max_new_tokens": max_new,
                   "p50_ms": lat["p50_ms"], "p95_ms": lat["p95_ms"]})


def _build_prefix_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        name = "gpt2_124m"
        shared_len, tail_len = 1024, 64
        seq_buckets = (64, 128, 256, 512, 1024, 2048)
    else:   # CPU sanity: the prefill must be COMPUTE-bound, not
        # dispatch-bound, or the row copy the cache adds costs more than
        # the prefill it removes and the measured ratio is meaningless
        name = "gpt2_124m"
        cfg = dict(vocab_size=512, units=256, num_layers=4, num_heads=8,
                   max_length=144, dropout=0.0)
        shared_len, tail_len = 120, 8
        seq_buckets = (16, 32, 64, 128)
    net = get_gpt2(name, **cfg)
    net.initialize()
    return net, shared_len, tail_len, seq_buckets


def bench_prefix_cache(n_requests: int = 12, max_new: int = 2,
                       trials: int = 3):
    """Repeated-system-prompt workload: TTFT with the prefix cache on vs
    off.  Requests run serially (TTFT isolation — concurrency would
    hide prefill behind decode of other requests); a fresh engine per
    trial keeps trials independent; warmup pays all compiles before any
    timed request."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    net, shared_len, tail_len, seq_buckets = _build_prefix_net(on_tpu)
    rs = onp.random.RandomState(7)
    shared = rs.randint(0, net.vocab_size, (shared_len,)).astype("int32")
    prompts = [onp.concatenate(
        [shared, rs.randint(0, net.vocab_size, (tail_len,))
         .astype("int32")]) for _ in range(n_requests)]

    def one_trial(pool_rows):
        eng = InferenceEngine(
            net, num_slots=2, max_batch=2, seq_buckets=seq_buckets,
            default_max_new_tokens=max_new, prefix_pool_rows=pool_rows,
            prefix_min_tokens=8, name="serving_prefix_bench")
        eng.warmup()
        with eng:
            for p in prompts:
                eng.infer(p, max_new_tokens=max_new)
        return eng.stats()

    off_vals, on_vals, last_on = [], [], None
    for _ in range(max(1, trials)):
        off_vals.append(one_trial(0)["ttft"]["p50_ms"])
        last_on = one_trial(2)
        on_vals.append(last_on["ttft"]["p50_ms"])
    pc = last_on["prefix_cache"]
    speedup = round(statistics.median(off_vals) /
                    statistics.median(on_vals), 4)
    reduction = round(100.0 * (1.0 - statistics.median(on_vals) /
                               statistics.median(off_vals)), 1)
    yield _record("serving_prefix_ttft_cache_off", off_vals, "ms", None,
                  {"n_requests": n_requests, "shared_prefix": shared_len,
                   "tail": tail_len})
    yield _record("serving_prefix_ttft_cache_on", on_vals, "ms", speedup,
                  {"n_requests": n_requests, "shared_prefix": shared_len,
                   "tail": tail_len,
                   "ttft_reduction_pct": reduction,
                   "prefix_hit_rate": pc["hit_rate"],
                   "prefix_tokens_saved": pc["prefix_tokens_saved"]})


def bench_fleet(n_replicas: int = 3, groups: int = 3, per_group: int = 16,
                max_new: int = 2, trials: int = 3):
    """1-vs-3-replica repeated-system-prompt workload.  Requests run
    serially (TTFT isolation); a fresh fleet per trial keeps trials
    independent; warmup pays every replica's compiles before any timed
    request.  The per-trial statistic is the request-weighted MEAN TTFT
    across replicas — the mean (unlike the median) moves with every
    extra prefix miss a bad placement causes."""
    import jax
    import numpy as onp

    from mxnet_tpu.fleet import FleetRouter
    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    net, shared_len, tail_len, seq_buckets = _build_prefix_net(on_tpu)
    if not on_tpu:
        # two lattice points suffice (suffix chunk + full prefill) and
        # keep per-arm warmup short, so the three routing arms of one
        # trial run close together in time — paired against the same
        # slice of host noise
        seq_buckets = (32, 128)
    rs = onp.random.RandomState(7)
    families = []
    for _g in range(groups):
        shared = rs.randint(0, net.vocab_size,
                            (shared_len,)).astype("int32")
        families.append([onp.concatenate(
            [shared, rs.randint(0, net.vocab_size,
                                (tail_len,)).astype("int32")])
            for _ in range(per_group)])
    # interleave the families: the worst case for any router that keys
    # on arrival order instead of content
    stream = [p for batch in zip(*families) for p in batch]

    def factory_for(fleet_name):
        def factory(name):
            return InferenceEngine(
                net, num_slots=1, max_batch=1, seq_buckets=seq_buckets,
                default_max_new_tokens=max_new, prefix_pool_rows=groups + 1,
                prefix_min_tokens=8, name=name)
        return factory

    def one_trial(n, routing, tag):
        import gc

        from mxnet_tpu.observability import flatten
        fleet = FleetRouter(factory=factory_for(tag), num_replicas=n,
                            routing=routing, name=tag, seed=0)
        fleet.warmup()
        # the timed window is short (serial TTFT isolation): a GC pause
        # from the engines just built must not land inside it
        gc.collect()
        with fleet:
            for p in stream:
                fleet.infer(p, max_new_tokens=max_new)
            s = fleet.stats()
            # snapshot the fleet-aggregated registry series while this
            # fleet is alive and healthy (it is a weakref-bound
            # collector, and its replica-up gauges zero out at stop)
            s["registry"] = flatten(prefix="mxtpu_fleet")
        total = sum(rep["stats"]["ttft"]["count"]
                    for rep in s["replicas"].values())
        mean_ms = sum(rep["stats"]["ttft"]["mean_ms"] *
                      rep["stats"]["ttft"]["count"]
                      for rep in s["replicas"].values()) / total
        return mean_ms, s

    single_vals, random_vals, affinity_vals = [], [], []
    last_aff = None
    for t in range(max(1, trials)):
        single_vals.append(one_trial(1, "affinity", f"fleet1_t{t}")[0])
        random_vals.append(one_trial(n_replicas, "random",
                                     f"fleetR_t{t}")[0])
        mean_ms, last_aff = one_trial(n_replicas, "affinity",
                                      f"fleetA_t{t}")
        affinity_vals.append(mean_ms)

    agg = last_aff["aggregate"]
    per_replica_hits = {
        name: rep["stats"]["prefix_cache"]["hit_rate"]
        for name, rep in last_aff["replicas"].items()}
    speed_vs_random = round(statistics.median(random_vals) /
                            statistics.median(affinity_vals), 4)
    speed_vs_single = round(statistics.median(single_vals) /
                            statistics.median(affinity_vals), 4)
    n_req = groups * per_group
    base = {"n_replicas": n_replicas, "groups": groups,
            "n_requests": n_req, "shared_prefix": shared_len,
            "tail": tail_len}
    yield _record("serving_fleet_ttft_single", single_vals, "ms", None,
                  dict(base, n_replicas=1))
    yield _record("serving_fleet_ttft_random_r3", random_vals, "ms",
                  round(statistics.median(single_vals) /
                        statistics.median(random_vals), 4), base)
    yield _record(
        "serving_fleet_ttft_affinity_r3", affinity_vals, "ms",
        speed_vs_random,
        dict(base, vs_single=speed_vs_single,
             fleet_prefix_hit_rate=agg["prefix_hit_rate"],
             per_replica_hit_rate=per_replica_hits,
             prefix_tokens_saved=agg["prefix_tokens_saved"],
             affinity_routed=last_aff["router"].get("affinity_routed", 0),
             fleet_registry=last_aff["registry"]))


def bench_elastic(trials: int = 3, max_replicas: int = 3):
    """Elastic-fleet flash-spike workload (docs/fleet.md "Elastic
    fleet"): replay the SAME deterministic loadgen flash-spike trace
    (10x arrival-rate step) against three arms — autoscaler-on
    (start 1, grow to ``max_replicas``), fixed-1, and
    fixed-``max_replicas`` — and compare completed throughput and
    interactive outcomes.  A uniform decode-step delay is injected
    identically into every arm: the tiny CPU sanity model would
    otherwise out-serve any spike, and the delay stands in for a model
    whose decode step is nontrivial (the regime elasticity exists
    for).  The headline: the autoscaler arm should approach
    fixed-``max_replicas`` throughput through the spike while spending
    fixed-1-like capacity outside it — replica-seconds is the cost
    column."""
    import jax
    import numpy as onp

    from mxnet_tpu.fleet import FleetAutoscaler, FleetRouter
    from mxnet_tpu.resilience import FaultPlan
    from mxnet_tpu.serving import InferenceEngine

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import loadgen

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        net, shared_len, tail_len, seq_buckets = _build_prefix_net(True)
    else:
        # CPU sanity wants the SMALLEST net that still serves: a
        # newcomer's warmup (factory build + compiles) must land inside
        # the replay window or the auto arm can never express added
        # capacity — the injected decode delay supplies the load, not
        # model size
        from mxnet_tpu.models import get_gpt2
        net = get_gpt2("gpt2_124m", vocab_size=61, units=16,
                       num_layers=1, num_heads=2, max_length=32,
                       dropout=0.0)
        net.initialize()
        shared_len, tail_len, seq_buckets = 10, 3, (16,)
    trace = loadgen.flash_spike(
        duration=6.0, base_rps=8.0, spike_factor=10.0,
        spike_start=0.25, spike_len=0.3, seed=11, families=3,
        shared_len=shared_len, tail_len=tail_len,
        vocab=net.vocab_size, max_new_tokens=2, interactive_frac=0.5)

    def factory_for(tag):
        def factory(name):
            return InferenceEngine(
                net, num_slots=2, max_batch=2, seq_buckets=seq_buckets,
                default_max_new_tokens=2, prefix_pool_rows=4,
                prefix_min_tokens=8, queue_depth=256, name=name)
        return factory

    def one_trial(tag, n_start, scaler_on):
        import gc

        from mxnet_tpu.observability import flatten
        fleet = FleetRouter(factory=factory_for(tag), num_replicas=n_start,
                            name=tag, health_interval=0.05,
                            breaker_threshold=100)
        fleet.warmup()
        gc.collect()
        scaler = FleetAutoscaler(
            fleet, min_replicas=1, max_replicas=max_replicas,
            interval=0.03, queue_high=3, queue_low=1, util_low=0.9,
            up_cycles=2, down_cycles=20, up_cooldown=0.4,
            down_cooldown=0.4) if scaler_on else None
        # replica-seconds: integrate fleet size over the replay — the
        # capacity bill each arm pays for its throughput
        sizes = []

        def on_tick(_t):
            sizes.append(len(fleet._healthy()))
        plan = FaultPlan().delay_at("serving.decode_step", 0.02, every=1)
        with fleet:
            if scaler is not None:
                scaler.start()
            try:
                with plan:
                    rep = loadgen.replay(trace, fleet, timeout=120.0,
                                         on_tick=on_tick)
            finally:
                if scaler is not None:
                    scaler.stop()
            s = fleet.stats()
            s["registry"] = flatten(prefix="mxtpu_fleet")
        wall = rep["wall_seconds"]
        mean_replicas = (sum(sizes) / len(sizes)) if sizes else n_start
        rep["replica_seconds"] = round(mean_replicas * wall, 2)
        rep["mean_replicas"] = round(mean_replicas, 3)
        rep["scale_ups"] = s["router"].get("scale_ups", 0)
        rep["scale_downs"] = s["router"].get("scale_downs", 0)
        if scaler is not None:
            rep["autoscaler"] = scaler.stats()
        rep["stats"] = s
        return rep["throughput_rps"], rep

    arms = {"auto": [], "fixed1": [], "fixedN": []}
    last = {}
    for t in range(max(1, trials)):
        for tag, n0, on in (("auto", 1, True), ("fixed1", 1, False),
                            ("fixedN", max_replicas, False)):
            rps, rep = one_trial(f"elastic_{tag}_t{t}", n0, on)
            arms[tag].append(rps)
            last[tag] = rep

    med = {k: statistics.median(v) for k, v in arms.items()}
    base = {"trace_events": len(trace), "max_replicas": max_replicas,
            "spike_factor": 10.0}
    yield _record("serving_elastic_rps_fixed1", arms["fixed1"], "req/s",
                  None, dict(base,
                             interactive=last["fixed1"]["by_priority"]
                             .get("interactive", {}),
                             replica_seconds=last["fixed1"]
                             ["replica_seconds"]))
    yield _record("serving_elastic_rps_fixedN", arms["fixedN"], "req/s",
                  round(med["fixedN"] / med["fixed1"], 4)
                  if med["fixed1"] else None,
                  dict(base,
                       interactive=last["fixedN"]["by_priority"]
                       .get("interactive", {}),
                       replica_seconds=last["fixedN"]["replica_seconds"]))
    yield _record(
        "serving_elastic_rps_autoscaler", arms["auto"], "req/s",
        round(med["auto"] / med["fixed1"], 4) if med["fixed1"] else None,
        dict(base,
             vs_fixedN=round(med["auto"] / med["fixedN"], 4)
             if med["fixedN"] else None,
             interactive=last["auto"]["by_priority"].get(
                 "interactive", {}),
             lost=last["auto"]["lost"],
             replica_seconds=last["auto"]["replica_seconds"],
             mean_replicas=last["auto"]["mean_replicas"],
             scale_ups=last["auto"]["scale_ups"],
             scale_downs=last["auto"]["scale_downs"],
             autoscaler=last["auto"].get("autoscaler"),
             fleet_registry=last["auto"]["stats"]["registry"]))


def _build_overload_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        seq_buckets = (64, 128, 256)
        prompt_lens = (64, 96, 128)
    else:   # CPU sanity: the comparison is about SCHEDULING policy
        # (which requests complete inside their deadline), not raw
        # compute, so a small model keeps the storm short while the
        # queue dynamics stay identical
        cfg = dict(vocab_size=256, units=64, num_layers=2, num_heads=4,
                   max_length=64, dropout=0.0)
        seq_buckets = (8, 16)
        prompt_lens = (5, 6, 7)
    net = get_gpt2("gpt2_124m", **cfg)
    net.initialize()
    return net, prompt_lens, seq_buckets


def bench_overload(n_waves: int = 20, trials: int = 3):
    """Mixed-priority sustained overload, controlled vs blind shedding.

    A calibration pass measures the engine's service rate T (req/s at
    full concurrency), then each trial drives one fresh engine with
    ``n_waves`` waves of three requests (one per class, tight/medium/
    loose deadlines expressed in units of 1/T) arriving every 1/T
    seconds — a sustained 3x-capacity storm, identical for both arms.
    A request scores iff its future RESOLVED within its deadline — the
    engine stamps ``InferenceFuture.t_done`` at resolution, so requests
    that completed mid-storm are scored at their true completion
    instant, not when the collection loop reaches them; goodput is
    scored generated tokens / storm wall time."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    net, prompt_lens, seq_buckets = _build_overload_net(on_tpu)
    rs = onp.random.RandomState(5)

    def mk():
        ln = prompt_lens[rs.randint(len(prompt_lens))]
        return rs.randint(0, net.vocab_size, (ln,)).astype("int32")

    def build(controlled, tag, queue_depth=6):
        return InferenceEngine(
            net, num_slots=2, max_batch=2, seq_buckets=seq_buckets,
            queue_depth=queue_depth, default_max_new_tokens=6,
            prefix_pool_rows=4 if controlled else 0, prefix_min_tokens=4,
            preemption=controlled, deadline_admission=controlled,
            brownout=controlled, name=tag)

    # ---- calibration: service rate with every control off (deep queue
    # so the whole calibration batch is admitted at once) ---------------
    cal = build(False, "serving_overload_cal", queue_depth=32)
    cal.warmup()
    with cal:
        futs = [cal.submit(mk(), max_new_tokens=6) for _ in range(12)]
        t0 = time.perf_counter()
        for f in futs:
            f.result(timeout=600)
        rate = 12 / (time.perf_counter() - t0)
    period = 1.0 / rate                      # one wave per service slot
    # (class, tokens, deadline in service periods): interactive must
    # finish inside the backlog a blind FIFO accumulates by mid-storm
    wave = (("best_effort", 6, 20.0), ("batch", 6, 10.0),
            ("interactive", 2, 4.0))

    def one_trial(controlled, tag):
        eng = build(controlled, tag)
        eng.warmup()
        done = []                            # (cls, tokens, ok)
        with eng:
            for _ in range(8):               # pre-storm steady state:
                eng.infer(mk(), max_new_tokens=6)   # latency history
            t_start = time.monotonic()
            pending = []
            for w in range(n_waves):
                for cls, toks, dl in wave:
                    timeout = dl * period
                    p = mk()
                    t_sub = time.monotonic()
                    try:
                        f = eng.submit(
                            p, max_new_tokens=toks, timeout=timeout,
                            priority=cls if controlled else None)
                        pending.append((cls, len(p), f, t_sub, timeout))
                    except Exception:
                        done.append((cls, 0, False))     # shed = miss
                wait = t_start + (w + 1) * period - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            for cls, plen, f, t_sub, timeout in pending:
                try:
                    out = f.result(timeout=600)
                    ok = f.t_done - t_sub <= timeout
                    done.append((cls, max(0, len(out) - plen), ok))
                except Exception:
                    done.append((cls, 0, False))
            wall = time.monotonic() - t_start
            s = eng.stats()
        per_class = {}
        for cls, _toks, _dl in wave:
            rows = [d for d in done if d[0] == cls]
            served_tokens = sum(t for _c, t, ok in rows if ok)
            per_class[cls] = {
                "goodput_tokens_per_s": round(served_tokens / wall, 2),
                "deadline_hit_rate": round(
                    sum(1 for _c, _t, ok in rows if ok) / len(rows), 4)}
        goodput = sum(t for _c, t, ok in done if ok) / wall
        return goodput, per_class, s

    def _sum_counts(acc, cur):
        """Sum one trial's (possibly nested) overload counters into the
        all-trials totals — the hit-rate medians upstream span every
        trial, so the shed/served breakdown in the same record must
        too, not describe whichever trial happened to run last."""
        out = dict(acc or {})
        for k, v in cur.items():
            if k == "controller":
                continue            # live state, not a counter
            if isinstance(v, dict):
                out[k] = _sum_counts(out.get(k), v)
            else:
                out[k] = out.get(k, 0) + v
        return out

    def run_arm(controlled, tag):
        goodputs, trials_pc, stats = [], [], None
        for t in range(max(1, trials)):
            g, pc, s = one_trial(controlled, f"{tag}_t{t}")
            goodputs.append(g)
            trials_pc.append(pc)
            stats = dict(s, overload=_sum_counts(
                (stats or {}).get("overload"), s["overload"]))
        per_class = {
            cls: {k: round(statistics.median(
                pc[cls][k] for pc in trials_pc), 4)
                for k in ("goodput_tokens_per_s", "deadline_hit_rate")}
            for cls, _t, _d in wave}
        ia_hits = [100.0 * pc["interactive"]["deadline_hit_rate"]
                   for pc in trials_pc]
        return ia_hits, per_class, goodputs, stats

    blind_hits, blind_pc, blind_gp, _ = run_arm(
        False, "serving_overload_blind")
    ctrl_hits, ctrl_pc, ctrl_gp, ctrl_stats = run_arm(
        True, "serving_overload_ctrl")

    base = {"n_waves": n_waves, "overload_factor": 3,
            "service_rate_req_per_s": round(rate, 2),
            "deadlines_in_service_periods": {
                cls: dl for cls, _t, dl in wave}}
    blind_med = statistics.median(blind_hits)
    ratio = round(statistics.median(ctrl_hits) / blind_med, 4) \
        if blind_med else None      # blind served zero interactive
    ov = ctrl_stats["overload"]
    yield _record(
        "serving_overload_interactive_hit_blind", blind_hits,
        "% deadlines met", None,
        dict(base, per_class=blind_pc,
             goodput_total_tokens_per_s=round(
                 statistics.median(blind_gp), 1)))
    yield _record(
        "serving_overload_interactive_hit_controlled", ctrl_hits,
        "% deadlines met", ratio,
        dict(base, per_class=ctrl_pc,
             goodput_total_tokens_per_s=round(
                 statistics.median(ctrl_gp), 1),
             sheds=ov["sheds"], served=ov["served"],
             rejected_infeasible=ov["rejected_infeasible"],
             preemptions=ov["preemptions"],
             preempt_resumes=ov["preempt_resumes"],
             brownouts=ov["brownouts"]))


def _build_paged_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        name = "gpt2_124m"
        short_lens, long_lens = (64, 96, 128), (1024, 1536)
        seq_buckets = (64, 128, 256, 512, 1024, 2048)
        page_size, max_new, dense_slots = 128, 64, 4
    else:   # CPU sanity: the comparison is about CAPACITY (how many
        # requests fit a fixed KV budget), not raw compute — a small
        # model keeps the burst short while the page accounting is
        # identical to the TPU shape
        name = "gpt2_124m"
        cfg = dict(vocab_size=512, units=128, num_layers=2, num_heads=4,
                   max_length=64, dropout=0.0)
        short_lens, long_lens = (8, 10, 12), (40, 48)
        seq_buckets = (8, 16)
        page_size, max_new, dense_slots = 8, 8, 2
    net = get_gpt2(name, **cfg)
    net.initialize()
    return (net, short_lens, long_lens, seq_buckets, page_size, max_new,
            dense_slots)


def bench_paged(n_requests: int = 16, trials: int = 3):
    """Paged vs dense at FIXED KV memory: a mixed short/long burst.

    Both arms hold exactly ``dense_slots * Tmax`` KV positions; the
    dense arm can run ``dense_slots`` requests at once no matter how
    short they are, the paged arm runs as many as their LIVE tokens
    fit.  Per trial (fresh engines — concurrency highwater and page
    counters are per-engine-lifetime): submit the whole burst, wait it
    out, score tokens/s and ``active_highwater``.  Outputs are asserted
    token-identical between the arms (greedy parity is a correctness
    gate of this bench, not just a test)."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    (net, short_lens, long_lens, seq_buckets, page_size, max_new,
     dense_slots) = _build_paged_net(on_tpu)
    rs = onp.random.RandomState(11)
    # ~1 in 4 requests is LONG — the worst case the dense layout
    # provisions every slot for
    lens = [long_lens[i % len(long_lens)] if i % 4 == 3
            else short_lens[i % len(short_lens)]
            for i in range(n_requests)]
    prompts = [rs.randint(0, net.vocab_size, (l,)).astype("int32")
               for l in lens]
    tmax = net.max_length
    n_logical = tmax // page_size
    kv_positions = dense_slots * tmax          # the fixed memory budget
    num_pages = dense_slots * n_logical        # same bytes, paged
    # the paged arm may lease as many slots as pages could ever cover
    # at the SHORTEST live footprint; bounded for sane bucket lattices
    paged_slots = min(n_requests, max(
        dense_slots + 1,
        num_pages // max(1, (min(short_lens) + max_new + page_size - 1)
                         // page_size)))

    def one_trial(layout):
        from mxnet_tpu.observability import flatten
        kw = dict(num_slots=dense_slots, prefix_pool_rows=0)
        if layout == "paged":
            kw = dict(num_slots=paged_slots, kv_layout="paged",
                      page_size=page_size, num_pages=num_pages)
        eng = InferenceEngine(
            net, max_batch=kw["num_slots"], seq_buckets=seq_buckets,
            queue_depth=4 * n_requests, default_max_new_tokens=max_new,
            name=f"serving_paged_{layout}", **kw)
        eng.warmup()
        with eng:
            t0 = time.perf_counter()
            futs = [eng.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            outs = [f.result(timeout=1800) for f in futs]
            dt = time.perf_counter() - t0
            s = eng.stats()
            # snapshot the registry while THIS engine is alive (it is
            # a weakref-bound collector: a dead engine prunes itself
            # from the scrape, so main()'s final snapshot would be
            # empty)
            s["registry"] = flatten(prefix="mxtpu_serving")
        toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return toks / dt, s, outs

    dense_vals, paged_vals = [], []
    dense_cc, paged_cc = [], []
    last_dense = last_paged = None
    for _ in range(max(1, trials)):
        tps, s, outs_d = one_trial("dense")
        dense_vals.append(tps)
        dense_cc.append(s["slots"]["active_highwater"])
        last_dense = s
        tps, s, outs_p = one_trial("paged")
        paged_vals.append(tps)
        paged_cc.append(s["slots"]["active_highwater"])
        last_paged = s
        for d, p in zip(outs_d, outs_p):      # correctness gate
            if not onp.array_equal(d, p):
                raise AssertionError(
                    "paged/dense greedy outputs diverged — the bench "
                    "numbers would be comparing different work")
    speedup = round(statistics.median(paged_vals) /
                    statistics.median(dense_vals), 4)
    cc_dense = statistics.median(dense_cc)
    cc_paged = statistics.median(paged_cc)
    base = {"n_requests": n_requests, "max_new_tokens": max_new,
            "prompt_lens": lens, "kv_positions": kv_positions,
            "page_size": page_size}
    yield _record(
        "serving_paged_dense", dense_vals, "tokens/sec", None,
        dict(base, num_slots=dense_slots, max_concurrent=cc_dense,
             concurrency_per_1k_kv=round(1000.0 * cc_dense /
                                         kv_positions, 3),
             slots=last_dense["slots"],
             registry_live=last_dense["registry"]))
    yield _record(
        "serving_paged", paged_vals, "tokens/sec", speedup,
        dict(base, num_slots=paged_slots, num_pages=num_pages,
             max_concurrent=cc_paged,
             concurrency_per_1k_kv=round(1000.0 * cc_paged /
                                         kv_positions, 3),
             concurrency_ratio=round(cc_paged / cc_dense, 4),
             slots=last_paged["slots"],
             registry_live=last_paged["registry"]))


def bench_quantized(n_requests: int = 24, trials: int = 3):
    """Quantized int8 KV vs fp32, four arms at a FIXED KV byte budget.

    The budget is the dense arm's cache footprint (``dense_slots *
    Tmax`` fp32 positions); each paged arm gets however many pages
    those BYTES buy at its storage cost — fp32 pages at ~2*L*H*D*4
    bytes/position, int8 pages at ~2*L*(H*D + 4*H) (codes + fp32 scale
    sidecars), so the int8 arm holds ~3.5x the positions and should
    sustain proportionally more concurrent requests.  Per trial (fresh
    engines — highwater is per-lifetime): submit the burst, score
    tokens/s and ``active_highwater`` per KV megabyte.  Contracts
    enforced every trial, not just in tests: fp32 gather == fp32
    kernel == dense token-for-token; int8 exact through the match
    horizon vs the fp32 kernel arm; the int8 arm runs ``debug_parity``
    and its measured max-abs logit delta stays bounded; every arm's
    compile counter is frozen after warmup."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    (net, short_lens, long_lens, seq_buckets, page_size, max_new,
     dense_slots) = _build_paged_net(on_tpu)
    rs = onp.random.RandomState(13)
    lens = [long_lens[i % len(long_lens)] if i % 4 == 3
            else short_lens[i % len(short_lens)]
            for i in range(n_requests)]
    prompts = [rs.randint(0, net.vocab_size, (l,)).astype("int32")
               for l in lens]
    tmax = net.max_length

    def bytes_per_position(kv_quant):
        # measured from a real 1-page cache (scale sidecars included),
        # not re-derived from model hyperparameters: the budget must
        # count exactly the bytes the engine will allocate
        cache = net.init_page_cache(1, page_size, kv_quant=kv_quant)
        total = sum(int(a.nbytes) // 2 for layer in cache
                    for a in layer.values())         # minus the zero page
        return total / page_size

    fp32_bpp = bytes_per_position(None)
    int8_bpp = bytes_per_position("int8")
    budget = int(dense_slots * tmax * fp32_bpp)      # the fixed budget
    pages = {"fp32": int(budget // (page_size * fp32_bpp)),
             "int8": int(budget // (page_size * int8_bpp))}
    min_fp = (min(short_lens) + max_new + page_size - 1) // page_size

    def slots_for(num_pages):
        return min(n_requests, max(dense_slots + 1,
                                   num_pages // max(1, min_fp)))

    horizon = 2                    # int8 exact-match horizon (tokens)
    parity_bound = 0.05            # max-abs logit delta vs fp32 twin

    def one_trial(arm):
        from mxnet_tpu.observability import flatten
        kw = dict(num_slots=dense_slots, prefix_pool_rows=0)
        if arm != "dense_fp32":
            quant = "int8" if arm.endswith("int8") else None
            np = pages["int8" if quant else "fp32"]
            kw = dict(num_slots=slots_for(np), kv_layout="paged",
                      page_size=page_size, num_pages=np,
                      kv_quant=quant,
                      paged_attention=("gather" if "gather" in arm
                                       else "kernel"),
                      debug_parity=bool(quant))
        eng = InferenceEngine(
            net, max_batch=kw["num_slots"], seq_buckets=seq_buckets,
            queue_depth=4 * n_requests, default_max_new_tokens=max_new,
            name=f"serving_quant_{arm}", **kw)
        n_warm = eng.warmup()
        with eng:
            t0 = time.perf_counter()
            futs = [eng.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            outs = [f.result(timeout=1800) for f in futs]
            dt = time.perf_counter() - t0
            s = eng.stats()
            s["registry"] = flatten(prefix="mxtpu_serving")
        if s["compile_cache"]["compiles"] != n_warm:
            raise AssertionError(
                f"{arm}: compiled on traffic ({s['compile_cache']} "
                f"vs {n_warm} at warmup)")
        toks = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        return toks / dt, s, outs

    arms = ("dense_fp32", "paged_gather_fp32", "paged_kernel_fp32",
            "paged_kernel_int8")
    vals = {a: [] for a in arms}
    ccs = {a: [] for a in arms}
    last = {}
    for _ in range(max(1, trials)):
        outs = {}
        for arm in arms:
            tps, s, o = one_trial(arm)
            vals[arm].append(tps)
            ccs[arm].append(s["slots"]["active_highwater"])
            last[arm] = s
            outs[arm] = o
        for arm in ("paged_gather_fp32", "paged_kernel_fp32"):
            for a, b in zip(outs["dense_fp32"], outs[arm]):
                if not onp.array_equal(a, b):
                    raise AssertionError(
                        f"{arm} diverged from dense fp32 — the bench "
                        f"would be comparing different work")
        for ref, got, p in zip(outs["paged_kernel_fp32"],
                               outs["paged_kernel_int8"], prompts):
            h = len(p) + horizon
            if not onp.array_equal(ref[:h], got[:h]):
                raise AssertionError(
                    "int8 arm broke the exact-match horizon "
                    f"({horizon} tokens)")
        err = last["paged_kernel_int8"]["quantized_kv"]["error"]
        if not (err["count"] and err["max"] <= parity_bound):
            raise AssertionError(
                f"int8 divergence contract violated: {err} "
                f"(bound {parity_bound})")

    budget_mb = budget / (1 << 20)
    med_cc = {a: statistics.median(ccs[a]) for a in arms}
    per_mb = {a: round(med_cc[a] / budget_mb, 3) for a in arms}
    base = {"n_requests": n_requests, "max_new_tokens": max_new,
            "prompt_lens": lens, "kv_budget_bytes": budget,
            "page_size": page_size, "exact_match_horizon": horizon}
    med = {a: statistics.median(vals[a]) for a in arms}
    yield _record(
        "serving_quant_dense_fp32", vals["dense_fp32"], "tokens/sec",
        None, dict(base, num_slots=dense_slots,
                   max_concurrent=med_cc["dense_fp32"],
                   concurrency_per_mb=per_mb["dense_fp32"],
                   slots=last["dense_fp32"]["slots"]))
    yield _record(
        "serving_quant_paged_gather_fp32", vals["paged_gather_fp32"],
        "tokens/sec",
        round(med["paged_gather_fp32"] / med["dense_fp32"], 4),
        dict(base, num_pages=pages["fp32"],
             num_slots=slots_for(pages["fp32"]),
             max_concurrent=med_cc["paged_gather_fp32"],
             concurrency_per_mb=per_mb["paged_gather_fp32"],
             slots=last["paged_gather_fp32"]["slots"]))
    yield _record(
        "serving_quant_paged_kernel_fp32", vals["paged_kernel_fp32"],
        "tokens/sec",
        round(med["paged_kernel_fp32"] / med["dense_fp32"], 4),
        dict(base, num_pages=pages["fp32"],
             num_slots=slots_for(pages["fp32"]),
             max_concurrent=med_cc["paged_kernel_fp32"],
             concurrency_per_mb=per_mb["paged_kernel_fp32"],
             kernel_vs_gather_x=round(med["paged_kernel_fp32"] /
                                      med["paged_gather_fp32"], 4),
             # off-TPU the kernel body runs under the Pallas
             # interpreter: the ratio prices interpret overhead, not
             # the in-place page read the kernel exists for
             read_arm="pallas" if on_tpu else "pallas_interpret",
             slots=last["paged_kernel_fp32"]["slots"]))
    qk = last["paged_kernel_int8"]["quantized_kv"]
    yield _record(
        "serving_quant_paged_kernel_int8", vals["paged_kernel_int8"],
        "tokens/sec",
        round(per_mb["paged_kernel_int8"] /
              per_mb["paged_kernel_fp32"], 4),
        dict(base, num_pages=pages["int8"],
             num_slots=slots_for(pages["int8"]),
             max_concurrent=med_cc["paged_kernel_int8"],
             concurrency_per_mb=per_mb["paged_kernel_int8"],
             concurrency_per_byte_x=round(
                 per_mb["paged_kernel_int8"] /
                 per_mb["paged_kernel_fp32"], 4),
             parity_error_max=err["max"], parity_samples=err["count"],
             quantized_kv=qk,
             registry_live=last["paged_kernel_int8"]["registry"]))


def _build_tiered_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        name = "gpt2_124m"
        shared_len, tail_len = 1024, 64
        seq_buckets = (1024, 2048)
        page_size, n_families = 128, 11
    else:   # CPU sanity: like the prefix bench, the prefill must be
        # COMPUTE-bound or the promotion copy costs more than the
        # prefill it replaces and the arm ordering is meaningless
        name = "gpt2_124m"
        cfg = dict(vocab_size=512, units=256, num_layers=4, num_heads=8,
                   max_length=272, dropout=0.0)
        shared_len, tail_len = 240, 8
        seq_buckets = (16, 32, 64, 128, 256)
        page_size, n_families = 16, 11
    net = get_gpt2(name, **cfg)
    net.initialize()
    return net, shared_len, tail_len, seq_buckets, page_size, n_families


def bench_tiered(trials: int = 3, max_new: int = 1):
    """Warm-family TTFT with a working set ~8-10x the device page
    pool, three arms (docs/serving.md "Tiered prefix cache"):

    - ``hbm``: a pool large enough that every family stays device-
      resident — the floor the tier is chasing.
    - ``tiered``: a starved pool + host tier — families demote under
      pressure and revisits promote (verify-on-promote included in the
      measured time).
    - ``recompute``: the same starved pool, tier OFF — revisits pay
      the full shared-prefix prefill again.

    Per trial (fresh engines; serial requests for TTFT isolation):
    warm every family once, then revisit each family with a NEW tail
    and time the revisit.  Greedy outputs are asserted token-identical
    across all three arms every trial — the numbers must compare the
    same work."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    (net, shared_len, tail_len, seq_buckets, page_size,
     n_families) = _build_tiered_net(on_tpu)
    rs = onp.random.RandomState(15)
    shared = [rs.randint(0, net.vocab_size, (shared_len,)).astype("int32")
              for _ in range(n_families)]
    warm_prompts = [onp.concatenate(
        [s, rs.randint(0, net.vocab_size, (tail_len,)).astype("int32")])
        for s in shared]
    revisit_prompts = [onp.concatenate(
        [s, rs.randint(0, net.vocab_size, (tail_len,)).astype("int32")])
        for s in shared]
    # worst case request = ceil((prompt + max_new) / page_size) pages;
    # the starved pool holds ONE family plus two pages of headroom, so
    # the working set is ~8-10x the pool and every warm insert evicts
    per_req = -(-(shared_len + tail_len + max_new) // page_size)
    starved_pages = per_req + 2
    hbm_pages = n_families * (per_req + 1) + 2
    working_x = round(n_families * per_req / starved_pages, 1)

    def one_trial(arm):
        kw = dict(num_pages=starved_pages)
        if arm == "hbm":
            kw = dict(num_pages=hbm_pages)
        elif arm == "tiered":
            kw["host_pool_bytes"] = 256 << 20
        eng = InferenceEngine(
            net, num_slots=1, max_batch=1, seq_buckets=seq_buckets,
            default_max_new_tokens=max_new, kv_layout="paged",
            page_size=page_size, prefix_min_tokens=8,
            name=f"serving_tiered_{arm}", **kw)
        n_warm = eng.warmup()
        with eng:
            for p in warm_prompts:
                eng.infer(p, max_new_tokens=max_new)
            lat, outs = [], []
            for p in revisit_prompts:
                t0 = time.perf_counter()
                outs.append(eng.infer(p, max_new_tokens=max_new,
                                      timeout=300))
                lat.append(1000.0 * (time.perf_counter() - t0))
            s = eng.stats()
        if s["compile_cache"]["compiles"] != n_warm:
            raise AssertionError(
                f"{arm} arm compiled post-warmup — the revisit times "
                f"would include tracing, not serving")
        return statistics.median(lat), s, outs

    arms = {"hbm": [], "tiered": [], "recompute": []}
    last = {}
    for _ in range(max(1, trials)):
        trial_outs = {}
        for arm in arms:
            med, s, outs = one_trial(arm)
            arms[arm].append(med)
            last[arm] = s
            trial_outs[arm] = outs
        for arm in ("tiered", "recompute"):      # correctness gate
            for a, b in zip(trial_outs["hbm"], trial_outs[arm]):
                if not onp.array_equal(a, b):
                    raise AssertionError(
                        f"{arm} arm diverged from hbm — the TTFT "
                        f"numbers would be comparing different work")
    med_hbm = statistics.median(arms["hbm"])
    med_tier = statistics.median(arms["tiered"])
    med_rec = statistics.median(arms["recompute"])
    base = {"n_families": n_families, "shared_prefix": shared_len,
            "tail": tail_len, "max_new_tokens": max_new,
            "page_size": page_size, "device_pool_pages": starved_pages,
            "working_set_x_pool": working_x}
    yield _record(
        "serving_tiered_ttft_hbm", arms["hbm"], "ms", None,
        dict(base, num_pages=hbm_pages,
             prefix=last["hbm"]["prefix_cache"]))
    yield _record(
        "serving_tiered_ttft_recompute", arms["recompute"], "ms",
        round(med_hbm / med_rec, 4),
        dict(base, prefix=last["recompute"]["prefix_cache"]))
    yield _record(
        "serving_tiered_ttft_tiered", arms["tiered"], "ms",
        round(med_hbm / med_tier, 4),
        dict(base, vs_hbm_x=round(med_tier / med_hbm, 4),
             vs_recompute_x=round(med_tier / med_rec, 4),
             tier=last["tiered"]["tier"],
             prefix=last["tiered"]["prefix_cache"]))


def _build_spec_net(on_tpu: bool):
    """A net whose early-exit drafter TRACKS the full model — the
    regime speculation targets.  A trained LM's residual stream is
    dominated by the embedding/early layers for easy tokens; a randomly
    initialized full-scale stack has no such structure (every layer
    scrambles the stream, so layer-1 logits vs layer-L logits are a
    coin flip and acceptance measures nothing).  Scaling each block's
    residual-out projections down reproduces the trained-model property
    — later blocks refine rather than rewrite — without needing a
    trained checkpoint in the bench."""
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        prompt_lens = (64, 96, 128)
        seq_buckets = (64, 128, 256)
        max_new, spec_tokens, draft_layers = 64, 3, 3
    else:   # CPU sanity: per-token decode must be dominated by the
        # per-call costs a verify window AMORTIZES (weight-streaming
        # matmul passes, program launch) rather than by per-token
        # attention flops — the same regime TPU decode lives in, where
        # a (k+1)-token verify reads the weights from HBM once while
        # k+1 decode steps read them k+1 times.  That regime needs
        # units large enough that streaming the weight matrices
        # dominates a one-token GEMM; measured on this host at
        # units=384 a (k+1=6)-token verify costs ~1.4x one decode
        # step, so speculation wins from ~2 accepted tokens/cycle.
        cfg = dict(vocab_size=512, units=384, num_layers=4,
                   num_heads=4, max_length=256, dropout=0.0)
        prompt_lens = (8, 12, 16)
        seq_buckets = (8, 16, 32)
        max_new, spec_tokens, draft_layers = 32, 5, 1
    net = get_gpt2("gpt2_124m", **cfg)
    net.initialize()
    for blk in net.blocks:
        for p in (blk.attn.out_proj.weight, blk.ffn.fc2.weight):
            p.set_data(p.data() * 0.03)
    return net, prompt_lens, seq_buckets, max_new, spec_tokens, \
        draft_layers


def bench_speculative(concurrency: int = 8, trials: int = 3):
    """Speculative vs plain decode on the same mixed greedy/sampled
    burst at IDENTICAL sampling params.  Output streams are asserted
    identical between the arms every trial (speculation's correctness
    contract: same tokens, fewer dispatches) — greedy rows doubly so,
    being also generate-parity-pinned by the test suite.  Reports
    tokens/s medians, the measured acceptance rate, and the live
    registry snapshot."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    (net, prompt_lens, seq_buckets, max_new, spec_tokens,
     draft_layers) = _build_spec_net(on_tpu)
    rs = onp.random.RandomState(0)
    prompts = [rs.randint(0, net.vocab_size,
                          (prompt_lens[i % len(prompt_lens)],))
               .astype("int32") for i in range(concurrency)]
    # identical sampling params both arms: half greedy (the parity
    # anchor), half seeded sampled (temperature + top-k) — streams are
    # identical between the arms at ANY setting; the temperature only
    # moves the acceptance rate (noisier targets are harder to draft)
    samp = [dict() if i % 2 == 0
            else dict(temperature=1.0, top_k=20, seed=100 + i)
            for i in range(concurrency)]
    total_tokens = concurrency * max_new

    def build(spec):
        kw = dict(spec_tokens=spec_tokens, draft_layers=draft_layers) \
            if spec else {}
        eng = InferenceEngine(
            net, num_slots=concurrency, max_batch=concurrency,
            seq_buckets=seq_buckets, queue_depth=4 * concurrency,
            default_max_new_tokens=max_new,
            name=f"serving_spec_{'on' if spec else 'off'}", **kw)
        eng.warmup()             # pays every compile up front (decode-
        return eng               # bench pattern: one engine, N trials)

    def one_trial(eng):
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=max_new, **k)
                for p, k in zip(prompts, samp)]
        outs = [f.result(timeout=1800) for f in futs]
        return total_tokens / (time.perf_counter() - t0), outs

    plain_vals, spec_vals = [], []
    plain_eng, spec_eng = build(False), build(True)
    with plain_eng, spec_eng:
        # one untimed priming burst per arm: first-burst host warmth
        # (allocator, page cache, lazy jax runtime state) is not a
        # property of either arm and must not land in trial 1
        one_trial(plain_eng)
        one_trial(spec_eng)
        for _ in range(max(1, trials)):
            tps, outs_p = one_trial(plain_eng)
            plain_vals.append(tps)
            tps, outs_s = one_trial(spec_eng)
            spec_vals.append(tps)
            for a, b in zip(outs_p, outs_s):     # correctness gate,
                if not onp.array_equal(a, b):    # every trial
                    raise AssertionError(
                        "speculative/plain output streams diverged — "
                        "the bench numbers would be comparing "
                        "different work")
        last_spec = spec_eng.stats()
        from mxnet_tpu.observability import flatten
        last_spec["registry"] = flatten(prefix="mxtpu_serving")
    speedup = round(statistics.median(spec_vals) /
                    statistics.median(plain_vals), 4)
    sp = last_spec["speculative"]
    base = {"concurrency": concurrency, "max_new_tokens": max_new,
            "spec_tokens": spec_tokens, "draft_layers": draft_layers}
    yield _record("serving_speculative_plain", plain_vals, "tokens/sec",
                  None, dict(base, spec_tokens=0))
    yield _record(
        "serving_speculative", spec_vals, "tokens/sec", speedup,
        dict(base, acceptance_rate=sp["acceptance_rate"],
             spec_cycles=sp["spec_cycles"],
             spec_tokens_proposed=sp["spec_tokens_proposed"],
             spec_tokens_accepted=sp["spec_tokens_accepted"],
             spec_faults=sp["spec_faults"],
             registry_live=last_spec["registry"]))


def _build_sharded_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        prompt_lens = (64, 96, 128)
        seq_buckets = (64, 128, 256)
        max_new = 64
    else:   # CPU sanity: the comparison is about PARITY and the
        # compile freeze on a real mesh, not speed (the virtual devices
        # share one host's cores) — but units large enough that the
        # partitioned matmuls are real work, not dispatch noise
        cfg = dict(vocab_size=2048, units=256, num_layers=4, num_heads=8,
                   max_length=256, dropout=0.0)
        prompt_lens = (8, 12, 16, 24)
        seq_buckets = (8, 16, 32)
        max_new = 32
    net = get_gpt2("gpt2_124m", **cfg)
    net.initialize()
    return net, prompt_lens, seq_buckets, max_new


def bench_sharded(concurrency: int = 8, trials: int = 3,
                  mesh_devices: int = None):
    """1-device vs N-device sharded decode on the same mixed
    greedy/sampled burst.  Token parity between the arms is asserted
    every trial (the contract sharding is judged by), and so is the
    per-(bucket, mesh)-point compile freeze.  See the module docstring
    for what the CPU ratio does and does not mean."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine
    from mxnet_tpu.test_utils import mesh_devices as _devices

    on_tpu = jax.default_backend() == "tpu"
    n = mesh_devices or min(4, len(jax.devices()))
    if n < 2 or _devices(n) is None:
        raise SystemExit(
            f"--workload sharded needs >= 2 chips (have "
            f"{len(jax.devices())})")
    net, prompt_lens, seq_buckets, max_new = _build_sharded_net(on_tpu)
    rs = onp.random.RandomState(0)
    prompts = [rs.randint(0, net.vocab_size,
                          (prompt_lens[i % len(prompt_lens)],))
               .astype("int32") for i in range(concurrency)]
    # half greedy (the generate-parity anchor), half seeded sampled —
    # parity between the arms must hold at ANY sampling setting
    samp = [dict() if i % 2 == 0
            else dict(temperature=1.0, top_k=20, seed=100 + i)
            for i in range(concurrency)]
    total_tokens = concurrency * max_new

    def build(mesh):
        kw = dict(mesh=mesh) if mesh else {}
        eng = InferenceEngine(
            net, num_slots=concurrency, max_batch=concurrency,
            seq_buckets=seq_buckets, queue_depth=4 * concurrency,
            default_max_new_tokens=max_new,
            name=f"serving_sharded_{mesh or 1}dev", **kw)
        eng.warmup()
        return eng

    def one_trial(eng):
        t0 = time.perf_counter()
        futs = [eng.submit(p, max_new_tokens=max_new, **k)
                for p, k in zip(prompts, samp)]
        outs = [f.result(timeout=1800) for f in futs]
        return total_tokens / (time.perf_counter() - t0), outs

    one_vals, mesh_vals = [], []
    eng1, engN = build(None), build(n)
    warm1 = eng1.stats()["compile_cache"]["compiles"]
    warmN = engN.stats()["compile_cache"]["compiles"]
    with eng1, engN:
        one_trial(eng1)          # untimed priming burst per arm (host
        one_trial(engN)          # warmth is not a property of either)
        for _ in range(max(1, trials)):
            tps, outs_1 = one_trial(eng1)
            one_vals.append(tps)
            tps, outs_n = one_trial(engN)
            mesh_vals.append(tps)
            for a, b in zip(outs_1, outs_n):   # parity gate, per trial
                if not onp.array_equal(a, b):
                    raise AssertionError(
                        "sharded/1-device output streams diverged — "
                        "the bench numbers would be comparing "
                        "different work")
        s1, sN = eng1.stats(), engN.stats()
        for s, warm in ((s1, warm1), (sN, warmN)):
            if s["compile"]["compiles"] != warm:
                raise AssertionError(
                    f"compile counter moved on traffic at mesh point "
                    f"{s['compile']['mesh_point']} — the (bucket, "
                    "mesh) freeze broke")
        from mxnet_tpu.observability import flatten
        registry = flatten(prefix="mxtpu_serving")
    ratio = round(statistics.median(mesh_vals) /
                  statistics.median(one_vals), 4)
    base = {"concurrency": concurrency, "max_new_tokens": max_new,
            "parity_asserted": True}
    yield _record("serving_sharded_1dev", one_vals, "tokens/sec", None,
                  dict(base, mesh=s1["mesh"], compile=s1["compile"]))
    yield _record(
        f"serving_sharded_mesh{n}", mesh_vals, "tokens/sec", ratio,
        dict(base, mesh=sN["mesh"], compile=sN["compile"],
             registry_live=registry))


def _build_disagg_net(on_tpu: bool):
    from mxnet_tpu.models import get_gpt2

    if on_tpu:
        cfg = dict(max_length=2048, dropout=0.0)
        name = "gpt2_124m"
        probe_len, chatty_len, chatty_new = 1024, 64, 64
        seq_buckets = (64, 128, 256, 512, 1024, 2048)
        page_size = 128
    else:   # CPU sanity: prefill must be COMPUTE-bound (same reasoning
        # as the prefix bench) or probe TTFT measures dispatch, not the
        # interference disaggregation removes
        name = "gpt2_124m"
        cfg = dict(vocab_size=512, units=128, num_layers=3, num_heads=4,
                   max_length=96, dropout=0.0)
        probe_len, chatty_len, chatty_new = 64, 8, 24
        seq_buckets = (16, 64)
        page_size = 16
    net = get_gpt2(name, **cfg)
    net.initialize()
    return net, probe_len, chatty_len, chatty_new, seq_buckets, page_size


def bench_disagg(n_chatty: int = 6, n_probes: int = 6, trials: int = 3):
    """Disaggregated 1P+1D vs colocated on chatty-decode background +
    long-prefill TTFT probes.  Probes generate ONE token (wall time ==
    TTFT); all outputs are greedy and asserted token-identical between
    the arms per trial.  Engines are built ONCE per arm (warmup pays
    all compiles for both roles; the counter is asserted frozen after
    all traffic) with an untimed priming burst per arm, then >= 3
    timed trials — the bench_sharded discipline."""
    import jax
    import numpy as onp

    from mxnet_tpu.serving import InferenceEngine

    on_tpu = jax.default_backend() == "tpu"
    (net, probe_len, chatty_len, chatty_new, seq_buckets,
     page_size) = _build_disagg_net(on_tpu)
    rs = onp.random.RandomState(13)
    chatty = [rs.randint(0, net.vocab_size, (chatty_len,)).astype("int32")
              for _ in range(n_chatty)]
    probes = [rs.randint(0, net.vocab_size, (probe_len,)).astype("int32")
              for _ in range(n_probes)]

    def build(role, name, target=None):
        eng = InferenceEngine(
            net, num_slots=n_chatty, max_batch=n_chatty,
            seq_buckets=seq_buckets, queue_depth=4 * (n_chatty + n_probes),
            default_max_new_tokens=chatty_new, kv_layout="paged",
            page_size=page_size, role=role, name=name)
        if target is not None:
            eng.migrate_to(target.adopt)
        eng.warmup()
        eng.start()
        return eng

    co = build("unified", "serving_disagg_colocated")
    dec = build("decode", "serving_disagg_decode")
    pre = build("prefill", "serving_disagg_prefill", target=dec)
    arms = {"colocated": (co, [co]), "disagg": (pre, [pre, dec])}
    warm = {e.name: e.stats()["compile_cache"]["compiles"]
            for _, engs in arms.values() for e in engs}

    def one_trial(arm):
        ingress, _ = arms[arm]
        t0 = time.perf_counter()
        bg = [ingress.submit(p, max_new_tokens=chatty_new) for p in chatty]
        ttfts, pouts = [], []
        for p in probes:          # probes timed one at a time: a probe
            tp = time.perf_counter()   # queued behind another probe
            f = ingress.submit(p, max_new_tokens=1)   # would measure
            pouts.append(f.result(timeout=1800))      # OUR burst, not
            ttfts.append((time.perf_counter() - tp) * 1000.0)  # the arm
        bouts = [f.result(timeout=1800) for f in bg]
        dt = time.perf_counter() - t0
        toks = sum(len(o) - len(p)
                   for o, p in zip(pouts + bouts, probes + chatty))
        return statistics.median(ttfts), toks / dt, pouts + bouts

    co_ttft, dg_ttft, co_tps, dg_tps = [], [], [], []
    one_trial("colocated")       # untimed priming burst per arm (host
    one_trial("disagg")          # warmth is not a property of either)
    for _ in range(max(1, trials)):
        ttft, tps, outs_c = one_trial("colocated")
        co_ttft.append(ttft)
        co_tps.append(tps)
        ttft, tps, outs_d = one_trial("disagg")
        dg_ttft.append(ttft)
        dg_tps.append(tps)
        for a, b in zip(outs_c, outs_d):       # parity gate, per trial
            if not onp.array_equal(a, b):
                raise AssertionError(
                    "disagg/colocated greedy outputs diverged — the "
                    "handoff changed the math, bench numbers void")
    for _, engs in arms.values():
        for e in engs:
            if e.stats()["compile_cache"]["compiles"] != warm[e.name]:
                raise AssertionError(
                    f"compile counter moved on traffic ({e.name}) — "
                    "warmup must pay every program for both roles")
    from mxnet_tpu.observability import flatten
    last = {"registry": flatten(prefix="mxtpu_serving")}
    mig = pre.stats()["migration"]
    mig_in = dec.stats()["migration"]
    for _, engs in arms.values():
        for e in engs:
            e.stop(drain=False)
    ratio = round(statistics.median(co_ttft) /
                  statistics.median(dg_ttft), 4)
    base = {"n_chatty": n_chatty, "n_probes": n_probes,
            "chatty_new_tokens": chatty_new, "probe_len": probe_len,
            "parity_asserted": True}
    yield _record(
        "serving_disagg_colocated_ttft", co_ttft, "ms", None,
        dict(base, decode_tokens_per_s=round(statistics.median(co_tps), 1)))
    yield _record(
        "serving_disagg_1p1d_ttft", dg_ttft, "ms", ratio,
        dict(base, decode_tokens_per_s=round(statistics.median(dg_tps), 1),
             migrations_by=mig["by"], migrated_pages=mig["migrated_pages"],
             migrations_in=mig_in["migrations_in"],
             migration_latency=mig["latency"],
             registry_live=last["registry"]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--workload",
                    choices=("decode", "prefix", "fleet", "overload",
                             "paged", "quantized", "speculative",
                             "sharded", "disagg", "elastic", "tiered"),
                    default="decode")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="device count for --workload sharded "
                         "(default: min(4, local devices))")
    args = ap.parse_args()

    from mxnet_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()

    if args.workload == "prefix":
        recs = bench_prefix_cache(trials=args.trials)
    elif args.workload == "fleet":
        recs = bench_fleet(trials=args.trials)
    elif args.workload == "overload":
        recs = bench_overload(trials=args.trials)
    elif args.workload == "paged":
        recs = bench_paged(trials=args.trials)
    elif args.workload == "quantized":
        recs = bench_quantized(trials=args.trials)
    elif args.workload == "speculative":
        recs = bench_speculative(trials=args.trials)
    elif args.workload == "sharded":
        recs = bench_sharded(trials=args.trials,
                             mesh_devices=args.mesh_devices)
    elif args.workload == "disagg":
        recs = bench_disagg(trials=args.trials)
    elif args.workload == "elastic":
        recs = bench_elastic(trials=args.trials)
    elif args.workload == "tiered":
        recs = bench_tiered(trials=args.trials)
    else:
        recs = bench_serving_decode(args.concurrency, args.max_new_tokens,
                                    args.trials)
    from mxnet_tpu.observability import flatten
    for rec in recs:
        # the final registry snapshot rides each record, so the BENCH
        # json carries compile/bucket/prefix counters next to the
        # throughput they explain (docs/observability.md)
        rec["registry"] = flatten(prefix="mxtpu_serving")
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
