"""mxnet_tpu.observability — unified metrics registry, request tracing,
fleet exporters.

Contracts under test: one ``collect()`` snapshot covers serving +
resilience + guardrail + io metrics under stable names; the registry
survives N writer threads racing concurrent readers; Prometheus text
output round-trips through a parser; a served request's spans form ONE
connected trace id across the submit/prefill/decode thread boundary;
``LatencyHistogram.percentile`` never leaves ``[min, max]``; ``stats()``
snapshots are schema-versioned and torn-read-free; the background
exporter drains gracefully (engine ``stop()`` and context-manager
paths) and never publishes a torn file.
"""
import json
import os
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import compiles
from mxnet_tpu.observability import (BackgroundExporter, MetricsRegistry,
                                     default_registry, flatten,
                                     parse_prometheus, to_json_lines,
                                     to_prometheus)
from mxnet_tpu.models import get_gpt2
from mxnet_tpu.serving import InferenceEngine, LatencyHistogram
from mxnet_tpu.serving.metrics import ServingMetrics


@pytest.fixture(scope="module")
def net():
    onp.random.seed(0)
    n = get_gpt2("gpt2_124m", vocab_size=97, units=32, num_layers=2,
                 num_heads=4, max_length=64, dropout=0.0)
    n.initialize()
    return n


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    obs.disable_tracing()


def _prompts(lens, seed=1):
    rs = onp.random.RandomState(seed)
    return [rs.randint(0, 97, (l,)).astype("int32") for l in lens]


def _engine(net, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("seq_buckets", (8, 16))
    kw.setdefault("default_max_new_tokens", 8)
    return InferenceEngine(net, **kw)


# ------------------------------------------------------------- registry

def test_registry_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("t_total", help="h", site="a")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)                     # counters are monotonic
    # get-or-create: same (name, labels) is the SAME metric
    assert reg.counter("t_total", site="a") is c
    assert reg.counter("t_total", site="b") is not c
    g = reg.gauge("t_gauge")
    g.set(3.5)
    g.inc()
    assert g.value == 4.5
    h = reg.histogram("t_seconds")
    h.observe(0.01)
    with h.time():
        pass
    snap = reg.collect()
    assert snap["schema_version"] == 1
    by = {(s["name"], tuple(sorted(s["labels"].items())))
          for s in snap["samples"]}
    assert ("t_total", (("site", "a"),)) in by
    assert ("t_gauge", ()) in by
    hist = [s for s in snap["samples"] if s["name"] == "t_seconds"][0]
    assert hist["count"] == 2
    assert hist["buckets"][-1][0] == float("inf")
    assert hist["buckets"][-1][1] == 2     # cumulative counts


def test_registry_gauge_callback_failure_drops_sample_not_snapshot():
    reg = MetricsRegistry()
    reg.gauge("dead", fn=lambda: 1 / 0)
    reg.counter("alive_total").inc()
    snap = reg.collect()
    names = [s["name"] for s in snap["samples"]]
    assert "alive_total" in names and "dead" not in names


def test_registry_collector_weakref_prunes():
    reg = MetricsRegistry()

    def dead():
        raise ReferenceError("producer collected")

    reg.register_collector("gone", dead)
    reg.register_collector("live", lambda: [
        {"name": "x_total", "kind": "counter", "labels": {}, "value": 1}])
    snap = reg.collect()
    assert [s["name"] for s in snap["samples"]] == ["x_total"]
    # the dead collector was pruned, not just skipped
    assert "gone" not in reg._collectors


def test_registry_under_contention():
    """N writer threads hammer counters + histograms while readers
    collect() concurrently: no exception, no lost increment."""
    reg = MetricsRegistry()
    n_writers, n_inc = 8, 500
    stop = threading.Event()
    errors = []

    def writer(i):
        c = reg.counter("contended_total")
        h = reg.histogram("contended_seconds", writer=str(i % 2))
        g = reg.gauge("contended_gauge")
        try:
            for k in range(n_inc):
                c.inc()
                h.observe(1e-4 * (k + 1))
                g.set(k)
        except Exception as e:          # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                snap = reg.collect()
                cs = [s for s in snap["samples"]
                      if s["name"] == "contended_total"]
                if cs:
                    v = cs[0]["value"]
                    assert 0 <= v <= n_writers * n_inc
                to_prometheus(snap)      # render under fire too
        except Exception as e:          # pragma: no cover
            errors.append(e)

    ws = [threading.Thread(target=writer, args=(i,))
          for i in range(n_writers)]
    rs = [threading.Thread(target=reader) for _ in range(3)]
    for t in rs + ws:
        t.start()
    for t in ws:
        t.join()
    stop.set()
    for t in rs:
        t.join()
    assert not errors
    assert reg.counter("contended_total").value == n_writers * n_inc
    snap = reg.collect()
    hists = [s for s in snap["samples"]
             if s["name"] == "contended_seconds"]
    assert sum(h["count"] for h in hists) == n_writers * n_inc


def test_serving_metrics_register_into_default_registry():
    m = ServingMetrics("reg_unit")
    m.count("submitted", 3)
    m.observe_request(0.01, 0.02, 0.03)
    flat = flatten(prefix="mxtpu_serving")
    assert flat['mxtpu_serving_submitted_total{engine="reg_unit"}'] == 3
    key = ('mxtpu_serving_latency_seconds'
           '{engine="reg_unit",phase="total"}:count')
    assert flat[key] == 1
    # same name re-registers (rebuilt engine): new instance wins
    m2 = ServingMetrics("reg_unit")
    m2.count("submitted", 1)
    flat = flatten(prefix="mxtpu_serving")
    assert flat['mxtpu_serving_submitted_total{engine="reg_unit"}'] == 1


def test_two_live_engines_never_collide_in_one_collect(net):
    """Fleet regression: two LIVE engines — even constructed from the
    same base name — claim distinct identities, so neither's weakref
    collector nor gauges overwrite the other's ``mxtpu_*`` series: one
    ``collect()`` scrapes BOTH engines' full series side by side.
    (Same-name replacement remains the behavior for sequential
    engines: a collected corpse releases its name.)"""
    a = _engine(net, name="replica_pair")
    b = _engine(net, name="replica_pair")
    assert a.name == "replica_pair" and b.name == "replica_pair-2"
    a.metrics.count("submitted", 3)
    b.metrics.count("submitted", 5)
    snap = default_registry().collect()
    by_engine = {}
    for s in snap["samples"]:
        if s["name"] == "mxtpu_serving_submitted_total" and \
                s["labels"].get("engine", "").startswith("replica_pair"):
            by_engine[s["labels"]["engine"]] = s["value"]
    assert by_engine == {"replica_pair": 3, "replica_pair-2": 5}
    gauge_owners = {s["labels"]["engine"]
                    for s in snap["samples"]
                    if s["name"] == "mxtpu_serving_queue_depth"
                    and s["labels"].get("engine", "")
                    .startswith("replica_pair")}
    assert gauge_owners == {"replica_pair", "replica_pair-2"}


def test_one_collect_covers_serving_resilience_guardrails_io(net):
    """The tentpole acceptance: serving counters, resilience/guardrail
    counters and the io quarantine counter all land in ONE default-
    registry collect() under stable names."""
    from mxnet_tpu.resilience import FaultPlan

    # serving
    eng = _engine(net, name="one_collect")
    with eng:
        eng.infer(_prompts((5,))[0], max_new_tokens=2)
    # resilience + guardrails counters ride a ServingMetrics instance
    m = ServingMetrics("resilience")
    m.count("checkpoint_commits")
    m.count("bad_steps", 2)
    # io quarantine
    X = onp.zeros((8, 3), "float32")
    it = mx.io.NDArrayIter(X, onp.zeros(8, "int32"), batch_size=4,
                           quarantine_nonfinite=True)
    with FaultPlan().nonfinite_at("io.bad_batch", at=1):
        batches = list(it)
    assert it.quarantined == 1 and len(batches) == 1
    snap = default_registry().collect()
    names = {(s["name"],
              tuple(sorted(s.get("labels", {}).items())))
             for s in snap["samples"]}
    assert ("mxtpu_serving_completed_total",
            (("engine", "one_collect"),)) in names
    assert ("mxtpu_serving_checkpoint_commits_total",
            (("engine", "resilience"),)) in names
    assert ("mxtpu_serving_bad_steps_total",
            (("engine", "resilience"),)) in names
    assert ("mxtpu_io_quarantined_batches_total", ()) in names
    assert ("mxtpu_serving_compile_cache_entries",
            (("engine", "one_collect"),)) in names


# ------------------------------------------------------------- exporters

def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("rt_total", site="x").inc(7)
    reg.gauge("rt_gauge").set(2.25)
    h = reg.histogram("rt_seconds")
    for v in (0.001, 0.01, 5.0):
        h.observe(v)
    text = to_prometheus(reg.collect())
    parsed = parse_prometheus(text)
    assert parsed[("rt_total", (("site", "x"),))] == 7.0
    assert parsed[("rt_gauge", ())] == 2.25
    assert parsed[("rt_seconds_count", ())] == 3.0
    assert abs(parsed[("rt_seconds_sum", ())] - 5.011) < 1e-9
    # cumulative buckets: the +Inf bucket equals count
    assert parsed[("rt_seconds_bucket", (("le", "+Inf"),))] == 3.0
    # a truncated export must FAIL parsing, not half-succeed
    with pytest.raises(ValueError):
        parse_prometheus(text[:len(text) // 2] + "\ngarbage{")


def test_prometheus_label_value_escaping_round_trip():
    """Engine and fleet names are user-supplied strings: label values
    holding ``"``, ``\\`` and NEWLINES must round-trip through the
    exposition format (a raw newline would tear the sample line in
    half).  Includes the sequential-unescape trap: a literal backslash
    followed by the letter n must NOT come back as a newline."""
    nasty = [
        'plain', 'quo"te', 'back\\slash', 'newline\nsplit',
        'back\\slash then "quote"', '\\n is two chars, not a newline',
        'trailing backslash\\', '\n', '\\', '"', 'brace}value',
        'all\\of"it\ntogether}',
    ]
    for i, v in enumerate(nasty):
        snap = {"samples": [{"name": "esc_gauge", "kind": "gauge",
                             "labels": {"engine": v}, "value": float(i),
                             "help": ""}]}
        text = to_prometheus(snap)
        parsed = parse_prometheus(text)
        assert parsed == {("esc_gauge", (("engine", v),)): float(i)}, \
            (v, text)


def test_prometheus_label_value_escaping_fuzz():
    import random
    rng = random.Random(20260804)
    alphabet = list('ab"\\\n}{=,x ') + ["\\n", "\\\\"]
    for trial in range(200):
        v = "".join(rng.choice(alphabet)
                    for _ in range(rng.randint(0, 12)))
        k = "k" + str(trial)
        snap = {"samples": [{"name": "fuzz_gauge", "kind": "gauge",
                             "labels": {k: v}, "value": 1.0,
                             "help": ""}]}
        text = to_prometheus(snap)
        parsed = parse_prometheus(text)
        assert parsed == {("fuzz_gauge", ((k, v),)): 1.0}, (repr(v), text)


def test_background_exporter_raising_sink_survives(tmp_path):
    """A ``sink=`` that raises must not kill the daemon thread:
    failures are counted, later ticks retry, and ``stop(flush=True)``
    still joins (docs/observability.md — a transient push-gateway
    outage must not lose the exporter for good)."""
    reg = MetricsRegistry()
    reg.counter("sink_total").inc()
    calls = {"n": 0}

    def flaky_sink(text):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("gateway down")

    exp = BackgroundExporter(sink=flaky_sink, interval=0.01, registry=reg)
    with exp:
        deadline = time.monotonic() + 10
        while (exp.errors < 2 or exp.exports < 1) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
    assert not exp.is_alive()              # stop() joined despite errors
    assert exp.errors >= 2                 # failures counted + surfaced
    assert exp.exports >= 1                # ...and later ticks recovered


def test_background_exporter_unwritable_path_survives(tmp_path):
    """An unwritable ``path=`` (full disk, bad mount) is the same
    contract: errors counted, thread alive until stop, final flush
    attempt does not raise."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("a file where the export dir should be")
    out = str(blocker / "m.prom")          # mkdir will fail: parent=file
    reg = MetricsRegistry()
    reg.counter("nope_total").inc()
    exp = BackgroundExporter(path=out, interval=0.01, registry=reg)
    with exp:
        deadline = time.monotonic() + 10
        while exp.errors < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert exp.is_alive()              # still running, not dead
    assert not exp.is_alive()              # stop(flush=True) joined
    assert exp.errors >= 2 and exp.exports == 0
    # the flush error path never published a torn/partial file
    assert not os.path.exists(out)


def test_json_lines_every_line_parses():
    reg = MetricsRegistry()
    reg.counter("jl_total").inc()
    reg.histogram("jl_seconds").observe(0.5)
    lines = to_json_lines(reg.collect()).splitlines()

    def reject(tok):                  # strict RFC JSON: a non-Python
        raise ValueError(tok)         # consumer would choke on Infinity

    objs = [json.loads(ln, parse_constant=reject) for ln in lines]
    assert objs[0]["schema_version"] == 1
    assert {o.get("name") for o in objs[1:]} == {"jl_total", "jl_seconds"}
    hist = [o for o in objs[1:] if o["name"] == "jl_seconds"][0]
    assert hist["buckets"][-1][0] == "+Inf"      # Prometheus spelling


def test_registry_dead_weakref_gauge_pruned():
    reg = MetricsRegistry()

    class Producer:
        depth = 3

    p = Producer()
    import weakref
    ref = weakref.ref(p)

    def fn():
        obj = ref()
        if obj is None:
            raise ReferenceError("producer collected")
        return obj.depth

    reg.gauge("prune_gauge", fn=fn)
    assert [s["name"] for s in reg.collect()["samples"]] == ["prune_gauge"]
    del p
    import gc
    gc.collect()
    assert reg.collect()["samples"] == []
    # pruned for good, not skipped per-scrape
    assert reg._metrics == {}


def test_background_exporter_atomic_file_and_drain(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("bg_total")
    out = str(tmp_path / "m.prom")
    exp = BackgroundExporter(path=out, interval=0.01, registry=reg)
    with exp:
        c.inc(5)
        deadline = time.monotonic() + 5
        while exp.exports == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert exp.exports >= 1
    # context exit = stop(flush=True): joined + final state on disk
    assert not exp.is_alive()
    parsed = parse_prometheus(open(out).read())
    assert parsed[("bg_total", ())] == 5.0
    # stop is idempotent
    exp.stop(flush=True)


def test_engine_stop_drains_attached_exporter(net, tmp_path):
    out = str(tmp_path / "engine.prom")
    exp = BackgroundExporter(path=out, interval=0.02)
    eng = _engine(net, name="drain_exp").attach_exporter(exp)
    with eng:
        eng.infer(_prompts((4,))[0], max_new_tokens=2)
    assert not exp.is_alive()          # stop() joined it
    parsed = parse_prometheus(open(out).read())
    key = ("mxtpu_serving_completed_total", (("engine", "drain_exp"),))
    assert parsed[key] >= 1.0          # final flush saw the terminal count


# --------------------------------------------------------------- tracing

def test_trace_ring_bounded_and_queryable():
    tr = obs.enable_tracing(capacity=16)
    tid = tr.new_trace_id()
    with tr.span("outer", trace_id=tid, k=1):
        tr.event("inner", trace_id=tid)
    for _ in range(40):
        tr.event("noise")
    assert len(tr) == 16 and tr.dropped > 0
    # ring eviction dropped the old spans; fresh ones still query
    tid2 = tr.new_trace_id()
    tr.record_span("late", 1.0, 2.0, trace_id=tid2)
    tl = tr.timeline(tid2)
    assert [d["name"] for d in tl] == ["late"]
    assert tl[0]["duration_ms"] == 1000.0


def test_request_spans_form_one_connected_trace(net):
    """The propagation contract: every span of one request — recorded
    from the caller thread (submit) AND the scheduler thread (queue,
    prefill, decode, complete) — carries one trace id, including the
    batched device calls it rode (trace_ids membership)."""
    tr = obs.enable_tracing(capacity=8192)
    eng = _engine(net, prefix_pool_rows=2, prefix_min_tokens=2,
                  name="trace_prop")
    prompts = _prompts((5, 9, 5, 7), seed=3)
    with eng:
        futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        for f in futs:
            f.result(timeout=120)
    tids = [f.trace_id for f in futs]
    assert all(t is not None for t in tids)
    assert len(set(tids)) == len(tids)          # one trace per request
    for tid in tids:
        names = {d["name"] for d in tr.timeline(tid)}
        # the full lifecycle under ONE id, across the thread boundary
        for expected in ("serving.submit", "serving.queue",
                         "serving.prefill_phase", "serving.decode_phase",
                         "serving.request", "serving.complete"):
            assert expected in names, (tid, expected, names)
        # and the shared batched steps the request rode
        assert any(n.startswith("serving.prefill") for n in names)
        assert "serving.decode_step" in names
    # spans of different requests never leak across ids
    only_first = [d for d in tr.timeline(tids[0])
                  if d["trace_id"] is not None]
    assert all(d["trace_id"] == tids[0] for d in only_first)


def test_tracing_disabled_records_nothing(net):
    tr = obs.enable_tracing()
    obs.disable_tracing()
    eng = _engine(net, name="trace_off")
    with eng:
        fut = eng.submit(_prompts((4,))[0], max_new_tokens=2)
        fut.result(timeout=120)
    assert fut.trace_id is None
    assert len(tr) == 0
    # a pre-tracing future's None id is NOT a wildcard: no whole-ring
    # dump masquerading as this request's timeline
    tr.event("unrelated")
    assert tr.timeline(fut.trace_id) == []


def test_trainer_and_loop_spans(tmp_path):
    from mxnet_tpu import gluon, nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.resilience import ResilientLoop

    tr = obs.enable_tracing()
    mesh = par.make_mesh()       # dp = all (virtual) devices
    with par.use_mesh(mesh):
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu", in_units=4),
                nn.Dense(2, in_units=8))
        net.initialize()
        trainer = par.ShardedTrainer(
            net, "sgd", loss=gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer_params={"learning_rate": 0.01})

        def make_iter():
            rs = onp.random.RandomState(0)
            return iter([(nd.array(rs.randn(8, 4).astype("float32")),
                          nd.array((rs.randn(8) > 0).astype("int32")))
                         for _ in range(3)])

        loop = ResilientLoop(trainer, str(tmp_path / "ck"), save_every=2,
                             seed=0)
        report = loop.run(make_iter, 3)
    assert report["completed_steps"] == 3
    assert len(tr.spans(name="trainer.step")) == 3
    assert len(tr.spans(name="loop.step")) == 3
    commits = tr.spans(name="checkpoint.commit")
    saves = tr.spans(name="checkpoint.save")
    assert len(commits) == 2 and len(saves) == 2   # step 2 + final step 3
    assert commits[0].attrs["step"] == 2


def _tiny_trainer():
    import jax

    from mxnet_tpu import gluon, nd
    from mxnet_tpu import parallel as par
    from mxnet_tpu.gluon import nn

    mesh = par.make_mesh(devices=jax.devices()[:1])
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4),
            nn.Dense(2, in_units=8))
    net.initialize()
    trainer = par.ShardedTrainer(
        net, "adam", loss=gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer_params={"learning_rate": 0.01}, mesh=mesh)
    rs = onp.random.RandomState(0)

    def batch(rows=8):
        return (nd.array(rs.randn(rows, 4).astype("float32")),
                nd.array((rs.randn(rows) > 0).astype("int32")))

    return trainer, batch


@pytest.mark.parametrize("launches,annotation", [
    (True, "marker:trainer:dispatch"), (False, "span:trainer.dispatch")])
def test_host_range_prefix_rule(launches, annotation, monkeypatch):
    """One rule for the range's name in a device capture: what launches
    programs is ``marker:<layer>:<phase>`` (a trace reader attributes a
    program to the last ``marker:`` opened before it started), what only
    waits is ``span:<layer>.<phase>``.  The range is opened whether or
    not a tracer is on; the span only when one is."""
    from mxnet_tpu.observability import trace as obs_trace

    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            opened.append("enter")

        def __exit__(self, *a):
            opened.append("exit")

    monkeypatch.setattr(obs_trace, "_TraceAnnotation", Annotation)
    with obs_trace.host_range("trainer", "dispatch", launches=launches):
        pass
    assert opened == [annotation, "enter", "exit"]      # tracer off
    tr = obs.enable_tracing()
    with tr.span("trainer.step") as step:
        with obs_trace.host_range("trainer", "dispatch",
                                  launches=launches, parent=step, k=1):
            pass
        with obs_trace.host_range("serving", "decode", launches=launches,
                                  span=False):
            pass                # the caller records its own span
    assert opened.count("enter") == opened.count("exit") == 3
    child, = tr.spans(name="trainer.dispatch")
    assert child.parent_id == step.span_id and child.attrs == {"k": 1}
    assert not tr.spans(name="serving.decode")


def test_serving_metrics_span_keeps_its_printed_name(monkeypatch):
    """``marker:<engine>:<kind>``: four committed trace readers match on
    it, and it records no span of its own."""
    from mxnet_tpu.observability import trace as obs_trace

    opened = []
    real = obs_trace._TraceAnnotation
    monkeypatch.setattr(obs_trace, "_TraceAnnotation",
                        lambda name: opened.append(name) or real(name))
    tr = obs.enable_tracing()
    with ServingMetrics("eng7").span("decode"):
        pass
    assert opened == ["marker:eng7:decode"] and len(tr) == 0


def test_trainer_phases_are_children_of_step():
    """The four host phases of a step are children of ``trainer.step``,
    in order and without overlap, and self times add up: the children's
    own time plus the step's own is the step's duration.  ``scalars``
    builds host values (numpy scalars and the key's two words; no
    program) and finds their copies on the device, ``place`` gathers the
    arrays on the mesh and places a batch array only if it is not where
    the step wants it, ``dispatch`` is the one launch of the step,
    ``rebind`` binds its outputs and ships the next step's count and
    key."""
    trainer, batch = _tiny_trainer()
    trainer.step(*batch()).asnumpy()             # compile outside
    tr = obs.enable_tracing()
    for _ in range(2):
        trainer.step(*batch()).asnumpy()
    steps = tr.spans(name="trainer.step")
    assert len(steps) == 2
    own = tr.self_seconds()
    phases = ("trainer.scalars", "trainer.place", "trainer.dispatch",
              "trainer.rebind")
    for step in steps:
        kids = [s for s in tr.spans() if s.parent_id == step.span_id]
        assert tuple(k.name for k in kids) == phases
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert step.t0 <= kids[0].t0 and kids[-1].t1 <= step.t1
        total = own[step.span_id] + sum(own[k.span_id] for k in kids)
        assert total == pytest.approx(step.duration_s, abs=1e-9)
        assert 0 <= own[step.span_id] < step.duration_s
    # the wait for the loss is the caller's, outside the step's span
    reads = tr.spans(name="ndarray.readback")
    assert len(reads) == 2 and all(r.parent_id is None for r in reads)
    assert all(r.t0 >= s.t1 for r, s in zip(reads, steps))


def test_timeline_reports_self_time():
    tr = obs.enable_tracing()
    tid = tr.new_trace_id()
    root = tr.new_span_id()
    # retrospective children first, naming an id reserved for the parent
    tr.record_span("serving.queue", 0.0, 1.0, trace_id=tid, parent=root)
    tr.record_span("serving.prefill_phase", 1.0, 1.5, trace_id=tid,
                   parent=root)
    tr.record_span("serving.decode_phase", 1.25, 3.0, trace_id=tid,
                   parent=root)                 # overlaps: counted once
    tr.record_span("serving.request", 0.0, 4.0, trace_id=tid,
                   span_id=root)
    tr.record_span("elsewhere", 0.0, 4.0, trace_id=tr.new_trace_id())
    tl = {d["name"]: d for d in tr.timeline(tid)}
    assert tl["serving.request"]["span_id"] == root
    assert tl["serving.request"]["parent_id"] is None
    assert tl["serving.queue"]["parent_id"] == root
    assert tl["serving.request"]["self_ms"] == 1000.0   # 4 s less [0, 3]
    assert tl["serving.queue"]["self_ms"] == 1000.0     # no children
    assert "elsewhere" not in tl


def test_request_phases_name_the_request_as_parent(net):
    tr = obs.enable_tracing(capacity=8192)
    eng = _engine(net, name="trace_parent")
    with eng:
        fut = eng.submit(_prompts((5,))[0], max_new_tokens=3)
        fut.result(timeout=120)
    tl = {d["name"]: d for d in tr.timeline(fut.trace_id)}
    root = tl["serving.request"]
    for phase in ("serving.prefill_phase", "serving.decode_phase"):
        assert tl[phase]["parent_id"] == root["span_id"]
    assert root["self_ms"] == pytest.approx(
        root["duration_ms"] - tl["serving.prefill_phase"]["duration_ms"]
        - tl["serving.decode_phase"]["duration_ms"], abs=1e-3)


def test_xla_compiles_total_says_which_step_compiled():
    """``mxtpu_xla_compiles_total`` moves by one on a batch shape the
    step has not seen and not on a repeated one, and the trainer records
    which step it was, with the batch's shapes."""
    def total():
        return sum(s["value"] for s in default_registry().collect()
                   ["samples"] if s["name"] == "mxtpu_xla_compiles_total")

    trainer, batch = _tiny_trainer()
    for _ in range(2):
        trainer.step(*batch()).asnumpy()         # everything warm
    tr = obs.enable_tracing()
    fr = obs.enable_flight_recorder()
    try:
        before = total()
        trainer.step(*batch()).asnumpy()         # a repeated shape
        assert total() == before
        assert not tr.spans(name="trainer.compile")
        trainer.step(*batch(rows=16)).asnumpy()  # a new one
        assert total() == before + 1
        trainer.step(*batch(rows=16)).asnumpy()
        assert total() == before + 1
        ev, = tr.spans(name="trainer.compile")
        step = tr.spans(name="trainer.step")[1]
        assert ev.parent_id == step.span_id
        cost = {k: ev.attrs.pop(k) for k in (
            "trace_s", "lower_s", "compile_s", "cache_hits",
            "cache_misses")}
        assert ev.attrs == {"step": 4, "compiles": 1,
                            "shapes": ["float32[16, 4]", "int32[16]"]}
        # ... and what it cost: this step's own tracing, lowering and
        # wait for the backend, not the three compiles before it
        assert all(0 < cost[k] < ev.t0 - step.t0
                   for k in ("trace_s", "lower_s", "compile_s"))
        assert cost["cache_hits"] + cost["cache_misses"] <= 1
        rec = [e for e in fr.events() if e.name == "trainer.compile"]
        assert len(rec) == 1 and rec[0].attrs["step"] == 4
        assert rec[0].attrs["compile_s"] == cost["compile_s"]
    finally:
        obs.disable_flight_recorder()


# ---------------------------------------------------------- compile log

def _since(t0, kind=None):
    """This thread's records of the compile log that ended after t0."""
    me = threading.get_ident()
    return [r for r in compiles.log() if r[1] > t0 and r[3] == me
            and kind in (None, r[0])]


def _counter(name):
    return sum(s["value"] for s in default_registry().collect()["samples"]
               if s["name"] == name)


def test_compile_log_keeps_a_record_by_kind():
    """One jit on the CPU: a ``trace``, a ``lower`` and a ``compile``
    record with positive seconds, ending in that order, none starting
    before the call; a second call is an in-memory hit and adds none."""
    import jax

    f = jax.jit(lambda x: x * 3 + 1)
    t0 = time.monotonic()
    f(onp.arange(5, dtype="float32")).block_until_ready()
    recs = _since(t0)
    assert [r[0] for r in recs if r[0] in ("trace", "lower", "compile")] \
        == ["trace", "lower", "compile"]
    assert all(r[2] > 0 and r[1] - r[2] >= t0 - 1e-3 for r in recs
               if not r[0].startswith("cache_"))
    assert [r[1] for r in recs] == sorted(r[1] for r in recs)
    assert {r[0]: r[4] for r in recs if r[4]} == {
        "trace": "<lambda>", "lower": "jit(<lambda>)",
        "compile": "jit(<lambda>)"}
    f(onp.arange(5, dtype="float32")).block_until_ready()
    assert _since(t0) == recs


def test_compile_log_counts_a_nested_trace_once():
    """A jit traced inside another reports both to ``jax.monitoring``;
    the log keeps the outer alone and the counter moves by its seconds,
    so neither a reader's union nor a scraper's sum counts the inner
    twice."""
    import jax

    inner = jax.jit(lambda x: x + 2)
    told = []

    def listen(name, secs, **_kw):
        if name.endswith("jaxpr_trace_duration"):
            told.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        before = _counter("mxtpu_jax_trace_seconds_total")
        t0 = time.monotonic()
        jax.jit(lambda x: inner(x) * inner(x * 2))(
            onp.arange(3, dtype="float32")).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(told) >= 2                     # jax told of inner and outer
    rec, = _since(t0, "trace")
    assert rec[2] == max(told)
    assert _counter("mxtpu_jax_trace_seconds_total") - before \
        == pytest.approx(rec[2])


def test_compile_log_until_cuts():
    import jax

    t0 = time.monotonic()
    jax.jit(lambda x: x - 7)(onp.ones(3, "float32")).block_until_ready()
    cut = time.monotonic()
    jax.jit(lambda x: x - 9)(onp.ones(3, "float32")).block_until_ready()
    whole = [r for r in compiles.log() if r[1] > t0]
    early = [r for r in compiles.log(until=cut) if r[1] > t0]
    assert [r[0] for r in whole].count("compile") == 2
    assert [r[0] for r in early].count("compile") == 1
    assert early == whole[:len(early)] and all(r[1] <= cut for r in early)


def test_compile_log_is_bounded_and_counts_what_fell_off(monkeypatch):
    import jax

    # on a log of its own: the process's log, once it has dropped a record,
    # stays unreadable for every later test this worker runs
    # (chipbench/harness/startup.py refuses a log that lost records)
    monkeypatch.setattr(compiles, "_LOG", compiles._Log())
    cap = compiles._CAPACITY
    held, lost = len(compiles.log()), compiles.dropped()
    for _ in range(cap + 10):
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_to_mlir_module_duration", 0.0)
    assert len(compiles.log()) == cap
    assert compiles.dropped() - lost == held + 10
    assert {r[0] for r in compiles.log()} == {"lower"}


def test_compile_log_tells_a_cache_hit_from_a_miss(tmp_path):
    """With a persistent cache that takes every program: the first
    compile is a ``cache_miss`` (compiled and written), the same program
    after ``jax.clear_caches()`` — what a second process would find — is
    a ``cache_hit`` with a ``cache_load`` inside its ``compile``.  The
    five registry names a scraper reads are exported."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        def f(x):
            return (x * 5).sum() - 11

        hits = _counter("mxtpu_compile_cache_hits_total")
        misses = _counter("mxtpu_compile_cache_misses_total")
        t0 = time.monotonic()
        jax.jit(f)(onp.ones(4, "float32")).block_until_ready()
        first = [r[0] for r in _since(t0)]
        assert first.count("cache_miss") == 1 and "cache_hit" not in first
        jax.clear_caches()
        t1 = time.monotonic()
        jax.jit(f)(onp.ones(4, "float32")).block_until_ready()
        second = _since(t1)
        kinds = [r[0] for r in second]
        assert kinds.count("cache_hit") == 1 and "cache_miss" not in kinds
        load, = [r for r in second if r[0] == "cache_load"]
        comp, = [r for r in second if r[0] == "compile"]
        assert comp[1] - comp[2] <= load[1] - load[2] and load[1] <= comp[1]
        assert _counter("mxtpu_compile_cache_hits_total") == hits + 1
        assert _counter("mxtpu_compile_cache_misses_total") == misses + 1
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    text = to_prometheus(default_registry().collect())
    for name in ("mxtpu_jax_trace_seconds_total",
                 "mxtpu_jax_lower_seconds_total",
                 "mxtpu_xla_compile_seconds_total",
                 "mxtpu_compile_cache_hits_total",
                 "mxtpu_compile_cache_misses_total"):
        assert name in text


def test_trainer_build_is_a_span_with_three_children():
    """``build`` under a ``Tracer``: ``trainer.build`` with ``settle``,
    ``state`` and ``place`` as its children, in order and inside it; the
    step is only wrapped, so nothing named ``jit_trainer_step`` compiles
    before the first ``step``."""
    trainer, batch = _tiny_trainer()
    tr = obs.enable_tracing()
    t0 = time.monotonic()
    trainer.build(*batch())
    build, = tr.spans(name="trainer.build")
    kids = [s for s in tr.spans() if s.parent_id == build.span_id]
    assert [k.name for k in kids] == ["trainer.build.settle",
                                      "trainer.build.state",
                                      "trainer.build.place"]
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
    assert build.t0 <= kids[0].t0 and kids[-1].t1 <= build.t1
    got = trainer.stats()["build"]
    assert got["seconds"] == pytest.approx(build.duration_s, abs=2e-3)
    for kid in kids:
        assert got[kid.name.rsplit(".", 1)[1] + "_s"] == pytest.approx(
            kid.duration_s, abs=2e-3)
    in_build = len(_since(t0, "compile"))
    trainer.step(*batch()).asnumpy()
    assert len(_since(t0, "compile")) > in_build
    assert len(tr.spans(name="trainer.build")) == 1       # once a trainer


def test_trainer_build_leaves_its_record_with_no_tracer():
    """What the benchmark's readers take: with nothing listening, one
    record a trainer where the compile log lives, the same numbers as
    ``stats()["build"]``."""
    trainer, batch = _tiny_trainer()
    assert "build" not in trainer.stats()
    had = compiles.builds()[-1:]
    t0 = time.monotonic()
    trainer.build(*batch())
    t1 = time.monotonic()
    built = compiles.builds()[-1:]
    assert built != had         # one record (the log keeps the newest 16)
    trainer.build(*batch())                     # built already: no record
    trainer.step(*batch()).asnumpy()
    assert compiles.builds()[-1:] == built
    start, end, settle_s, state_s, place_s = built[0]
    assert t0 <= start < end <= t1
    assert trainer.stats()["build"] == {
        "seconds": end - start, "settle_s": settle_s, "state_s": state_s,
        "place_s": place_s}
    assert min(settle_s, state_s, place_s) > 0
    assert settle_s + state_s + place_s <= end - start


def test_warm_step_adds_nothing_to_the_compile_log():
    """Everything this module keeps is paid for when a program is got:
    a warm step with the ``Tracer`` off appends no record and no build."""
    trainer, batch = _tiny_trainer()
    b = batch()
    for _ in range(2):
        trainer.step(*b).asnumpy()
    before = (compiles.log(), compiles.dropped(), compiles.builds())
    for _ in range(3):
        trainer.step(*b).asnumpy()
    assert (compiles.log(), compiles.dropped(), compiles.builds()) == before


def test_names_inside_the_compiled_step():
    """Names that no trace reader can see yet, checked where they can
    be: the program is ``jit_trainer_step``; the forward and the loss
    lower under the scope ``fwd`` (the backward reads
    ``transpose(jvp(fwd))`` by itself) and the update under
    ``optimizer``."""
    trainer, batch = _tiny_trainer()
    lowered = trainer.lower_step(*batch())
    text = lowered.as_text(debug_info=True)
    hlo = lowered.compile().as_text()
    assert hlo.startswith("HloModule jit_trainer_step")
    for scope in ("jvp(fwd)", "transpose(jvp(fwd))", "optimizer"):
        assert f'"jit(trainer_step)/{scope}/' in text, scope
        assert f'op_name="jit(trainer_step)/{scope}/' in hlo, scope


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd", "flash_bwd_dq",
                                    "flash_bwd_dkv", "paged_decode"])
def test_pallas_kernels_carry_their_names(kernel, monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import flash
    from mxnet_tpu.ops.flash import flash_attention
    from mxnet_tpu.ops.paged import paged_attention

    if kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        # the two calls of a sequence whose dq VMEM cannot hold
        monkeypatch.setattr(flash, "_VMEM_FUSED", 0)

    if kernel == "paged_decode":
        q = jnp.zeros((2, 1, 4, 16), jnp.float32)
        pages = jnp.zeros((7, 8, 4, 16), jnp.float32)
        jaxpr = jax.make_jaxpr(paged_attention)(
            q, pages, pages, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2, 1), jnp.int32))
    else:
        q = jnp.zeros((1, 256, 2, 64), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True))))(
                q, q, q)

    def names(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield str(eqn.params.get("name")
                          or eqn.params["name_and_src_info"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from names(sub)

    assert kernel in list(names(jaxpr.jaxpr))


# ---------------------------------------------- LatencyHistogram bounds

def test_percentile_never_above_observed_max():
    """Regression: geometric interpolation inside the winning bucket —
    and the open-ended top bucket — must never report a percentile
    above the largest observed sample."""
    h = LatencyHistogram()
    # all samples beyond the last finite bound -> open-ended tail
    for v in (150.0, 200.0, 500.0):
        h.observe(v)
    for q in (50, 95, 99, 100):
        assert h.percentile(q) <= h.max
    # winning-bucket interpolation with the max mid-bucket
    h2 = LatencyHistogram()
    for _ in range(100):
        h2.observe(0.010)
    assert h2.percentile(99) <= h2.max
    assert h2.percentile(99) <= 0.010


def test_percentile_never_below_observed_min():
    """The symmetric hole: every sample in bucket 0 sits below the
    synthetic bounds[0]/2 floor when samples are tiny."""
    h = LatencyHistogram()
    for v in (1e-9, 2e-9, 3e-9):
        h.observe(v)
    for q in (1, 50, 99):
        p = h.percentile(q)
        assert h.min <= p <= h.max


def test_percentile_fuzz_stays_in_observed_range():
    rs = onp.random.RandomState(7)
    for _ in range(50):
        h = LatencyHistogram()
        for v in 10.0 ** rs.uniform(-7, 3.5, size=rs.randint(1, 30)):
            h.observe(float(v))
        for q in (0, 1, 50, 90, 99, 100):
            p = h.percentile(q)
            assert h.min <= p <= h.max


# ------------------------------------------------------- stats() contract

def test_stats_schema_version_and_atomic_snapshot(net):
    eng = _engine(net, name="stats_atomic")
    assert eng.stats()["schema_version"] == 1
    m = eng.metrics
    stop = threading.Event()
    errors = []

    def writer():
        while not stop.is_set():
            # one observe_request updates queue+prefill+decode+total+ttft
            # under ONE lock acquisition — a snapshot must see them move
            # together
            m.observe_request(0.001, 0.002, 0.003)

    def reader():
        try:
            for _ in range(300):
                s = m.stats()
                lat = s["latency"]
                assert lat["queue"]["count"] == lat["prefill"]["count"] \
                    == lat["decode"]["count"] == lat["total"]["count"] \
                    == s["ttft"]["count"], "torn stats() snapshot"
        except Exception as e:          # pragma: no cover
            errors.append(e)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start()
    r.start()
    r.join()
    stop.set()
    w.join()
    assert not errors


# --------------------------------------------------- obs-tier contracts

@pytest.mark.obs
@pytest.mark.slow
def test_tracing_disabled_overhead_within_noise(net):
    """The zero-cost contract, measured: engine decode throughput with
    tracing DISABLED must match a run where tracing was never enabled,
    within trial spread (same contract shape as serving_perf)."""
    prompts = _prompts((5, 7, 9, 4), seed=5)

    def run_once(name):
        eng = _engine(net, name=name)
        eng.warmup()
        with eng:
            t0 = time.perf_counter()
            for _ in range(3):
                futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
                for f in futs:
                    f.result(timeout=120)
            return time.perf_counter() - t0

    run_once("warm")                       # pay residual compiles
    base = min(run_once(f"base{i}") for i in range(3))
    obs.enable_tracing()
    obs.disable_tracing()                  # enabled-then-disabled
    off = min(run_once(f"off{i}") for i in range(3))
    # generous bound: CPU timing is noisy; the disabled path is one
    # global load + None check per site, nowhere near 1.5x
    assert off < base * 1.5, (base, off)


def test_flash_plan_is_an_event_once_per_plan():
    """``flash_attention`` says which sizes it chose and how much of the
    score square they run: one ``flash.plan`` event per distinct plan
    while a tracer is on (recorded as the call is traced: nothing in a
    step's hot path), nothing with it off."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.flash import flash_attention, tile_plan

    q = jnp.zeros((1, 512, 1, 64), jnp.float32)

    def call(x, causal=True):
        return flash_attention(x, x, x, causal=causal, block_q=128,
                               block_k=128, interpret=True)

    obs.disable_tracing()
    call(q)
    tr = obs.enable_tracing()
    try:
        assert not tr.spans(name="flash.plan")     # said nothing while off
        fn = jax.jit(call)
        fn(q), fn(q), call(q)                      # traced twice, one plan
        ev, = tr.spans(name="flash.plan")
        plan = tile_plan(512, 512, 64, jnp.float32, True, block_q=128,
                         chunk=128)
        assert ev.attrs == dict(plan._asdict(), tq=512, tk=512, d=64,
                                dtype="float32", causal=True,
                                has_seg=False)
        assert (ev.attrs["tiles_run"], ev.attrs["tiles_full"]) == (10, 16)
        # which backward the call site got: the one call, its dq summed in
        # float32 over the head's 512 rows (64 lanes pad to 128), inside
        # the VMEM a call has unasked
        assert (ev.attrs["backward"], ev.attrs["dq_bytes"],
                ev.attrs["bwd_vmem"]) == ("fused", 4 * 512 * 128, 0)
        call(q, causal=False)                      # another plan
        assert [s.attrs["tiles_run"]
                for s in tr.spans(name="flash.plan")] == [10, 16]
    finally:
        obs.disable_tracing()


def test_flash_plan_of_a_windowed_call_masks_less_than_it_runs():
    """Under a window a block walks its band by the two edges, and only
    the tiles ON an edge build a mask: ``tiles_masked`` of the
    ``flash.plan`` event is smaller than ``tiles_run`` (before PR 44
    every visit was masked and the two were equal), and both are under
    the causal mask's own."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.flash import flash_attention

    q = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.float32)

    def call(x, window):
        return flash_attention(x, x, x, causal=True, window=window,
                               interpret=True)

    tr = obs.enable_tracing()
    try:
        for window in (1024, None):            # traced only: nothing runs
            jax.eval_shape(lambda x: call(x, window), q)
        windowed, causal = (e.attrs for e in tr.spans(name="flash.plan"))
    finally:
        obs.disable_tracing()
    assert windowed["window"] == 1024 and "window" not in causal
    assert (windowed["block_q"], windowed["slab"]) == (512, 512)
    # (512, 512) tiles of four blocks: a block's own square, the square
    # behind it that every row sees whole, and one on the trailing edge
    assert (windowed["tiles_run"], windowed["tiles_masked"]) == (9, 6)
    assert (causal["tiles_run"], causal["tiles_masked"]) == (10, 4)
    assert (windowed["tiles_run_bwd"], causal["tiles_run_bwd"]) == (108, 136)
