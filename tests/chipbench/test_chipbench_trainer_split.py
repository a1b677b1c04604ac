"""The three metrics that split ``trainer.step_ms`` and the ranges they
rest on: the readers on a hand-made run (to the microsecond), found by
file name like every other reader; the host ranges that the program
itself opens under a ``jax.profiler`` session with no ``Tracer`` on; the
serving engine's compile counter on the case of PERF.md's finding 2."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import chipbench_tiny  # noqa: E402
from chipbench import run as R  # noqa: E402
from chipbench.harness import xtrace  # noqa: E402

NEW = ("trainer.device_step_ms", "trainer.step_gap_ms",
       "trainer.launches_per_step")
STEP = "jit_trainer_step(10949533901252873124)"


def _modules(steps=3, step_ms=96.8, gaps_ms=(1.15, 1.28, 1.0, 0.7, 2.4)):
    """``steps`` steps of five programs each, as ``xtrace.reduce`` gives
    them: (name, launcher, start_s, dur_s) on device 0."""
    small = ["jit_convert_element_type(1)"] * 3 + ["jit__threefry_fold_in(2)"]
    out, at = [], 0.01
    for _ in range(steps):
        for name, gap in zip(small, gaps_ms):
            at += gap * 1e-3
            out.append((name, "marker:trainer:scalars", at, 6e-7))
            at += 6e-7
        at += gaps_ms[-1] * 1e-3
        # the launcher is deliberately a LATER range: a launch is
        # asynchronous, the readers must go by the program's name
        out.append((STEP, "marker:trainer:scalars", at, step_ms * 1e-3))
        at += step_ms * 1e-3
    return out


def _read(name, modules):
    return R.load_module(REPO, "layer_metrics", name).read(
        {"trace": {"modules": modules}})


@pytest.mark.parametrize("name,want", [
    ("trainer.device_step_ms", 96.8),
    ("trainer.step_gap_ms", 1.15 + 1.28 + 1.0 + 0.7 + 2.4 + 4 * 6e-4),
    ("trainer.launches_per_step", 5.0)])
def test_reader_on_hand_made_run(name, want):
    got = _read(name, _modules())
    assert abs(got - want) < 1e-3 if name.endswith("_ms") else got == want


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_the_named_step(name):
    """The parent's trace: the step is ``jit_pure`` there."""
    parent = [(n.replace("trainer_step", "pure"), l, s, d)
              for n, l, s, d in _modules()]
    assert _read(name, parent) is None
    assert _read(name, []) is None


def test_one_step_has_a_duration_and_nothing_between():
    one = _modules(steps=1)
    assert abs(_read("trainer.device_step_ms", one) - 96.8) < 1e-3
    assert _read("trainer.step_gap_ms", one) is None
    assert _read("trainer.launches_per_step", one) is None


def test_readers_on_the_recorded_trace():
    """The trace recorded on the chip before the step had its name: read
    with the name put in, the three metrics give what ISSUE 24 read off
    it by hand, and gap plus duration is the host's step."""
    r = xtrace.reduce(os.path.join(REPO, "chipbench", "data",
                                   "sample.xplane.pb.gz"))
    named = [(n.replace("jit_pure(", "jit_trainer_step("), l, s, d)
             for n, l, s, d in r["modules"]]
    dur = _read("trainer.device_step_ms", named)
    gap = _read("trainer.step_gap_ms", named)
    assert 96.7 < dur < 96.9 and 6.5 < gap < 6.8
    assert _read("trainer.launches_per_step", named) == 5.0


def test_new_readers_through_the_harness(tmp_path):
    """As ``run.py`` finds them: by the entry in BENCHMARK.json and the
    file name, in the training cell and in no other."""
    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["tiny_train"]
        assert entries[name]["layer"] == "trainer"
        assert R.load_module(root, "layer_metrics", name).NAME == name
    # the committed readers want a whole run; these three want the trace
    bench = dict(bench, per_layer=[entries[name] for name in NEW])
    run = {"e2e": {"train_tokens_per_s": 1.0, "setup_s": 1.0},
           "trace": {"modules": _modules()}}
    got = R.per_layer(bench, "tiny_train", run, root)
    assert set(got) == set(NEW)
    assert got["trainer.launches_per_step"] == {"value": 5.0,
                                                "unit": "count"}
    # on the parent's program the line leaves them out and nothing raises
    run["trace"]["modules"] = []
    assert R.per_layer(bench, "tiny_train", run, root) == {}
    serving = {"e2e": {"ms_per_token_p50": 1.0, "setup_s": 1.0}}
    assert not set(NEW) & {m["name"] for m in R.metrics_for(
        bench, "per_layer", "tiny_chat", serving["e2e"])}


def test_program_ranges_reach_a_profile_with_no_tracer(tmp_path):
    """Two steps of a tiny trainer under ``jax.profiler`` with the
    ``Tracer`` off, driven as the training driver drives it: the ranges
    the PROGRAM opens are on the host plane, and an idle gap is named by
    the innermost of them, not by the driver's ``chipbench:step``."""
    import jax
    import numpy as onp

    from mxnet_tpu import gluon, nd, observability as obs
    from mxnet_tpu import parallel as par
    from mxnet_tpu.data import DevicePrefetcher
    from mxnet_tpu.gluon import nn

    obs.disable_tracing()
    rs = onp.random.RandomState(0)
    batches = [(rs.randn(8, 4).astype("float32"),
                (rs.randn(8) > 0).astype("int32")) for _ in range(4)]
    mesh = par.make_mesh(devices=jax.devices()[:1])
    with par.use_mesh(mesh):
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu", in_units=4),
                nn.Dense(2, in_units=8))
        net.initialize()
        trainer = par.ShardedTrainer(
            net, "adam", loss=gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer_params={"learning_rate": 0.01})
        trainer.build(nd.array(batches[0][0]), nd.array(batches[0][1]))
        feed = DevicePrefetcher(iter(batches),
                                shardings=trainer.batch_shardings)
        try:
            for _ in range(2):                  # compile outside the trace
                data, labels = next(feed)
                trainer.step(data, labels).asnumpy()
            jax.profiler.start_trace(str(tmp_path))
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_ANNOTATION):
                for _ in range(2):
                    with jax.profiler.TraceAnnotation("chipbench:step"):
                        data, labels = next(feed)
                        trainer.step(data, labels).asnumpy()
            jax.profiler.stop_trace()
        finally:
            feed.close()
    host = xtrace.load(xtrace.find_xplane(str(tmp_path)))["host"]
    names = [n for n, _s, _e in host]
    for want in ("marker:trainer:scalars", "marker:trainer:place",
                 "marker:trainer:dispatch", "span:trainer.rebind",
                 "span:ndarray.readback", "span:input.next"):
        assert names.count(want) == 2, (want, sorted(set(names)))
    by_name = {}
    for n, s, e in host:
        by_name.setdefault(n, []).append((s, e))
    step = by_name["chipbench:step"][-1]
    for want in ("marker:trainer:dispatch", "span:ndarray.readback",
                 "span:input.next"):
        s, e = by_name[want][-1]
        assert step[0] <= s and e <= step[1]     # inside the driver's range
        # a gap of the device anywhere inside the range takes its name
        assert xtrace._name_gap(host, s + 0.25 * (e - s),
                                s + 0.75 * (e - s)) == want
    # what launches is a marker:, what waits is a span:, so that the
    # launcher of a program is never a range that only waited
    markers = [h for h in host if h[0].startswith("marker:")]
    s, e = by_name["span:ndarray.readback"][-1]
    assert xtrace._launcher(markers, e) == "marker:trainer:dispatch"


def test_set_data_then_warmup_moves_the_engines_compile_counter():
    """PERF.md section 6, finding 2: weights handed over with ``set_data``
    are committed arrays, ``warmup()`` runs the decode program on fresh
    uncommitted caches, and the first live step compiles it again.  The
    engine's counter is fed from XLA's own compiles and moves; its count
    of programs (first call per bucket) stays put."""
    import numpy as np

    from chipbench.drivers import serve
    from mxnet_tpu import observability as obs

    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        config = json.load(f)
    net, eng = serve.build(config, 7)
    param = next(iter(net.collect_params().values()))
    assert param.data().jax._committed
    warmed = eng.warmup()
    stats = eng.stats()["compile"]
    assert warmed == stats["compiles"] == stats["programs"]
    eng.start()
    try:
        rng = np.random.default_rng(0)
        for n in (8, 40):
            eng.infer(rng.integers(0, 128, n).astype("int32"),
                      max_new_tokens=4)
    finally:
        eng.stop()
    after = eng.stats()["compile"]
    assert after["programs"] == stats["programs"]
    assert after["compiles"] > warmed
    assert after["by_mesh_point"] == {"1dev": after["compiles"]}
    gauge = [s for s in obs.default_registry().collect()["samples"]
             if s["name"] == "mxtpu_serving_compiles"
             and s["labels"]["engine"] == eng.metrics.name]
    assert [s["value"] for s in gauge] == [after["compiles"]]
