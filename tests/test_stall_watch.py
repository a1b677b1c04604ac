"""``observability.stalls``: every verdict from a planted cause, and no
record from a steady run.

The cases whose cause lives on the real clock (a sleep, a compile, a
collection, a feed) run under the real witness thread; the others plant
the clock itself and wake a witness by hand, so that nothing depends on
how this machine schedules a sleeping thread.
"""
import gc
import logging
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu import gluon, nd
from mxnet_tpu import parallel as par
from mxnet_tpu.data import DevicePrefetcher
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import default_registry, host_range, stalls
from mxnet_tpu.observability import flightrecorder as frmod

STEADY_S = 0.005


def readback(step=0, result=None):
    """The read of a step's loss: the first read since ``awaiting``."""
    stalls.awaiting(step, result)
    return host_range("ndarray", "readback", launches=False)


@pytest.fixture
def watch():
    """The real watch, with nothing remembered."""
    stalls._reset()
    stalls.start()
    yield stalls
    stalls._reset()


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


@pytest.fixture
def planted(monkeypatch):
    """A clock the test moves and a witness the test wakes: ``(clock,
    wake)``; ``wake(n, late=0)`` is n wakes 20 ms apart, the last one
    ``late`` seconds after it was due."""
    if stalls._WITNESS is not None:
        stalls._WITNESS.stop()
    stalls._reset()
    clock = Clock()
    monkeypatch.setattr(stalls, "_now", clock)
    by_hand = stalls.Witness(clock=clock)
    monkeypatch.setattr(stalls, "_WITNESS", by_hand)
    monkeypatch.setattr(stalls, "_ON", True)

    def wake(n=1, late=0.0):
        for i in range(n):
            clock.t += stalls._PERIOD + (late if i == n - 1 else 0.0)
            by_hand.tick()

    yield clock, wake
    stalls._reset()


def steady(clock, n=12):
    for _ in range(n):
        with readback():
            clock.t += STEADY_S


def only_record():
    recs = stalls.log()
    assert len(recs) == 1, recs
    return recs[0]


# ------------------------------------------------- causes on the real clock
def test_a_planted_sleep_is_one_record(watch, caplog):
    for _ in range(12):
        with readback():
            time.sleep(STEADY_S)
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.stalls"):
        with readback(41):
            time.sleep(0.4)
    rec = only_record()
    assert rec["phase"] == "ndarray.readback"
    assert rec["step"] == 41
    assert rec["thread"] == threading.current_thread().name
    assert abs(rec["seconds"] - 0.4) < 0.05
    assert STEADY_S <= rec["expected_s"] < 0.05
    assert rec["watched"] and rec["ready_after_s"] is None
    assert rec["verdict"] == "device_or_runtime"
    assert rec["proc"] is None or "cpu_s" in rec["proc"]
    warned = [r for r in caplog.records if r.name == "mxnet_tpu.stalls"]
    assert len(warned) == 1
    line = warned[0].getMessage()
    assert "step 41" in line and "device_or_runtime" in line
    assert '"phase": "ndarray.readback"' in line    # the evidence, as JSON
    total = stalls.summary()
    assert total["count"] == 1 and total["last"] is rec
    assert abs(total["over_expected_s"]
               - (rec["seconds"] - rec["expected_s"])) < 1e-6


def test_a_compile_on_another_thread_is_named(watch):
    import jax
    import jax.numpy as jnp

    for _ in range(12):
        with readback():
            time.sleep(STEADY_S)
    stop = threading.Event()

    def compile_afresh():
        x, i = jnp.ones((64, 64)), 0
        while not stop.is_set():
            i += 1

            def chain(v, k=float(i)):
                for _ in range(40):
                    v = jnp.tanh(v @ v + k)
                return v
            jax.jit(chain)(x).block_until_ready()

    other = threading.Thread(target=compile_afresh)
    other.start()
    try:
        time.sleep(0.05)
        with readback():
            time.sleep(0.6)
    finally:
        stop.set()
        other.join()
    rec = only_record()
    assert rec["verdict"] == "compile", rec
    assert {c["kind"] for c in rec["compiles"]} >= {"compile"}


def test_a_collection_is_named(watch):
    for _ in range(12):
        with readback():
            time.sleep(0.001)
    gc.collect()
    gc.disable()
    try:
        for _ in range(1_000_000):
            a = []
            a.append([a])
        del a
        with readback():
            gc.collect()
    finally:
        gc.enable()
    rec = only_record()
    assert rec["gc_s"] > 0.5 * rec["seconds"]
    # the collection held the witness too: that is not the host's doing
    assert rec["verdict"] == "gc", rec


# ------------------------------------------------ causes on a planted clock
def test_another_thread_inside_one_phase_is_named(planted):
    clock, wake = planted
    steady(clock)
    inside, leave = threading.Event(), threading.Event()

    def feeder():
        with stalls.phase("input.ship"):
            inside.set()
            leave.wait(10)

    other = threading.Thread(target=feeder, name="feeder-under-test")
    other.start()
    inside.wait(10)
    try:
        with readback():
            wake(25)
    finally:
        leave.set()
        other.join()
    rec = only_record()
    assert rec["verdict"] == "thread:input.ship", rec
    seen = {t["name"]: t for t in rec["threads"]}
    assert seen["feeder-under-test"]["phase"] == "input.ship"
    assert any("feeder" in f for f in seen["feeder-under-test"]["frames"])
    assert len(seen["feeder-under-test"]["frames"]) <= 3


class Ready:
    def is_ready(self):
        return True


def test_a_result_ready_from_the_start_is_a_late_wake(planted):
    clock, wake = planted
    steady(clock)
    with readback(9, Ready()):
        wake(25)
    rec = only_record()
    assert rec["step"] == 9
    assert rec["ready_after_s"] is not None
    assert rec["seconds"] - rec["ready_after_s"] > 0.1
    assert rec["verdict"] == "wake_late"


def test_a_result_not_ready_until_the_end_is_the_devices(planted):
    class Never:
        def is_ready(self):
            return False

    clock, wake = planted
    steady(clock)
    with readback(10, Never()):
        wake(25)
    rec = only_record()
    assert rec["ready_after_s"] is None
    assert rec["verdict"] == "device_or_runtime"


def test_a_witness_woken_late_is_a_frozen_host(planted):
    clock, wake = planted
    steady(clock)
    with readback(11, Ready()):
        wake(4)
        wake(1, late=1.5)       # nobody ran for a second and a half
        wake(2)
    rec = only_record()
    assert rec["verdict"] == "host_frozen", rec
    assert abs(rec["witness_late_max_s"] - 1.5) < 1e-6
    assert max(late for _at, late in stalls.lateness()) == \
        pytest.approx(1.5)


def test_a_freeze_nobody_watched_is_still_a_frozen_host(planted):
    """A host that does not run freezes the witness with the rest: the
    phase may end before the witness's next wake."""
    clock, wake = planted
    steady(clock)
    wake(3)
    with readback():
        clock.t += 2.0
    rec = only_record()
    assert not rec["watched"]
    assert rec["verdict"] == "host_frozen", rec


def test_a_steady_run_records_nothing(planted):
    clock, wake = planted
    for i in range(200):
        with readback():
            clock.t += STEADY_S * (1 + (i % 7) / 10)
        wake(1)
    # long, but no longer than they always are
    for _ in range(20):
        with host_range("trainer", "dispatch", launches=True):
            clock.t += 0.5
            wake(1)
    assert stalls.log() == [] and stalls.summary()["count"] == 0


def test_a_phase_with_fewer_than_8_samples_is_not_judged(planted):
    clock, wake = planted
    steady(clock, n=stalls._MIN_SAMPLES - 1)
    with readback():
        wake(50)
    assert stalls.log() == []
    with readback():            # the eighth sample was that long one
        wake(50)
    assert len(stalls.log()) == 1


def test_reads_that_wait_for_no_step_are_neither_kept_nor_judged(planted):
    """A driver reads fifty small arrays in set-up, then steps of half a
    second: the steps are judged against steps, from the eighth on."""
    clock, wake = planted
    for _ in range(50):
        with host_range("ndarray", "readback", launches=False):
            clock.t += 0.001
    assert "ndarray.readback" not in stalls._LENGTHS
    for step in range(12):
        with readback(step):
            clock.t += 0.5
            wake(1)
        with host_range("ndarray", "readback", launches=False):
            clock.t += 2.0      # a second output, long since there: no wait
            wake(1)
    assert stalls.log() == []
    assert len(stalls._LENGTHS["ndarray.readback"]) == 12
    with readback(12):
        wake(100)
    assert only_record()["step"] == 12


def test_a_hiccup_under_the_floor_is_not_a_stall(planted):
    clock, wake = planted
    steady(clock, n=20)
    with readback():
        clock.t += 0.12         # 28 medians with the wake, but under
        wake(1)                 # 0.15 s over one
    assert stalls.log() == []


def test_the_ring_is_bounded_and_counts_what_fell_off(planted, caplog):
    clock, wake = planted
    caplog.set_level(logging.CRITICAL, logger="mxnet_tpu.stalls")
    steady(clock, n=64)
    extra = 5
    for i in range(stalls._CAPACITY + extra):
        stalls._LENGTHS["ndarray.readback"].extend([STEADY_S] * 2)
        with readback(i):
            clock.t += 0.3
    assert len(stalls.log()) == stalls._CAPACITY
    assert stalls.dropped() == extra
    assert stalls.summary()["count"] == stalls._CAPACITY + extra


def test_counters_and_events_carry_the_stall(planted):
    from mxnet_tpu import observability as obs

    clock, wake = planted
    steady(clock)
    counted = default_registry().counter(
        "mxtpu_host_stalls_total", help="", phase="ndarray.readback",
        verdict="device_or_runtime")
    seconds = default_registry().counter(
        "mxtpu_host_stall_seconds_total", help="",
        phase="ndarray.readback")
    before = counted.value, seconds.value
    tracer = obs.enable_tracing()
    fr = frmod.enable(capacity=16)
    try:
        with readback() as phase:
            wake(25)
        spans = {s.name: s for s in tracer.spans()}
        events = {e.name: e for e in fr.events()}
    finally:
        obs.disable_tracing()
        frmod.disable()
    rec = only_record()
    assert counted.value == before[0] + 1
    assert seconds.value - before[1] == pytest.approx(
        rec["seconds"] - rec["expected_s"])
    assert spans["host.stall"].parent_id == phase.span.span_id
    assert spans["host.stall"].attrs["verdict"] == "device_or_runtime"
    assert events["host.stall"].attrs["phase"] == "ndarray.readback"


# ---------------------------------------------------------- who feeds it
def test_the_prefetchers_stall_goes_through_the_log(watch):
    def slow():
        yield (onp.zeros((2, 3), "float32"), onp.zeros(2, "float32"))
        time.sleep(0.3)
        yield (onp.ones((2, 3), "float32"), onp.ones(2, "float32"))

    fr = frmod.enable(capacity=64)
    try:
        pf = DevicePrefetcher(slow(), depth=2, stall_timeout=0.05)
        got = list(pf)
        st = pf.stats()
        pf.close()
        events = [e for e in fr.events() if e.name == "data.stall"]
    finally:
        frmod.disable()
    assert len(got) == 2 and st["stalls"] == 1
    rec = only_record()         # too little history to judge: declared
    assert rec["phase"] == "input.next" and rec["expected_s"] is None
    assert rec["verdict"] == "thread:input.pull", rec
    assert len(events) == 1
    assert events[0].attrs["verdict"] == rec["verdict"]
    assert events[0].attrs["consumed"] == 1
    assert events[0].attrs["waited"] >= 0.25


def test_the_trainer_says_what_stalled(watch):
    import jax

    def batches(n, sleep_at):
        for i in range(n):
            if i == sleep_at:
                time.sleep(0.5)
            rs = onp.random.RandomState(1000 + i)
            x = rs.randn(8, 6).astype("float32")
            yield (nd.array(x), nd.array((x.sum(1) > 0).astype("int32")))

    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=6),
            nn.Dense(2, in_units=16))
    net.initialize()
    mesh = par.make_mesh(dp=1, devices=jax.devices()[:1])
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "adam", loss=gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer_params={"learning_rate": 0.01})
        trainer.build(*next(batches(1, -1)))
        assert trainer.stats()["stalls"] == {
            "count": 0, "over_expected_s": 0.0, "last": None}
        feed = trainer.attach_data_source(DevicePrefetcher(
            batches(30, 20), shardings=trainer.batch_shardings))
        for data, labels in feed:
            trainer.step(data, labels).asnumpy()
        feed.close()
    said = trainer.stats()["stalls"]
    # the feeder's pull and, when the ring had run dry, the step's wait
    pull, wait = stalls.log()
    assert said["count"] == 2 and said["last"] is wait
    assert (pull["phase"], pull["thread"]) == ("input.pull",
                                               "mxtpu-data-feeder")
    assert pull["verdict"] == "device_or_runtime"   # nobody else's doing
    assert wait["phase"] == "input.next"
    assert wait["verdict"] == "thread:input.pull"
    assert 17 <= wait["step"] <= 20
    assert 0.3 < wait["seconds"] - wait["expected_s"] < 0.6
    assert said["over_expected_s"] == pytest.approx(
        sum(r["seconds"] - r["expected_s"] for r in (pull, wait)))


def test_threads_and_the_witness_do_not_trip_each_other(watch):
    """More threads than cores entering and leaving phases under a
    switch interval of 10 us while a witness wakes as fast as it can:
    nobody raises, every phase that was entered was left, and steady
    phases give no record."""
    import sys

    failed, stop = [], threading.Event()
    by_hand = stalls.Witness()

    def wake():
        try:
            while not stop.is_set():
                by_hand.tick()
        except Exception as e:      # what the test is looking for
            failed.append(repr(e))

    def work(i):
        try:
            for n in range(2000):
                with host_range("trainer", "place", launches=True):
                    with stalls.phase(f"input.ship{i % 3}"):
                        stalls.awaiting(n, None)
        except Exception as e:
            failed.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        waker = threading.Thread(target=wake)
        waker.start()
        workers = [threading.Thread(target=work, args=(i,), name=f"w{i}")
                   for i in range(24)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(60)
        stop.set()
        waker.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not waker.is_alive() and not any(t.is_alive() for t in workers)
    assert failed == []
    mine = [s for s in stalls._SLOTS.values()
            if s.thread.startswith("w")]
    assert all(s.phase is None and s.awaited == (1999, None) for s in mine)
    assert len(stalls._LENGTHS["trainer.place"]) == stalls._HISTORY
    assert stalls.log() == []


def test_the_watch_is_cheap(watch):
    """A loose guard: the profiler's range, the slot and the history of a
    phase, with the watch on."""
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with host_range("trainer", "rebind", launches=False):
            pass
    assert (time.perf_counter() - t0) / n < 5e-6
    assert stalls.log() == []
