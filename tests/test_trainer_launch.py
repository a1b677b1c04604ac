"""One launch a step: between one loss and the next launch of
``jit_trainer_step`` the trainer launches nothing else.  The step's
scalars and key are made on the host and shipped as transfers (behind the
last step's launch where they can be foretold), the key is the stream
``mx.random`` gives everyone else, a placed batch passes through, and none
of it compiles twice."""
import glob

import jax
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, observability as obs
from mxnet_tpu import parallel as par
from mxnet_tpu import random as mxrandom
from mxnet_tpu.data import DevicePrefetcher
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability.compiles import on_this_thread as xla_compiles

GUARDS = [{}, {"guard_nonfinite": True},
          {"clip_global_norm": 1.0, "loss_scaler": True}]
GUARD_IDS = ["unguarded", "guarded", "clip+scaler"]


def _trainer(dropout=0.0, lr=0.01, optimizer="adam", **guards):
    from mxnet_tpu import amp

    if guards.get("loss_scaler") is True:
        guards = dict(guards, loss_scaler=amp.LossScaler())
    mesh = par.make_mesh(devices=jax.devices()[:1])
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4))
    if dropout:
        net.add(nn.Dropout(dropout))
    net.add(nn.Dense(2, in_units=8))
    net.initialize()
    return par.ShardedTrainer(
        net, optimizer, loss=gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer_params={"learning_rate": lr}, mesh=mesh, **guards)


def _batches(n, rows=8, seed=0):
    rs = onp.random.RandomState(seed)
    return [(rs.randn(rows, 4).astype("float32"),
             (rs.randn(rows) > 0).astype("int32")) for _ in range(n)]


def _nd(batch):
    return tuple(nd.array(a) for a in batch)


def _record_calls(trainer):
    """Keep what every call of the compiled step was handed after its
    arrays: (key, lr, t, ...)."""
    seen = []
    inner = trainer._step_fn

    def recording(params, aux, states, batch, *scalars):
        seen.append(scalars)
        return inner(params, aux, states, batch, *scalars)

    trainer._step_fn = recording
    return seen


def _executables_in_profile(tmp_path, steps):
    """Run ``steps`` under ``jax.profiler`` on the CPU and count what the
    host handed to the backend: one ``PjRtCpuExecutable::Execute`` per
    executable launched, and the jitted functions called, by name."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        steps()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    launched, called = 0, set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "PjRtCpuExecutable::Execute":
                    launched += 1
                elif ev.name.startswith("PjitFunction("):
                    called.add(ev.name[len("PjitFunction("):-1])
    return launched, called


# ------------------------------------------------------------ (a) one launch
@pytest.mark.parametrize("guards", GUARDS, ids=GUARD_IDS)
def test_a_warm_step_launches_one_executable(guards, tmp_path):
    obs.disable_tracing()
    trainer = _trainer(dropout=0.5, **guards)
    batches = _batches(5)
    trainer.build(*_nd(batches[0]))
    feed = DevicePrefetcher(iter(batches), shardings=trainer.batch_shardings)
    try:
        def step():
            data, labels = next(feed)
            out = trainer.step(data, labels)
            (out[0] if isinstance(out, tuple) else out).asnumpy()

        step()
        step()                                  # compile outside the trace
        launched, called = _executables_in_profile(
            tmp_path, lambda: [step() for _ in range(3)])
    finally:
        feed.close()
    assert called == {"trainer_step"}
    assert launched == 3


def test_the_count_sees_a_second_executable(tmp_path, monkeypatch):
    """The control of the test above: a trainer whose learning rate is
    made on the device again, as before, launches more than the step
    (the scalars are made twice a step: for it, and ahead for the next),
    and the same count says so."""
    import jax.numpy as jnp

    obs.disable_tracing()
    trainer = _trainer()
    batches = [_nd(b) for b in _batches(4)]
    host_scalars = trainer._host_scalars

    def device_lr(key, t, *poisons):
        key, lr, t = host_scalars(key, t, *poisons)
        return key, jnp.asarray(float(lr), jnp.float32), t

    monkeypatch.setattr(trainer, "_host_scalars", device_lr)
    for b in batches[:2]:
        trainer.step(*b).asnumpy()
    launched, called = _executables_in_profile(
        tmp_path, lambda: [trainer.step(*b).asnumpy() for b in batches[2:]])
    assert launched == 2 + 4 and called == {"trainer_step",
                                            "convert_element_type"}


# ------------------------------------------------------- (b) the key's stream
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_step_key_is_the_next_key_of_the_stream(seed):
    """Seeded alike, the keys three steps use are the keys three
    ``next_key()`` calls give (``fold_in(root, c)`` for c = 0, 1, 2), the
    counter moves by one a step, and whoever draws next draws what they
    would have drawn behind three ``next_key()`` calls."""
    trainer = _trainer(dropout=0.5)
    batches = [_nd(b) for b in _batches(4)]
    trainer.step(*batches[0]).asnumpy()
    seen = _record_calls(trainer)
    ctx = mx.current_context()
    state = mxrandom.get_state()

    mx.random.seed(seed)
    want = [onp.asarray(mxrandom.next_key()) for _ in range(3)]
    want_draw = nd.random_uniform(shape=(5,)).asnumpy()

    mx.random.seed(seed)
    counters, losses = [], []
    for b in batches[1:]:
        before = state._counters.get(ctx, 0)
        losses.append(float(trainer.step(*b).asnumpy()))
        counters.append(state._counters[ctx] - before)
    draw = nd.random_uniform(shape=(5,)).asnumpy()

    assert counters == [1, 1, 1]
    for (key, _lr, _t), k in zip(seen, want):
        assert key.dtype == onp.uint32 and key.shape == (2,)
        assert onp.asarray(key).tolist() == k.tolist()
    assert draw.tolist() == want_draw.tolist()


def test_same_seed_same_dropout_losses():
    """The key reaches the dropout mask: twins seeded alike lose alike,
    and another seed gives other losses."""
    def losses(seed):
        trainer = _trainer(dropout=0.5)
        for p, v in zip(trainer.net.collect_params().values(), weights):
            p.set_data(nd.array(v))
        mx.random.seed(seed)
        return [float(trainer.step(*_nd(b)).asnumpy()) for b in _batches(3)]

    probe = _trainer(dropout=0.5)
    weights = [p.data().asnumpy() for p in
               probe.net.collect_params().values()]
    assert losses(3) == losses(3)
    assert losses(3) != losses(4)


# ------------------------------------- (c) changes between steps, no compile
@pytest.mark.parametrize("guards", GUARDS[:2], ids=GUARD_IDS[:2])
def test_lr_and_seed_between_steps_take_effect_without_a_compile(guards):
    trainer = _trainer(dropout=0.5, lr=0.01, **guards)
    batches = [_nd(b) for b in _batches(5)]
    trainer.step(*batches[0])
    trainer.step(*batches[1])
    seen = _record_calls(trainer)
    mx.random.seed(11)
    want = onp.asarray(mxrandom.next_key())     # its programs compile here
    total = obs.default_registry().counter("mxtpu_xla_compiles_total")
    total0, mine0 = total.value, xla_compiles()

    trainer.step(*batches[2])
    trainer.set_learning_rate(0.5)
    trainer.step(*batches[3])
    mx.random.seed(11)
    out = trainer.step(*batches[4])
    (out[0] if isinstance(out, tuple) else out).asnumpy()

    assert [float(s[1]) for s in seen] == [
        float(onp.float32(0.01)), 0.5, 0.5]
    assert [int(s[2]) for s in seen] == [3, 4, 5]
    assert onp.asarray(seen[2][0]).tolist() == want.tolist()
    for scalars in seen:
        assert [(v.dtype, v.weak_type) for v in scalars[1:3]] == [
            (onp.float32, False), (onp.int32, False)]
        assert all((p.dtype, p.weak_type) == (onp.float32, False)
                   for p in scalars[5:])
        assert all(v.committed for v in scalars)
    assert total.value == total0 and xla_compiles() == mine0


def test_a_poisoned_step_is_skipped_without_a_compile():
    """The poisons are host scalars too: a fault plan's NaN travels in
    the step's call, the flag says so, and nothing compiles for it."""
    from mxnet_tpu.resilience import FaultPlan

    trainer = _trainer(guard_nonfinite=True)
    batches = [_nd(b) for b in _batches(4)]
    for b in batches[:2]:
        trainer.step(*b)
    mine0 = xla_compiles()
    with FaultPlan().nonfinite_at("trainer.grad_nonfinite", at=1):
        _loss, bad = trainer.step(*batches[2])
        _loss, good = trainer.step(*batches[3])
    assert not bool(bad.asnumpy()) and bool(good.asnumpy())
    assert xla_compiles() == mine0


def test_a_foretold_step_transfers_nothing_before_its_launch(monkeypatch):
    """Behind its launch a step ships the count and key the next step
    will need if nothing happens in between; that step then finds them
    (and the unchanged rate) on the device and puts nothing.  A draw or
    a new rate in between costs it one transfer each, and the values it
    runs with are the right ones either way."""
    from mxnet_tpu.parallel import trainer as trainer_mod

    trainer = _trainer(dropout=0.5)
    batches = [_nd(b) for b in _batches(6)]
    trainer.step(*batches[0])
    puts = []
    put = trainer_mod._mesh_device_put

    def counting(value, sharding):
        puts.append(value)
        return put(value, sharding)

    inner = trainer._step_fn
    before_launch, handed = [], []

    def launching(params, aux, states, batch, *scalars):
        before_launch.append(len(puts))
        handed.append(scalars)
        del puts[:]
        return inner(params, aux, states, batch, *scalars)

    trainer._step_fn = launching
    monkeypatch.setattr(trainer_mod, "_mesh_device_put", counting)

    def step(i):
        del puts[:]
        # the feed's part: a batch already where the step wants it
        placed = trainer._device_args(*[(a,) for a in batches[i]])[3]
        del puts[:]
        trainer.step(nd.NDArray(placed[0]), nd.NDArray(placed[1]))
        return len(puts)                    # what it shipped ahead

    mx.random.seed(3)
    step(1)                                 # a new root: the key is put
    assert step(2) == 2 and before_launch[-1] == 0
    assert step(3) == 2 and before_launch[-1] == 0
    mxrandom.next_key()                     # someone draws in between
    want = onp.asarray(mxrandom.next_key_words(advance=False)).tolist()
    step(4)
    assert before_launch[-1] == 1
    assert onp.asarray(handed[-1][0]).tolist() == want
    trainer.set_learning_rate(0.25)
    step(5)
    assert before_launch[-1] == 1
    assert float(handed[-1][1]) == 0.25 and int(handed[-1][2]) == 6


# ------------------------------------------------------- (d) one compile, ever
@pytest.mark.parametrize("guards", GUARDS, ids=GUARD_IDS)
def test_build_lower_and_steps_compile_once(guards):
    trainer = _trainer(dropout=0.5, **guards)
    batches = [_nd(b) for b in _batches(4)]
    mine0 = xla_compiles()
    trainer.build(*batches[0])
    trainer.lower_step(*batches[0])
    for b in batches[1:]:
        out = trainer.step(*b)
    (out[0] if isinstance(out, tuple) else out).asnumpy()
    assert xla_compiles() - mine0 == 1
    assert trainer.optimizer.num_update == 3


@pytest.mark.parametrize("counter", [0, 1, 2, 1000, 2 ** 31, 2 ** 32 - 1])
@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 31 + 5,
                                  2 ** 40 + 3, -1])
def test_host_key_is_jax_fold_in(seed, counter):
    root = mxrandom._seed_words(seed)
    assert list(root) == onp.asarray(jax.random.PRNGKey(seed)).tolist()
    made = mxrandom._fold_in_words(root, counter)
    want = onp.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), counter))
    assert made.dtype == want.dtype and made.shape == want.shape
    assert made.tolist() == want.tolist()


def test_host_keys_and_device_keys_share_one_counter():
    """Interleaved, the two ways of drawing hand out one stream: every
    draw takes the next counter, whichever way it came."""
    mx.random.seed(21)
    want = [onp.asarray(mxrandom.next_key()).tolist() for _ in range(4)]
    mx.random.seed(21)
    got = [mxrandom.next_key_words().tolist(),
           onp.asarray(mxrandom.next_key()).tolist(),
           mxrandom.next_key_words().tolist(),
           onp.asarray(mxrandom.next_key()).tolist()]
    assert got == want
    ctx = mx.cpu(3)
    mx.random.seed(5, ctx=ctx)
    want = onp.asarray(mxrandom.next_key(ctx)).tolist()
    mx.random.seed(5, ctx=ctx)
    assert mxrandom.next_key_words(ctx).tolist() == want


def test_another_prng_impl_keeps_the_device_key():
    """Only threefry is folded on the host.  Under another default
    implementation the step's key is ``next_key()``'s, as it always was,
    and nothing is foretold of it."""
    jax.config.update("jax_default_prng_impl", "rbg")
    try:
        mx.random.seed(2)
        assert mxrandom.next_key_words(advance=False) is None
        key = mxrandom.next_key_words()
        assert isinstance(key, jax.Array) and key.shape == (4,)
        trainer = _trainer(dropout=0.5)
        losses = [float(trainer.step(*_nd(b)).asnumpy())
                  for b in _batches(3)]
        assert all(onp.isfinite(losses))
    finally:
        jax.config.update("jax_default_prng_impl", "threefry2x32")
        mx.random.seed(0)


# ------------------------------------------------------------ (e) batch_puts
def test_batch_puts_counts_only_what_the_trainer_placed():
    trainer = _trainer()
    batches = _batches(6)
    trainer.build(*_nd(batches[0]))
    assert trainer.stats()["batch_puts"] == 0
    feed = DevicePrefetcher(iter(batches[:3]),
                            shardings=trainer.batch_shardings)
    trainer.attach_data_source(feed)
    try:
        placed = []
        for data, labels in feed:
            placed.append((data.jax, labels.jax))
            trainer.step(data, labels).asnumpy()
    finally:
        feed.close()
    stats = trainer.stats()
    assert stats["batch_puts"] == 0 and stats["data"]["batches_shipped"] == 3
    # the arrays the feed placed are the ones the step was handed
    args = trainer._device_args((nd.NDArray(placed[-1][0]),),
                                (nd.NDArray(placed[-1][1]),))[3]
    assert args[0] is placed[-1][0] and args[1] is placed[-1][1]
    assert trainer.stats()["batch_puts"] == 0
    # host-fed (uncommitted) arrays are placed here, two a step
    for b in batches[3:5]:
        trainer.step(*_nd(b)).asnumpy()
    assert trainer.stats()["batch_puts"] == 4
    # a feed that ships to no sharding leaves the placing to the trainer
    bare = DevicePrefetcher(iter(batches[5:]))
    try:
        data, labels = next(bare)
        trainer.step(data, labels).asnumpy()
    finally:
        bare.close()
    assert trainer.stats()["batch_puts"] == 6
