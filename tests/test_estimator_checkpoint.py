"""Estimator + checkpoint/resume tests (parity model: test_gluon_estimator.py
+ model_backwards_compatibility_check)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                               EarlyStoppingHandler,
                                               Estimator, LoggingHandler)


def _toy_loader(n=64, batch=16, seed=0):
    rs = onp.random.RandomState(seed)
    X = rs.randn(n, 6).astype("float32")
    y = (X.sum(1) > 0).astype("float32")
    ds = gluon.data.ArrayDataset(nd.array(X), nd.array(y))
    return gluon.data.DataLoader(ds, batch_size=batch)


def _net():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    return net


def test_estimator_fit():
    # the draw is the process's: unseeded, the accuracy depended on which
    # test files had shared this worker (0.797 against > 0.8 was seen)
    mx.random.seed(0)
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "adam",
                                          {"learning_rate": 0.01}))
    est.fit(_toy_loader(), epochs=5)
    name, acc = est.train_metrics[0].get()
    assert name == "accuracy"
    assert acc > 0.8, acc
    lname, lval = est.train_loss_metric.get()
    assert lval < 0.7


def test_estimator_validation_and_early_stop():
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "adam",
                                          {"learning_rate": 0.01}))
    stopper = EarlyStoppingHandler(monitor=est.val_metrics[0], patience=2,
                                   mode="max")
    est.fit(_toy_loader(), val_data=_toy_loader(seed=1), epochs=50,
            event_handlers=[stopper])
    assert stopper.current_epoch < 50  # stopped early


def test_estimator_checkpoint(tmp_path):
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    ckpt = CheckpointHandler(str(tmp_path), model_prefix="m",
                             epoch_period=1, max_checkpoints=2)
    est.fit(_toy_loader(), epochs=3, event_handlers=[ckpt])
    files = sorted(os.listdir(tmp_path))
    assert any(f.endswith(".params") for f in files)
    # max_checkpoints enforced
    assert len([f for f in files if f.endswith(".params")]) <= 2
    # reload
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net2.load_parameters(os.path.join(
        str(tmp_path), [f for f in files if f.endswith(".params")][-1]))
    x = nd.array(onp.ones((2, 6), "float32"))
    onp.testing.assert_allclose(net(x).asnumpy(), net2(x).asnumpy(),
                                rtol=1e-6)


def test_orbax_checkpoint_roundtrip(tmp_path):
    from mxnet_tpu.utils.checkpoint import (CheckpointManager,
                                            load_checkpoint, save_checkpoint)
    tree = {"w": nd.array(onp.arange(6, dtype="float32").reshape(2, 3)),
            "b": nd.array(onp.array([1.0, 2.0], "float32"))}
    save_checkpoint(str(tmp_path / "ckpt"), 3, tree)
    restored = load_checkpoint(str(tmp_path / "ckpt"), like=tree)
    onp.testing.assert_allclose(onp.asarray(restored["w"]),
                                tree["w"].asnumpy())
    onp.testing.assert_allclose(onp.asarray(restored["b"]),
                                tree["b"].asnumpy())


def test_orbax_manager_steps(tmp_path):
    from mxnet_tpu.utils.checkpoint import CheckpointManager
    m = CheckpointManager(str(tmp_path / "run"), max_to_keep=2,
                          async_save=True)
    tree = {"x": nd.array(onp.ones(4, "float32"))}
    for s in (1, 2, 3):
        tree["x"] *= 2.0
        m.save(s, tree)
    m.wait_until_finished()
    assert m.latest_step() == 3
    assert len(m.all_steps()) <= 2  # max_to_keep
    restored = m.restore(3, like=tree)
    onp.testing.assert_allclose(onp.asarray(restored["x"]),
                                tree["x"].asnumpy())
    m.close()


def test_sharded_trainer_checkpoint(tmp_path):
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs multi-device mesh (conftest forces 8 cpu)")
    from mxnet_tpu import parallel as par
    mesh = par.make_mesh(dp=2, devices=jax.devices()[:2])

    def make(seed):
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(2))
        net.initialize(mx.init.Xavier())
        return net

    X = nd.array(onp.random.RandomState(0).randn(8, 4).astype("float32"))
    y = nd.array(onp.zeros(8, "int32"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    with par.use_mesh(mesh):
        net = make(0)
        tr = par.ShardedTrainer(net, "adam", loss=loss_fn,
                                optimizer_params={"learning_rate": 0.01})
        for _ in range(3):
            tr.step((X,), (y,))
        mgr = tr.save_checkpoint(str(tmp_path / "shard"), step=3)
        mgr.wait_until_finished()
        mgr.close()
        w_before = {n: p.data().asnumpy() for n, p in tr._trainable}
        nu_before = tr.optimizer.num_update

        # perturb, then restore
        for _ in range(2):
            tr.step((X,), (y,))
        tr.load_checkpoint(str(tmp_path / "shard"))
        for n, p in tr._trainable:
            onp.testing.assert_allclose(p.data().asnumpy(), w_before[n],
                                        rtol=1e-6)
        assert tr.optimizer.num_update == nu_before


def test_fit_requires_stopping_criterion():
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    with pytest.raises(ValueError):
        est.fit(_toy_loader())            # no epochs, no batches
    est.fit(_toy_loader(), epochs=0)      # trains nothing, terminates
    est.fit(_toy_loader(), batches=3)     # batch-bounded run terminates


def test_validation_runs_before_monitors():
    # ValidationHandler (priority -1000) must fire before the early stopper
    # reads val metrics: with a fresh estimator the first epoch_end would
    # otherwise see an empty (nan) metric and stop instantly.
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "adam",
                                          {"learning_rate": 0.01}))
    stopper = EarlyStoppingHandler(monitor=est.val_metrics[0], patience=0,
                                   mode="max")
    est.fit(_toy_loader(), val_data=_toy_loader(seed=1), epochs=3,
            event_handlers=[stopper])
    n, v = est.val_metrics[0].get()
    assert not onp.isnan(v)
    # second fit on the same handler starts from a clean slate
    est.fit(_toy_loader(), val_data=_toy_loader(seed=1), epochs=2,
            event_handlers=[stopper])
    assert stopper.current_epoch >= 1


def test_checkpoint_best_survives_rotation(tmp_path):
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "adam",
                                          {"learning_rate": 0.01}))
    ckpt = CheckpointHandler(str(tmp_path), model_prefix="m", epoch_period=1,
                             max_checkpoints=2, save_best=True, mode="max",
                             monitor=est.train_metrics[0])
    est.fit(_toy_loader(), epochs=6, event_handlers=[ckpt])
    assert os.path.exists(os.path.join(tmp_path, "m-best.params"))
    kept = [f for f in os.listdir(tmp_path)
            if f.startswith("m-epoch") and f.endswith(".params")]
    assert len(kept) == 2  # rotation still bounded


def test_val_metric_copies_config():
    from mxnet_tpu import metric as mmetric
    net = _net()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=mmetric.TopKAccuracy(top_k=2))
    assert est.val_metrics[0].top_k == 2


def test_gradient_update_and_metric_handlers_overridable():
    """2.x parity: the optimizer step and metric updates are handlers a
    user can replace (e.g. gradient accumulation every 2 batches)."""
    from mxnet_tpu.gluon.contrib.estimator import (Estimator,
                                                   GradientUpdateHandler,
                                                   MetricHandler)

    class EveryTwo(GradientUpdateHandler):
        def __init__(self):
            self.count = 0

        def batch_end(self, estimator, *args, **kwargs):
            self.count += 1
            if self.count % 2 == 0:
                estimator.trainer.step(2 * estimator._batch_size)

    net = nn.Dense(2, in_units=4)
    net.initialize()
    X = onp.random.randn(32, 4).astype("f")
    Y = onp.random.randint(0, 2, (32,))
    data = [(mx.nd.array(X[i:i+8]), mx.nd.array(Y[i:i+8]))
            for i in range(0, 32, 8)]
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=trainer)
    h = EveryTwo()
    w0 = net.weight.data().asnumpy().copy()
    est.fit(data, epochs=1, event_handlers=[h])
    assert h.count == 4                      # saw every batch
    assert not onp.allclose(w0, net.weight.data().asnumpy())
    # default MetricHandler updated train metrics
    assert est.train_loss_metric.num_inst > 0
