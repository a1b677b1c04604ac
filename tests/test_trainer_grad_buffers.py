"""``ShardedTrainer`` computes and consumes its gradients inside its own
program, so at ``build`` it gives back the gradient buffer
``Parameter.initialize`` attached to each trainable (4 B a float32
parameter it never read or wrote).  Eager gradient code afterwards gets a
zero buffer again on demand."""
import gc

import jax
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu import observability as obs
from mxnet_tpu import parallel as par
from mxnet_tpu.gluon import nn


def _net(seed=0, grad_req=None):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu", in_units=32),
            nn.Dense(8, in_units=64))
    if grad_req:
        net.collect_params().setattr("grad_req", grad_req)
    net.initialize(mx.init.Xavier())
    return net


def _batch():
    rng = onp.random.default_rng(0)
    return (nd.array(rng.standard_normal((16, 32)).astype("float32")),
            nd.array(rng.integers(0, 8, (16,)).astype("float32")))


def _trainer(net, **kw):
    mesh = par.make_mesh(devices=jax.devices()[:1])
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    return par.ShardedTrainer(net, "adam", loss=lambda o, l: loss(o, l).mean(),
                              optimizer_params={"learning_rate": 1e-2},
                              mesh=mesh, **kw)


def _live_bytes():
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def _n_params(net):
    return sum(int(onp.prod(p.shape)) for p in net.collect_params().values())


def test_build_releases_every_trainable_gradient_buffer():
    net = _net()
    x, y = _batch()
    params = list(net.collect_params().values())
    assert all(p.data().grad is not None for p in params)
    before = _live_bytes()
    t = _trainer(net)
    t.build(x, y)
    after = _live_bytes()
    assert all(p.data().grad is None for p in params)
    n = _n_params(net)
    # Adam's two moments came (8 B a parameter), the buffers went (4 B)
    assert after - before == pytest.approx(8 * n - 4 * n, abs=4096)
    gauge = obs.registry.default_registry().gauge(
        "mxtpu_trainer_grad_buffer_bytes_released")
    assert gauge.value == 4 * n
    t.step(x, y)
    assert all(p.data().grad is None for p in params)   # a step brings none


def test_event_is_emitted_once_a_build():
    net = _net()
    x, y = _batch()
    tr = obs.enable_tracing()
    try:
        t = _trainer(net)
        t.build(x, y)
        t.step(x, y)
        t.step(x, y)
        events = tr.spans(name="trainer.grad_buffers")
    finally:
        obs.disable_tracing()
    assert len(events) == 1
    assert events[0].attrs == {"released_bytes": 4 * _n_params(net),
                               "parameters": 4}


def _null_grad():
    p = gluon.Parameter("w", shape=(2,), grad_req="null")
    p.initialize()
    return p.grad()


def test_parameters_that_are_not_trained_keep_what_they_had():
    net = _net()
    frozen = net[0].weight
    frozen.grad_req = "null"
    x, y = _batch()
    t = _trainer(net)
    t.build(x, y)
    assert frozen.data().grad is not None      # not the step's to release
    assert net[1].weight.data().grad is None
    with pytest.raises(mx.base.MXNetError, match="no gradient buffer"):
        _null_grad()                 # never had one: the error it was


def test_eager_gradient_code_still_works_after_build():
    net, twin = _net(), _net()
    x, y = _batch()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    t = _trainer(net)
    t.build(x, y)
    # the eager gradient of an untouched twin
    with autograd.record():
        l2 = loss(twin(x), y).mean()
    l2.backward()
    want = [p.grad().asnumpy() for p in twin.collect_params().values()]
    # grad() on a released buffer: zeros of the parameter's shape
    w = net[0].weight
    g = w.grad()
    assert g.shape == w.shape and float(abs(g.asnumpy()).max()) == 0.0
    assert w.grad() is g                       # attached again, once
    net.collect_params().zero_grad()           # released ones stay zero
    assert net[1].weight.data().grad is None
    with autograd.record():
        l1 = loss(net(x), y).mean()
    l1.backward()
    got = [p.grad().asnumpy() for p in net.collect_params().values()]
    for a, b in zip(got, want):
        onp.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_add_accumulates_from_the_zeros_a_released_buffer_stands_for():
    net, twin = _net(grad_req="add"), _net()
    x, y = _batch()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        l2 = loss(twin(x), y).mean()
    l2.backward()
    want = [p.grad().asnumpy() for p in twin.collect_params().values()]
    _trainer(net).build(x, y)
    assert all(p.data().grad is None for p in net.collect_params().values())
    for _ in range(2):
        with autograd.record():
            l1 = loss(net(x), y).mean()
        l1.backward()
    for p, b in zip(net.collect_params().values(), want):
        onp.testing.assert_allclose(p.grad().asnumpy(), 2 * b, rtol=1e-5,
                                    atol=1e-6)
    net.collect_params().zero_grad()
    assert all(float(abs(p.grad().asnumpy()).max()) == 0.0
               for p in net.collect_params().values())


def test_gluon_trainer_after_a_sharded_build():
    """A ``gluon.Trainer`` on a net a ``ShardedTrainer`` was built on:
    record, backward, step, as if nothing had been released."""
    net = _net()
    x, y = _batch()
    _trainer(net).build(x, y)
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    eager = gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1})
    w0 = net[1].weight.data().asnumpy().copy()
    with autograd.record():
        l1 = loss(net(x), y).mean()
    l1.backward()
    eager.step(1)
    assert abs(net[1].weight.data().asnumpy() - w0).max() > 1e-5


def test_step_is_bit_equal_with_and_without_a_prior_grad_call():
    x, y = _batch()
    out = []
    for touch in (False, True):
        net = _net(seed=3)
        t = _trainer(net)
        t.build(x, y)
        if touch:
            for p in net.collect_params().values():
                p.grad()
        losses = [t.step(x, y).asnumpy() for _ in range(3)]
        out.append((losses, [p.data().asnumpy()
                             for p in net.collect_params().values()]))
    for a, b in zip(out[0][0], out[1][0]):
        onp.testing.assert_array_equal(a, b)
    for a, b in zip(out[0][1], out[1][1]):
        onp.testing.assert_array_equal(a, b)


def test_compiled_accumulation_needs_no_buffer():
    """``grad_accum`` (the grad_req='add' idiom, compiled) accumulates
    inside the step: same update as one whole batch, no buffer attached."""
    x, y = _batch()
    out = []
    for accum in (1, 2):
        net = _net(seed=5)
        t = _trainer(net, grad_accum=accum)
        t.build(x, y)
        t.step(x, y)
        assert all(p.data().grad is None
                   for p in net.collect_params().values())
        out.append([p.data().asnumpy()
                    for p in net.collect_params().values()])
    for a, b in zip(*out):
        onp.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_trainer_gained_no_argument():
    import inspect

    names = set(inspect.signature(par.ShardedTrainer.__init__).parameters)
    assert names == {"self", "net", "optimizer", "loss", "optimizer_params",
                     "mesh", "rules", "data_specs", "label_specs",
                     "seq_axis", "donate", "donate_batch", "grad_accum",
                     "guard_nonfinite", "clip_global_norm", "loss_scaler"}
