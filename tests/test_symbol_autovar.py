"""1.x symbolic parity: auto-created parameter variables, partial shape
inference (nnvm InferShape role), and the classic loss-head ops
(SoftmaxOutput/LinearRegressionOutput) driving Module.fit."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.io import NDArrayIter


def test_auto_param_variables_and_names():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    assert fc.list_arguments() == ["data", "fc1_weight", "fc1_bias"]
    conv = mx.sym.Convolution(data, kernel=(3, 3), num_filter=4,
                              name="conv0")
    assert conv.list_arguments() == ["data", "conv0_weight", "conv0_bias"]
    nb = mx.sym.FullyConnected(data, num_hidden=8, no_bias=True,
                               name="fcn")
    assert nb.list_arguments() == ["data", "fcn_weight"]


def test_batchnorm_aux_states():
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(mx.sym.Convolution(
        data, kernel=(3, 3), num_filter=4, name="c"), name="bn")
    assert bn.list_auxiliary_states() == ["bn_moving_mean", "bn_moving_var"]
    assert "bn_moving_mean" not in bn.list_arguments()
    assert "bn_gamma" in bn.list_arguments()


def test_partial_shape_inference():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    arg_shapes, out_shapes, aux_shapes = net.infer_shape(data=(5, 7))
    d = dict(zip(net.list_arguments(), arg_shapes))
    assert d["fc1_weight"] == (16, 7)
    assert d["fc2_weight"] == (3, 16)
    assert out_shapes == [(5, 3)]


def test_partial_inference_conv_chain():
    data = mx.sym.Variable("data")
    c1 = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                            name="c1")
    bn = mx.sym.BatchNorm(c1, name="bn1")
    act = mx.sym.Activation(bn, act_type="relu")
    arg_shapes, out_shapes, aux_shapes = act.infer_shape(
        data=(2, 3, 16, 16))
    d = dict(zip(act.list_arguments(), arg_shapes))
    assert d["c1_weight"] == (8, 3, 3, 3)
    assert d["bn1_gamma"] == (8,)
    assert aux_shapes == [(8,), (8,)]
    assert out_shapes == [(2, 8, 16, 16)]


def test_simple_bind_with_auto_vars():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    ex = net.simple_bind(data=(3, 6))
    out = ex.forward(is_train=False, data=nd.array(
        onp.ones((3, 6), onp.float32)))
    assert out[0].shape == (3, 4)


def test_softmax_output_backward_is_p_minus_onehot():
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.ops import SoftmaxOutput
    rs = onp.random.RandomState(0)
    x = nd.array(rs.randn(4, 5).astype("f"))
    y = nd.array(onp.array([0, 2, 4, 1], "f"))
    x.attach_grad()
    with autograd.record():
        p = SoftmaxOutput(x, y)
    p.backward()
    probs = p.asnumpy()
    onehot = onp.eye(5, dtype="f")[[0, 2, 4, 1]]
    onp.testing.assert_allclose(x.grad.asnumpy(), probs - onehot,
                                rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_module_fit_with_classic_symbol():
    """The full 1.x idiom: auto-var symbol + SoftmaxOutput + Module.fit
    (with the upstream rescale_grad=1/batch default)."""
    rs = onp.random.RandomState(0)
    X = rs.randn(300, 1, 28, 28).astype("f") * 0.1
    y = rs.randint(0, 10, 300)
    X[onp.arange(300), 0, 0, y] += 3.0
    it = NDArrayIter(X, y.astype("f"), 50, shuffle=True,
                     last_batch_handle="discard")
    val = NDArrayIter(X, y.astype("f"), 50)
    data = mx.sym.Variable("data")
    flat = mx.sym.reshape(data, shape=(-1, 784))
    h = mx.sym.Activation(mx.sym.FullyConnected(
        flat, num_hidden=64, name="fc1"), act_type="relu")
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        h, num_hidden=10, name="fc2"), name="softmax")
    mod = mx.mod.Module(out, label_names=("softmax_label",))
    mod.fit(it, eval_data=val, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(),
            eval_metric="acc", num_epoch=6)
    acc = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.95, acc


def test_linear_regression_output_head():
    from mxnet_tpu import autograd
    from mxnet_tpu.ndarray.ops import LinearRegressionOutput
    x = nd.array(onp.array([[1.0, 2.0]], "f"))
    y = nd.array(onp.array([[0.5, 0.5]], "f"))
    x.attach_grad()
    with autograd.record():
        out = LinearRegressionOutput(x, y)
    out.backward()
    onp.testing.assert_allclose(out.asnumpy(), x.asnumpy())
    onp.testing.assert_allclose(x.grad.asnumpy(), [[0.5, 1.5]],
                                rtol=1e-6)


@pytest.mark.slow
def test_example_scripts_run(tmp_path):
    """example/ scripts run unmodified (the compatibility pledge)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    for script in ("train_mnist_gluon.py", "train_mnist_module.py"):
        r = subprocess.run(
            [sys.executable, os.path.join(repo, "example", script)],
            capture_output=True, text=True, timeout=560, env=env)
        assert r.returncode == 0, (script, r.stdout[-500:], r.stderr[-500:])
        assert "done" in r.stdout


def test_keyword_input_idiom():
    """mx.sym.FullyConnected(data=d, num_hidden=k) — the dominant
    GluonCV-era keyword calling form."""
    d = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data=d, num_hidden=10, name="fc2")
    assert fc.list_arguments() == ["data", "fc2_weight", "fc2_bias"]
    shapes, outs, _ = fc.infer_shape(data=(4, 8))
    assert dict(zip(fc.list_arguments(), shapes))["fc2_weight"] == (10, 8)
    # weight by keyword, data positional
    w = mx.sym.Variable("w", shape=(10, 8))
    fc2 = mx.sym.FullyConnected(d, weight=w, num_hidden=10, no_bias=True)
    assert fc2.list_arguments() == ["data", "w"]


def test_auto_name_matches_node_name():
    d = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(d, num_hidden=4)
    node_name = fc._name
    assert f"{node_name}_weight" in fc.list_arguments()


def test_loss_head_label_shape_inferred():
    """simple_bind with only the data shape: the label var's shape is
    back-inferred (upstream behavior)."""
    d = mx.sym.Variable("data")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, num_hidden=10, name="fc"),
        name="softmax")
    ex = out.simple_bind(data=(32, 784))
    assert "softmax_label" in ex.arg_dict
    assert tuple(ex.arg_dict["softmax_label"].shape) == (32,)


@pytest.mark.slow
def test_batchnorm_module_train_updates_moving_stats():
    """Symbolic BN: training updates moving stats (batch_norm.cc's aux
    mutation) so inference normalizes correctly — val accuracy survives
    the is_train=False switch."""
    rs = onp.random.RandomState(0)
    # data with strongly non-unit statistics so untrained moving stats
    # (mean 0 / var 1) would wreck inference
    X = (rs.randn(240, 3, 8, 8) * 5 + 7).astype("f")
    y = rs.randint(0, 4, 240)
    X[onp.arange(240), 0, 0, y] += 30.0
    it = NDArrayIter(X, y.astype("f"), 40, shuffle=True,
                     last_batch_handle="discard")
    val = NDArrayIter(X, y.astype("f"), 40)
    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, kernel=(3, 3), num_filter=8, pad=(1, 1),
                           name="c1")
    bn = mx.sym.BatchNorm(c, name="bn1")
    act = mx.sym.Activation(bn, act_type="relu")
    flat = mx.sym.reshape(act, shape=(0, -1))
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        flat, num_hidden=4, name="fc"), name="softmax")
    mod = mx.mod.Module(out, label_names=("softmax_label",))
    mod.fit(it, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.init.Xavier(), eval_metric="acc", num_epoch=4)
    # moving stats moved off their inits
    aux = mod._aux_params
    assert abs(aux["bn1_moving_mean"].asnumpy()).max() > 0.5
    assert abs(aux["bn1_moving_var"].asnumpy() - 1.0).max() > 0.5
    acc = dict(mod.score(val, mx.metric.Accuracy()))["accuracy"]
    assert acc > 0.9, acc


def test_user_supplied_moving_stats_classify_as_aux():
    d = mx.sym.Variable("data")
    mm = mx.sym.Variable("my_mean")
    mv = mx.sym.Variable("my_var")
    g = mx.sym.Variable("g")
    b = mx.sym.Variable("b")
    bn = mx.sym.BatchNorm(d, g, b, mm, mv, name="bn")
    assert bn.list_auxiliary_states() == ["my_mean", "my_var"]
    assert "my_mean" not in bn.list_arguments()


def test_batchnorm_output_mean_var_still_updates_moving_stats():
    """BN with output_mean_var=True must ALSO update moving stats during
    training (batch_norm.cc updates aux regardless of output_mean_var)."""
    rs = onp.random.RandomState(1)
    x = mx.nd.array((rs.randn(32, 6) * 4 + 5).astype("f"))
    d = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(d, output_mean_var=True, name="bnm")
    # use only the normalized output downstream; mean/var outputs exist
    loss = mx.sym.MakeLoss(mx.sym.mean(bn[0] * bn[0]))
    ex = loss.simple_bind(data=(32, 6))
    ex.copy_params_from({"bnm_gamma": mx.nd.ones((6,)),
                         "bnm_beta": mx.nd.zeros((6,)),
                         "bnm_moving_mean": mx.nd.zeros((6,)),
                         "bnm_moving_var": mx.nd.ones((6,))})
    ex.arg_dict["data"]._rebind(x.jax)
    ex.forward(is_train=True)
    mm = ex.arg_dict["bnm_moving_mean"].asnumpy()
    mv = ex.arg_dict["bnm_moving_var"].asnumpy()
    assert abs(mm).max() > 0.1, mm       # moved toward batch mean (~5)
    assert abs(mv - 1.0).max() > 0.1, mv


def test_multi_output_batchnorm_json_roundtrip():
    """num_outputs must survive tojson/load_json — a loaded multi-output
    BN node with default arity would hand consumers the whole tuple."""
    d = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(d, output_mean_var=True, name="bnr")
    loss = mx.sym.MakeLoss(mx.sym.mean(bn[0] * bn[0] + bn[1]))
    loaded = mx.sym.load_json(loss.tojson())
    ex = loaded.simple_bind(data=(4, 3))
    ex.arg_dict["data"]._rebind(
        mx.nd.array(onp.random.randn(4, 3).astype("f")).jax)
    out = ex.forward(is_train=True)
    assert out[0].shape == ()


def test_string_bool_attrs_from_upstream_json():
    """Upstream MXNet 1.x serializes every attr as a string; a loaded
    BatchNorm with output_mean_var='False' must stay single-output."""
    import json as _j
    d = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(d, name="bns")
    g = _j.loads(bn.tojson())
    for n in g["nodes"]:
        if n["op"] == "BatchNorm":
            n["attrs"]["output_mean_var"] = "False"   # upstream style
            n["attrs"]["use_global_stats"] = "False"
    loaded = mx.sym.load_json(_j.dumps(g))
    assert loaded.num_outputs == 1
    ex = loaded.simple_bind(data=(4, 3))
    out = ex.forward(is_train=True)
    assert len(out) == 1 and out[0].shape == (4, 3)
