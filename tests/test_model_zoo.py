"""Vision model zoo (parity: python/mxnet/gluon/model_zoo/vision +
tests/python/unittest/test_gluon_model_zoo.py — build every model, run a
forward pass, check the output head).
"""
import numpy as onp
import pytest

pytestmark = pytest.mark.slow

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import get_model

# small spatial input keeps CPU runtime sane; AlexNet/VGG need >= 224-ish
# strides, so give each family an adequate size
_CASES = [
    ("resnet18_v1", 64), ("resnet50_v2", 64),
    ("alexnet", 224),
    ("vgg11", 64), ("vgg13_bn", 64),
    ("squeezenet1_0", 224), ("squeezenet1_1", 224),
    ("densenet121", 64),
    ("mobilenet1_0", 64), ("mobilenet0_25", 64),
    ("mobilenet_v2_1_0", 64), ("mobilenet_v2_0_5", 64),
    ("inception_v3", 128),
]


@pytest.mark.parametrize("name,size", _CASES)
def test_model_forward(name, size):
    net = get_model(name, classes=10)
    net.initialize(mx.init.Xavier())
    x = nd.array(onp.random.RandomState(0).randn(2, 3, size, size)
                 .astype("float32"))
    out = net(x)
    assert out.shape == (2, 10)
    assert onp.isfinite(out.asnumpy()).all()


def test_model_zoo_registry_complete():
    from mxnet_tpu.models.vision import _models
    for family in ("alexnet", "vgg16", "vgg19_bn", "squeezenet1_1",
                   "densenet201", "mobilenet0_5", "mobilenet_v2_0_75",
                   "resnet152_v2", "inception_v3"):
        assert family in _models
    with pytest.raises(ValueError):
        get_model("resnet20_v9")


def test_model_zoo_hybridize_matches_eager():
    net = get_model("mobilenet_v2_0_25", classes=7)
    net.initialize(mx.init.Xavier())
    x = nd.array(onp.random.RandomState(1).randn(2, 3, 64, 64)
                 .astype("float32"))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    onp.testing.assert_allclose(hybrid, eager, rtol=1e-4, atol=1e-4)


def test_model_zoo_save_load_roundtrip(tmp_path):
    net = get_model("squeezenet1_1", classes=5)
    net.initialize(mx.init.Xavier())
    x = nd.array(onp.random.RandomState(2).randn(1, 3, 224, 224)
                 .astype("float32"))
    ref = net(x).asnumpy()
    f = str(tmp_path / "m.params")
    net.save_parameters(f)
    net2 = get_model("squeezenet1_1", classes=5)
    net2.load_parameters(f)
    onp.testing.assert_allclose(net2(x).asnumpy(), ref, rtol=1e-5,
                                atol=1e-5)


@pytest.mark.parametrize("version,layers", [(1, 18), (2, 50)])
def test_resnet_nhwc_matches_nchw(version, layers):
    """layout='NHWC' (the channels-last form; its speed on the chip is
    not measured) must be numerically identical to NCHW given the same OIHW
    weights (docs/resnet_roofline_r05.md)."""
    from mxnet_tpu import autograd
    from mxnet_tpu.models.vision import get_resnet

    rs = onp.random.RandomState(0)
    x_nchw = rs.randn(2, 3, 32, 32).astype("float32")
    xc, xh = nd.array(x_nchw), nd.array(x_nchw.transpose(0, 2, 3, 1))

    net_c = get_resnet(version, layers, classes=10, thumbnail=True)
    net_c.initialize()
    net_c(xc)
    net_h = get_resnet(version, layers, classes=10, thumbnail=True,
                       layout="NHWC")
    net_h.initialize()
    net_h(xh)
    # same build order -> same param sequence; weights are OIHW in BOTH
    # layouts so they copy across directly (checkpoint compatibility)
    for vc, vh in zip(net_c.collect_params().values(),
                      net_h.collect_params().values()):
        assert vc.shape == vh.shape
        vh.set_data(vc.data())
    onp.testing.assert_allclose(net_c(xc).asnumpy(), net_h(xh).asnumpy(),
                                rtol=3e-4, atol=3e-4)
    with autograd.record():
        loss = (net_h(xh) ** 2).sum()
    loss.backward()
    g = net_h.collect_params()
    assert all(onp.isfinite(v.grad().asnumpy()).all()
               for v in g.values() if v.grad_req != "null")
