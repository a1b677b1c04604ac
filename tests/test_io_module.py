"""mx.io / mx.recordio / mx.mod tests (parity model: test_io.py,
test_recordio.py, test_module.py in tests/python/unittest)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.io import DataBatch, DataDesc, NDArrayIter, PrefetchingIter, \
    ResizeIter
from mxnet_tpu.recordio import (IRHeader, MXIndexedRecordIO, MXRecordIO,
                                pack, pack_img, unpack, unpack_img)


# ------------------------------------------------------------- recordio

def test_recordio_roundtrip(tmp_path):
    f = str(tmp_path / "test.rec")
    w = MXRecordIO(f, "w")
    payloads = [bytes([i]) * (i + 1) for i in range(10)]
    for p in payloads:
        w.write(p)
    w.close()
    r = MXRecordIO(f, "r")
    for p in payloads:
        assert r.read() == p
    assert r.read() is None
    r.close()


def test_indexed_recordio(tmp_path):
    f = str(tmp_path / "t.rec")
    idx = str(tmp_path / "t.idx")
    w = MXIndexedRecordIO(idx, f, "w")
    for i in range(20):
        w.write_idx(i, f"record{i}".encode())
    w.close()
    r = MXIndexedRecordIO(idx, f, "r")
    assert r.keys == list(range(20))
    assert r.read_idx(13) == b"record13"
    assert r.read_idx(2) == b"record2"
    r.close()


def test_pack_unpack():
    hdr = IRHeader(0, 3.0, 7, 0)
    s = pack(hdr, b"payload")
    h2, data = unpack(s)
    assert data == b"payload"
    assert h2.label == 3.0 and h2.id == 7
    # array label
    hdr = IRHeader(0, onp.array([1.0, 2.0], dtype=onp.float32), 0, 0)
    h3, data = unpack(pack(hdr, b"xy"))
    onp.testing.assert_allclose(h3.label, [1.0, 2.0])
    assert data == b"xy"


def test_pack_img_roundtrip():
    img = (onp.random.RandomState(0).rand(32, 32, 3) * 255).astype(onp.uint8)
    s = pack_img(IRHeader(0, 1.0, 0, 0), img, quality=100, img_fmt=".png")
    hdr, img2 = unpack_img(s)
    assert img2.shape == (32, 32, 3)
    onp.testing.assert_array_equal(img, img2)  # png is lossless


# ------------------------------------------------------------------- io

def test_ndarray_iter():
    data = onp.arange(40, dtype=onp.float32).reshape(10, 4)
    label = onp.arange(10, dtype=onp.float32)
    it = NDArrayIter(data, label, batch_size=3, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (3, 4)
    assert batches[-1].pad == 2
    onp.testing.assert_allclose(batches[0].data[0].asnumpy(), data[:3])
    # discard mode
    it2 = NDArrayIter(data, label, batch_size=3,
                      last_batch_handle="discard")
    assert len(list(it2)) == 3
    # reset + iterate again
    it2.reset()
    assert len(list(it2)) == 3


def test_ndarray_iter_shuffle():
    data = onp.arange(100, dtype=onp.float32).reshape(100, 1)
    it = NDArrayIter(data, data[:, 0], batch_size=10, shuffle=True)
    seen = onp.concatenate([b.data[0].asnumpy()[:, 0] for b in it])
    assert sorted(seen.tolist()) == list(range(100))


def test_provide_data():
    it = NDArrayIter(onp.zeros((8, 3, 2), dtype=onp.float32),
                     onp.zeros(8), batch_size=4)
    d = it.provide_data[0]
    assert d.name == "data" and d.shape == (4, 3, 2)


def test_prefetching_iter():
    data = onp.arange(32, dtype=onp.float32).reshape(16, 2)
    base = NDArrayIter(data, onp.zeros(16), batch_size=4)
    it = PrefetchingIter(base)
    n = sum(1 for _ in it)
    assert n == 4
    it.reset()
    assert sum(1 for _ in it) == 4


def test_resize_iter():
    data = onp.zeros((8, 2), dtype=onp.float32)
    base = NDArrayIter(data, onp.zeros(8), batch_size=4)
    it = ResizeIter(base, 5)
    assert sum(1 for _ in it) == 5


def test_image_record_iter(tmp_path):
    from mxnet_tpu.io import ImageRecordIter
    rec_f = str(tmp_path / "img.rec")
    idx_f = str(tmp_path / "img.idx")
    w = MXIndexedRecordIO(idx_f, rec_f, "w")
    rs = onp.random.RandomState(0)
    for i in range(8):
        img = (rs.rand(40, 40, 3) * 255).astype(onp.uint8)
        w.write_idx(i, pack_img(IRHeader(0, float(i % 2), i, 0), img,
                                img_fmt=".png"))
    w.close()
    it = ImageRecordIter(path_imgrec=rec_f, path_imgidx=idx_f,
                         data_shape=(3, 32, 32), batch_size=4,
                         rand_crop=True, rand_mirror=True)
    batch = it.next()
    assert batch.data[0].shape == (4, 3, 32, 32)
    assert batch.label[0].shape == (4,)


def test_mnist_iter_synthetic():
    from mxnet_tpu.io import MNISTIter
    it = MNISTIter(batch_size=32, flat=True)
    b = it.next()
    assert b.data[0].shape == (32, 784)
    assert it.synthetic  # no raw files in the sandbox


# ---------------------------------------------------------------- module

def _mlp_symbol():
    sym = mx.sym
    data = sym.Variable("data")
    w1 = sym.Variable("fc1_weight", shape=(32, 4))
    b1 = sym.Variable("fc1_bias", shape=(32,))
    fc1 = sym.FullyConnected(data, w1, b1, num_hidden=32, name="fc1")
    act = sym.relu(fc1)
    w2 = sym.Variable("fc2_weight", shape=(3, 32))
    b2 = sym.Variable("fc2_bias", shape=(3,))
    fc2 = sym.FullyConnected(act, w2, b2, num_hidden=3, name="fc2")
    loss = sym.softmax_cross_entropy(fc2, sym.Variable("softmax_label"))
    return fc2, loss


def _toy_data(n=96, seed=0):
    rs = onp.random.RandomState(seed)
    X = rs.randn(n, 4).astype(onp.float32)
    y = (X.sum(axis=1) > 0).astype(onp.float32) + \
        (X[:, 0] > 1).astype(onp.float32)
    return X, y


@pytest.mark.slow
def test_module_train():
    from mxnet_tpu.module import Module
    _, loss = _mlp_symbol()
    X, y = _toy_data()
    it = NDArrayIter(X, y, batch_size=16, last_batch_handle="discard")
    mod = Module(loss, data_names=("data",),
                 label_names=("softmax_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer(optimizer="adam",
                       optimizer_params=(("learning_rate", 0.01),))
    first_loss = None
    for epoch in range(12):
        it.reset()
        tot, nb = 0.0, 0
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            tot += float(mod.get_outputs()[0].asnumpy().mean())
            nb += 1
        if first_loss is None:
            first_loss = tot / nb
    assert tot / nb < first_loss * 0.7, (first_loss, tot / nb)


def test_module_fit_and_score():
    from mxnet_tpu.module import Module
    sym = mx.sym
    data = sym.Variable("data")
    w = sym.Variable("fc_weight", shape=(3, 4))
    b = sym.Variable("fc_bias", shape=(3,))
    logits = sym.FullyConnected(data, w, b, num_hidden=3)
    out = sym.softmax(logits, axis=-1)
    X, y = _toy_data(128)
    it = NDArrayIter(X, y, batch_size=16, last_batch_handle="discard")

    mod = Module(out, label_names=("softmax_label",))
    # fit with a loss-symbol-free softmax output: use custom training below
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    acc = mod.score(it, "acc")
    assert acc[0][0] == "accuracy"


def test_module_checkpoint(tmp_path):
    from mxnet_tpu.module import Module
    _, loss = _mlp_symbol()
    X, y = _toy_data(32)
    it = NDArrayIter(X, y, batch_size=16)
    mod = Module(loss, label_names=("softmax_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.init.Xavier())
    mod.init_optimizer()
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 3)
    assert os.path.exists(f"{prefix}-symbol.json")
    assert os.path.exists(f"{prefix}-0003.params")

    mod2 = Module.load(prefix, 3, label_names=("softmax_label",))
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod2.init_params()
    a1, _ = mod.get_params()
    a2, _ = mod2.get_params()
    for k in a1:
        onp.testing.assert_allclose(a1[k].asnumpy(), a2[k].asnumpy())


def test_bucketing_module():
    from mxnet_tpu.module import BucketingModule
    sym = mx.sym

    def sym_gen(seq_len):
        data = sym.Variable("data")
        w = sym.Variable("w", shape=(2, 8))
        fc = sym.FullyConnected(
            sym.reshape(data, shape=(-1, 8)), w, None, num_hidden=2,
            no_bias=True)
        return sym.softmax(fc, axis=-1), ("data",), ()

    bm = BucketingModule(sym_gen, default_bucket_key=8)
    batch8 = DataBatch([nd.array(onp.ones((4, 8), onp.float32))],
                       provide_data=[DataDesc("data", (4, 8))],
                       provide_label=[])
    bm.bind(data_shapes=[DataDesc("data", (4, 8))])
    bm.init_params(initializer=mx.init.Xavier())
    bm.forward(batch8, is_train=False)
    out8 = bm.get_outputs()[0]
    assert out8.shape == (4, 2)

    batch16 = DataBatch([nd.array(onp.ones((4, 16), onp.float32))],
                        provide_data=[DataDesc("data", (4, 16))],
                        provide_label=[])
    batch16.bucket_key = 16
    bm.forward(batch16, is_train=False)
    out16 = bm.get_outputs()[0]
    assert out16.shape == (8, 2)
    # bucket 16 shares the same weight values as bucket 8
    a8, _ = bm._buckets[8].get_params()
    a16, _ = bm._buckets[16].get_params()
    onp.testing.assert_allclose(a8["w"].asnumpy(), a16["w"].asnumpy())


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats["CPU"].enabled
    assert "PALLAS" in feats
    assert isinstance(mx.runtime.feature_list(), list)


def test_disable_jit_debug_lever():
    """mx.util.disable_jit ≈ MXNET_ENGINE_TYPE=NaiveEngine (SURVEY §5.2)."""
    import jax
    from mxnet_tpu import util
    net_in = nd.array(onp.ones((2, 3), onp.float32))
    assert not jax.config.jax_disable_jit
    with util.disable_jit():
        assert jax.config.jax_disable_jit
        out = (net_in * 2).sum()
        assert float(out.asscalar()) == 12.0
    assert not jax.config.jax_disable_jit


def test_engine_type_env_knob():
    """MXNET_ENGINE_TYPE=NaiveEngine disables staging at import time."""
    import subprocess, sys, os
    code = ("import jax, mxnet_tpu; "
            "print(bool(jax.config.jax_disable_jit))")
    env = dict(os.environ, MXNET_ENGINE_TYPE="NaiveEngine",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().endswith("True")


def test_monitor_records_matching_ops():
    """mx.mon.Monitor parity: stats of matching op outputs between
    tic()/toc()."""
    import mxnet_tpu as mx
    import numpy as onp

    mon = mx.mon.Monitor(interval=1, pattern=".*FullyConnected.*",
                         sort=True)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    x = mx.nd.array(onp.ones((2, 3), "f"))
    mon.install()
    try:
        mon.tic()
        net(x)
        res = mon.toc()
    finally:
        mon.uninstall()
    assert res and all("FullyConnected" in name for _, name, _ in res)
    assert all(onp.isfinite(stat) for _, _, stat in res)
    # interval=2 skips every other batch
    mon2 = mx.mon.Monitor(interval=2, pattern=".*").install()
    try:
        mon2.tic(); net(x); first = mon2.toc()
        mon2.tic(); net(x); second = mon2.toc()
    finally:
        mon2.uninstall()
    assert first and not second
    # module integration
    d = mx.sym.Variable("data")
    out = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        d, num_hidden=2, name="fc"), name="softmax")
    mod = mx.mod.Module(out, label_names=("softmax_label",))
    m = mod.install_monitor(mx.mon.Monitor(1, pattern=".*fc.*"))
    m.uninstall()
