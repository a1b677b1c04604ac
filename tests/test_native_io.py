"""Native C++ data plane (libmxtpu_io): RecordIO framing, offset scan,
threaded image pipeline — and parity with the pure-Python fallback.

Parity: dmlc recordio framing + src/io/iter_image_recordio_2.cc.
"""
import os

import numpy as onp
import pytest

from mxnet_tpu import recordio as rio
from mxnet_tpu.io import ImageRecordIter
from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack, pack_img, unpack
from mxnet_tpu.utils import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="MXNET_TPU_NO_NATIVE is set")


def _write_img_rec(path, n=24, seed=0, label_width=1):
    rs = onp.random.RandomState(seed)
    wr = MXRecordIO(path, "w")
    for i in range(n):
        img = rs.randint(0, 255, (36 + (i % 5), 48, 3), dtype=onp.uint8)
        if label_width == 1:
            hdr = IRHeader(0, float(i), i, 0)
        else:
            hdr = IRHeader(0, onp.arange(label_width, dtype=onp.float32) + i,
                           i, 0)
        wr.write(pack_img(hdr, img, quality=95))
    wr.close()


def test_native_writer_python_reader_roundtrip(tmp_path):
    p = str(tmp_path / "a.rec")
    recs = [b"hello", b"x" * 37, b"", b"yz1", b"\x00\x01\x02"]
    w = native.NativeRecordWriter(p)
    for r in recs:
        w.write(r)
    w.close()
    rd = MXRecordIO(p, "r")
    got = []
    while True:
        r = rd.read()
        if r is None:
            break
        got.append(r)
    rd.close()
    assert got == recs


def test_native_scan_matches_python_framing(tmp_path):
    p = str(tmp_path / "b.rec")
    recs = [os.urandom(n) for n in (1, 3, 4, 5, 127, 0)]
    wr = MXRecordIO(p, "w")
    for r in recs:
        wr.write(r)
    wr.close()
    offs, lens = native.scan_record_offsets(p)
    assert list(lens) == [len(r) for r in recs]
    with open(p, "rb") as f:
        for o, l, r in zip(offs, lens, recs):
            f.seek(int(o))
            assert f.read(int(l)) == r


def test_image_record_iter_native_matches_python(tmp_path):
    """The SAME iterator config must yield identical batches with the
    native pipeline and with the Python fallback (center crop, no
    randomness)."""
    p = str(tmp_path / "img.rec")
    _write_img_rec(p)
    kw = dict(path_imgrec=p, data_shape=(3, 32, 32), batch_size=8,
              mean_r=10., mean_g=5., mean_b=1., std_r=2., std_g=2.,
              std_b=2.)
    it_native = ImageRecordIter(**kw)
    assert it_native._native is not None
    os.environ["MXNET_TPU_NO_NATIVE"] = "1"
    try:
        it_py = ImageRecordIter(**kw)
        assert it_py._native is None
        for b_nat, b_py in zip(it_native, it_py):
            d1 = b_nat.data[0].asnumpy()
            d2 = b_py.data[0].asnumpy()
            onp.testing.assert_allclose(d1, d2, atol=1.5)  # decoder delta
            onp.testing.assert_array_equal(b_nat.label[0].asnumpy(),
                                           b_py.label[0].asnumpy())
    finally:
        del os.environ["MXNET_TPU_NO_NATIVE"]


def test_image_record_iter_native_shuffle_epochs(tmp_path):
    p = str(tmp_path / "img.rec")
    _write_img_rec(p)
    it = ImageRecordIter(path_imgrec=p, data_shape=(3, 32, 32),
                         batch_size=8, shuffle=True, rand_crop=True,
                         rand_mirror=True, seed=7)
    assert it._native is not None
    e1 = [b.label[0].asnumpy().copy() for b in it]
    it.reset()
    e2 = [b.label[0].asnumpy().copy() for b in it]
    assert len(e1) == len(e2) == 3
    # shuffled differently across epochs (overwhelmingly likely)
    assert any((a != b).any() for a, b in zip(e1, e2))
    # every label appears exactly once per epoch
    assert sorted(onp.concatenate(e1).tolist()) == list(map(float, range(24)))


def test_image_record_iter_multi_label(tmp_path):
    p = str(tmp_path / "ml.rec")
    _write_img_rec(p, label_width=3)
    it = ImageRecordIter(path_imgrec=p, data_shape=(3, 32, 32),
                         batch_size=4, label_width=3)
    assert it._native is not None
    b = next(iter(it))
    lab = b.label[0].asnumpy()
    assert lab.shape == (4, 3)
    onp.testing.assert_array_equal(lab[0], [0., 1., 2.])


def test_native_pipeline_flags_bad_records(tmp_path):
    """A record whose payload is not a decodable image is flagged and the
    iterator transparently falls back to Python for it (which also fails
    → overall error), while pure-JPEG files stay native-only."""
    p = str(tmp_path / "mixed.rec")
    wr = MXRecordIO(p, "w")
    rs = onp.random.RandomState(0)
    img = rs.randint(0, 255, (40, 40, 3), dtype=onp.uint8)
    wr.write(pack_img(IRHeader(0, 1.0, 0, 0), img, quality=90))
    wr.write(pack_img(IRHeader(0, 2.0, 1, 0), img, img_fmt=".png"))
    wr.close()
    offs, lens = native.scan_record_offsets(p)
    pipe = native.NativeImagePipeline(p, offs, lens, (3, 32, 32))
    pipe.schedule(onp.arange(2))
    data, labels, ok, n = pipe.next_batch(2)
    assert n == 2
    assert ok[0] and not ok[1]          # png is python-fallback territory
    assert labels[0, 0] == 1.0
    pipe.close()


def test_image_record_iter_honors_idx_subset(tmp_path):
    """A .idx sidecar that subsets/reorders records must be honored by the
    native path exactly as by the fallback."""
    p = str(tmp_path / "s.rec")
    pidx = str(tmp_path / "s.idx")
    rs = onp.random.RandomState(0)
    wr = rio.MXIndexedRecordIO(pidx, p, "w")
    for i in range(12):
        img = rs.randint(0, 255, (40, 40, 3), dtype=onp.uint8)
        wr.write_idx(i, pack_img(IRHeader(0, float(i), i, 0), img))
    wr.close()
    # keep only every third record, reversed
    keys = list(range(0, 12, 3))[::-1]
    idx_map = {}
    with open(pidx) as f:
        for line in f:
            k, o = line.split("\t")
            idx_map[int(k)] = int(o)
    with open(pidx, "w") as f:
        for k in keys:
            f.write(f"{k}\t{idx_map[k]}\n")
    it = ImageRecordIter(path_imgrec=p, path_imgidx=pidx,
                         data_shape=(3, 32, 32), batch_size=4)
    assert it._native is not None
    b = next(iter(it))
    assert b.label[0].asnumpy().tolist() == [9.0, 6.0, 3.0, 0.0]


def test_multipart_roundtrip_python(tmp_path):
    """Payloads containing the 4-byte-aligned magic word are split into
    multipart frames on write (dmlc cflag 1/2/3) and reassembled on read."""
    import struct
    magic = struct.pack("<I", 0xced7230a)
    p = str(tmp_path / "mp.rec")
    recs = [
        magic,                              # magic alone
        b"abcd" + magic + b"efgh",          # aligned magic inside
        b"ab" + magic + b"cd",              # UNaligned magic: no split
        magic * 3,                          # consecutive magics
        b"x" * 8 + magic + b"y" * 5,        # unaligned tail after split
        b"plain old record",
    ]
    wr = MXRecordIO(p, "w")
    for r in recs:
        wr.write(r)
    wr.close()
    rd = MXRecordIO(p, "r")
    got = []
    while True:
        r = rd.read()
        if r is None:
            break
        got.append(r)
    rd.close()
    assert got == recs
    # raw frame check: the aligned-magic records really are multipart
    with open(p, "rb") as f:
        blob = f.read()
    lrec0 = struct.unpack_from("<I", blob, 4)[0]
    assert lrec0 >> 29 == 1                # first record opens a chain


def test_multipart_native_writer_and_scan(tmp_path):
    """Native writer splits identically; native scan merges chains into
    logical records; the pipeline's Python-fallback read path reassembles."""
    import struct
    magic = struct.pack("<I", 0xced7230a)
    recs = [b"abcd" + magic + b"efgh", b"plain", magic + b"zz"]
    pn = str(tmp_path / "n.rec")
    w = native.NativeRecordWriter(pn)
    for r in recs:
        w.write(r)
    w.close()
    # byte-identical to the Python writer
    pp = str(tmp_path / "p.rec")
    wr = MXRecordIO(pp, "w")
    for r in recs:
        wr.write(r)
    wr.close()
    with open(pn, "rb") as fa, open(pp, "rb") as fb:
        assert fa.read() == fb.read()
    # python reader reassembles the native file
    rd = MXRecordIO(pn, "r")
    got = []
    while True:
        r = rd.read()
        if r is None:
            break
        got.append(r)
    rd.close()
    assert got == recs
    # native scan: 3 logical records, multipart ones flagged via bit 63
    offs, lens = native.scan_record_offsets(pn)
    assert len(lens) == 3
    assert bool(lens[0] >> 63) and bool(lens[2] >> 63)
    assert not (lens[1] >> 63)
    # reassemble_span on the flagged span reproduces the record
    from mxnet_tpu.recordio import reassemble_span
    with open(pn, "rb") as f:
        f.seek(int(offs[0]))
        span = f.read(int(lens[0]) & ~(1 << 63))
    assert reassemble_span(span) == recs[0]


def test_multipart_jpeg_through_native_pipeline(tmp_path):
    """An image record whose JPEG payload embeds an aligned magic word
    flows through the native pipeline via in-worker reassembly."""
    import struct
    rs = onp.random.RandomState(3)
    img = rs.randint(0, 255, (40, 48, 3), dtype=onp.uint8)
    payload = pack_img(IRHeader(0, 5.0, 0, 0), img, quality=90)
    # force a multipart record: pad the payload so an aligned magic lands
    # inside it (JPEG decoders ignore trailing garbage after EOI)
    pad = (-len(payload)) % 4
    payload2 = payload + b"\x00" * pad + struct.pack("<I", 0xced7230a) + \
        b"\x00" * 4
    p = str(tmp_path / "j.rec")
    wr = MXRecordIO(p, "w")
    wr.write(payload2)
    wr.write(pack_img(IRHeader(0, 7.0, 1, 0), img, quality=90))
    wr.close()
    offs, lens = native.scan_record_offsets(p)
    assert len(lens) == 2 and bool(lens[0] >> 63)
    pipe = native.NativeImagePipeline(p, offs, lens, (3, 32, 32))
    pipe.schedule(onp.arange(2))
    data, labels, ok, n = pipe.next_batch(2)
    assert n == 2
    assert ok.all()
    assert labels[0, 0] == 5.0 and labels[1, 0] == 7.0
    pipe.close()


def test_image_record_iter_uint8_dtype(tmp_path):
    """dtype='uint8' ships raw pixels (device-side normalization); values
    must equal the float32 path's un-normalized output exactly."""
    p = str(tmp_path / "u8.rec")
    _write_img_rec(p)
    kw = dict(path_imgrec=p, data_shape=(3, 32, 32), batch_size=8)
    b_f32 = next(iter(ImageRecordIter(**kw)))
    it = ImageRecordIter(dtype="uint8", **kw)
    b_u8 = next(iter(it))
    arr = b_u8.data[0].asnumpy()
    assert arr.dtype == onp.uint8
    onp.testing.assert_array_equal(arr.astype(onp.float32),
                                   b_f32.data[0].asnumpy())
    onp.testing.assert_array_equal(b_u8.label[0].asnumpy(),
                                   b_f32.label[0].asnumpy())
    # raw pixels cannot carry host-side normalization
    with pytest.raises(ValueError):
        ImageRecordIter(dtype="uint8", mean_r=123.0, **kw)
    # device-side cast is where normalization now lives
    x = b_u8.data[0].astype("float32")
    assert x.dtype == onp.float32
