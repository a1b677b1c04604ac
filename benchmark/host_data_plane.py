#!/usr/bin/env python
"""Host data-plane benchmark: the chip-independent half of the resnet50_io
story (VERDICT r4 item 2).

Measures, WITHOUT any TPU:
  1. raw native pipeline (libmxtpu_io pread+libjpeg+augment) img/s vs
     worker threads — the software ceiling of the C++ plane;
  2. ImageRecordIter end-to-end Python-level batch throughput (f32 and
     uint8 ship-raw-pixels modes);
  3. PrefetchingIter overlap efficiency against a fake consumer that
     sleeps per batch (stand-in for the device step): end-to-end epoch
     time vs max(producer, consumer) ideal.

The record file matches bench.py's resnet50_io workload bit-for-bit in
spirit: (size+16)^2 RGB jpegs quality 90, random crop+mirror to size.

Usage:  python benchmark/host_data_plane.py [--n-img 512] [--size 224]
        [--out docs/host_data_plane_r05.md]
Prints one JSON line per measurement; optionally writes the markdown
summary used for the round-5 analysis note.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu()  # a host-only measurement: it must not take the chip

from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack_img  # noqa: E402
from mxnet_tpu.utils import native  # noqa: E402


def write_rec(path: str, n_img: int, size: int) -> None:
    wr = MXRecordIO(path, "w")
    rng = onp.random.RandomState(0)
    for i in range(n_img):
        img = rng.randint(0, 255, (size + 16, size + 16, 3)).astype("uint8")
        wr.write(pack_img(IRHeader(0, float(i % 100), i, 0), img, quality=90))
    wr.close()


def bench_native_raw(rec: str, n_img: int, size: int, threads: int,
                     batch: int = 64, epochs: int = 2) -> float:
    """img/s of the raw C++ plane: pread + decode + rand crop/mirror +
    normalize into ready NCHW f32 batches, drained as fast as Python can."""
    offs, lens = native.scan_record_offsets(rec)
    pipe = native.NativeImagePipeline(
        rec, offs, lens, (3, size, size), rand_crop=True, rand_mirror=True,
        threads=threads)
    order = onp.arange(n_img)
    # warm epoch (page cache, thread spin-up)
    pipe.schedule(order)
    done = 0
    while done < n_img:
        done += pipe.next_batch(min(batch, n_img - done))[3]
    t0 = time.perf_counter()
    for _ in range(epochs):
        pipe.schedule(order)
        done = 0
        while done < n_img:
            done += pipe.next_batch(min(batch, n_img - done))[3]
    dt = time.perf_counter() - t0
    pipe.close()
    return epochs * n_img / dt


def bench_record_iter(rec: str, n_img: int, size: int, dtype: str,
                      batch: int = 64, epochs: int = 2) -> float:
    """ImageRecordIter end-to-end (native plane + Python batching + NDArray
    materialization) img/s."""
    import mxnet_tpu as mx

    it = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True, dtype=dtype)
    for b in it:           # warm epoch
        b.data[0].asnumpy()
    it.reset()
    t0 = time.perf_counter()
    n = 0
    for _ in range(epochs):
        for b in it:
            n += b.data[0].shape[0]
            b.data[0].asnumpy()   # force materialization, like a consumer
        it.reset()
    return n / (time.perf_counter() - t0)


def bench_prefetch_overlap(rec: str, n_img: int, size: int,
                           step_ms: float, batch: int = 64) -> dict:
    """PrefetchingIter against a consumer sleeping step_ms per batch.
    overlap = ideal/actual where ideal = max(producer_time, consumer_time);
    1.0 means decode fully hidden behind the (fake) device step."""
    import mxnet_tpu as mx

    inner = mx.io.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, size, size), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True, dtype="uint8")
    for _ in inner:        # warm epoch: pipeline spin-up + page cache —
        pass               # prod_t must be comparable to the warmed run
    inner.reset()
    # producer-only epoch time
    t0 = time.perf_counter()
    nb = 0
    for _ in inner:
        nb += 1
    prod_t = time.perf_counter() - t0
    inner.reset()

    it = mx.io.PrefetchingIter(inner)
    for _ in it:          # warm (prefetch thread spin-up)
        pass
    it.reset()
    t0 = time.perf_counter()
    for b in it:
        time.sleep(step_ms / 1e3)
    actual = time.perf_counter() - t0
    cons_t = nb * step_ms / 1e3
    ideal = max(prod_t, cons_t)
    return {"producer_s": round(prod_t, 3), "consumer_s": round(cons_t, 3),
            "actual_s": round(actual, 3),
            "overlap_eff": round(ideal / actual, 3) if actual else 0.0}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-img", type=int, default=512)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if not native.available():
        print(json.dumps({"error": "native IO library unavailable"}))
        return 1

    ncpu = os.cpu_count() or 1
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        rec = os.path.join(tmp, "bench.rec")
        write_rec(rec, args.n_img, args.size)
        rec_mb = os.path.getsize(rec) / 2 ** 20

        for threads in (1, 2, 4):
            v = bench_native_raw(rec, args.n_img, args.size, threads)
            rows.append({"metric": f"native_decode_augment_t{threads}",
                         "value": round(v, 1), "unit": "img/s"})
            print(json.dumps(rows[-1]))
        for dtype in ("float32", "uint8"):
            v = bench_record_iter(rec, args.n_img, args.size, dtype)
            rows.append({"metric": f"image_record_iter_{dtype}",
                         "value": round(v, 1), "unit": "img/s"})
            print(json.dumps(rows[-1]))
        for step_ms in (0.0, 70.0):
            r = bench_prefetch_overlap(rec, args.n_img, args.size, step_ms)
            rows.append({"metric": f"prefetch_overlap_step{int(step_ms)}ms",
                         "value": r["overlap_eff"], "unit": "ideal/actual",
                         **r})
            print(json.dumps(rows[-1]))

    if args.out:
        with open(args.out, "w") as f:
            f.write(f"""# Host data-plane benchmark (round 5)

Machine: {ncpu} CPU core(s).  Workload identical in shape to bench.py's
resnet50_io: {args.n_img} jpegs of ({args.size + 16}, {args.size + 16}, 3)
q=90 ({rec_mb:.1f} MB file), random-crop+mirror to {args.size}, NCHW f32.

| metric | value | unit |
|---|---|---|
""" + "\n".join(
                f"| {r['metric']} | {r['value']} | {r['unit']} |"
                for r in rows) + "\n")
        print(json.dumps({"written": args.out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
