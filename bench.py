"""Benchmark harness: training throughput on the TPU chip.

Prints ONE JSON line for the primary workload (GPT-2 124M):
    {"metric", "value", "unit", "vs_baseline"}
vs_baseline is measured MFU / 0.45 — the BASELINE.json north-star target of
>=45% MFU (the reference tree shipped no published numbers, see BASELINE.md).

Extra workloads from BASELINE.md (ResNet-50 images/sec, BERT-large
samples/sec) run with --workload resnet50|bert|all; each prints its own JSON
line in the same schema (primary line last so drivers that read one line get
GPT-2).

There is no CPU mode: with no TPU the run fails before any workload
(``mxnet_tpu.utils.platform.require_tpu``), a device kind missing from the
peak table is an error, and a workload's exception ends the process
non-zero.  ``chip_smoke.py`` is the quick proof that the main path starts
on the chip; the CPU backend is for the tests.

Trial hygiene: after warmup each workload runs >= 3 independent timed
segments; ``value`` is the MEDIAN per-trial throughput and the record
carries ``trials`` (each segment's value) and ``spread_pct`` =
(max-min)/median.  benchmark/serving_bench.py (the online-inference bench)
emits the same schema.
"""
from __future__ import annotations

import argparse
import json
import time
from statistics import median as _median

import numpy as onp

# bf16 peak FLOP/s per chip, keyed by the start of jax's ``device_kind``
# (Google Cloud TPU documentation, per-generation system architecture pages)
_PEAK_BF16_FLOPS = {
    "tpu v5 lite": 197e12, "tpu v5e": 197e12, "tpu v5": 459e12,
    "tpu v4": 275e12, "tpu v6 lite": 918e12, "tpu v6e": 918e12,
}


def peak_flops_per_device() -> float:
    import jax
    kind = jax.devices()[0].device_kind
    for k, v in _PEAK_BF16_FLOPS.items():
        if kind.lower().startswith(k):
            return v
    raise RuntimeError(
        f"bench: no peak FLOP/s on record for device_kind {kind!r} — add "
        "it to _PEAK_BF16_FLOPS with its source; an assumed peak would "
        "make every MFU in the record meaningless")


def _run_steps(trainer, batches, warmup: int, steps: int,
               trials: int = 3) -> list:
    """Warm up (each step synced, so lazy compile/upload never leaks into
    the timed region), then run ``trials`` independent timed segments of
    `steps` async-dispatched steps, each closed by one hard sync.
    ``batches`` is a list of (data, labels) tuples OR a callable
    returning the next batch (streaming input pipelines).  Returns the
    per-trial durations in seconds — callers report the MEDIAN plus the
    spread, so one noisy segment (host jitter, background compile) can
    never masquerade as the steady-state number."""
    if callable(batches):
        nth = lambda i: batches()          # noqa: E731
    else:
        nth = lambda i: batches[i % len(batches)]  # noqa: E731

    def as_loss(r):
        # a guardrails-enabled trainer returns (loss, all_finite)
        return r[0] if isinstance(r, tuple) else r

    for i in range(warmup):
        loss = as_loss(trainer.step(*nth(i)))
        float(loss.asnumpy())     # hard sync — waitall is not enough
    times = []
    for _ in range(max(1, trials)):
        t0 = time.perf_counter()
        loss = None
        for i in range(steps):
            loss = as_loss(trainer.step(*nth(i)))
        float(loss.asnumpy())
        times.append(time.perf_counter() - t0)
    return times


def _ce_loss(logits, labels):
    from mxnet_tpu.ndarray import ops as F
    lse = F.logsumexp(logits, axis=-1)
    return (lse - F.pick(logits, labels, axis=-1)).mean()


def _record(metric: str, value: float, unit: str, mfu: float,
            batch=None, trials=None) -> dict:
    import jax
    d = jax.devices()[0]
    rec = {"metric": metric, "value": round(value, 1), "unit": unit,
           "vs_baseline": round(mfu / 0.45, 4), "platform": d.platform,
           "device_kind": d.device_kind, "device_count": len(jax.devices())}
    if batch is not None:
        rec["batch"] = batch   # ACTUAL per-step batch (after dp rounding)
    if trials is not None and len(trials) > 1:
        # value is the MEDIAN of the per-trial throughputs; spread_pct =
        # (max-min)/median — a large spread flags an untrustworthy run
        rec["trials"] = [round(v, 1) for v in trials]
        rec["spread_pct"] = round(
            100.0 * (max(trials) - min(trials)) / value, 2) if value else None
    return rec


def _fit_batch(batch: int, mesh) -> int:
    """Round batch up to a multiple of the mesh's dp axis (the default
    mesh of a four-chip host is dp=4)."""
    from mxnet_tpu import parallel as par
    dp = par.axis_size(mesh, "dp")
    return -(-batch // dp) * dp


# ------------------------------------------------------------------ GPT-2

def _bench_gpt2_config(on_tpu: bool, long: bool, batch_override=None) -> dict:
    """GPT-2 training throughput; ``long`` is BASELINE config 5 (seq 4096
    through the Pallas flash path, O(T) memory)."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import get_gpt2, gpt2_lm_loss

    if on_tpu:
        # 8,192 tokens a step: the v5e compiler gives the bf16 step 8.9 GB
        # of temporaries there (batch 8 x 1024 and batch 2 x 4096 alike) and
        # 17.1 GB at twice that, on a chip with 15.75 GB (compile
        # rehearsal, CHANGES.md PR 21)
        batch, seq, steps, warmup = (2, 4096, 10, 3) if long \
            else (8, 1024, 20, 3)
        layers, units, vocab = 12, 768, 50257
        net = get_gpt2("gpt2_124m", max_length=seq, dropout=0.0)
    else:  # CPU sanity mode: tiny variant, same code path
        batch, seq, steps, warmup = (2, 512, 2, 1) if long \
            else (4, 128, 3, 1)
        layers, units, vocab = (2, 128, 512) if long else (4, 256, 1024)
        net = get_gpt2("gpt2_124m", vocab_size=vocab, units=units,
                       num_layers=layers, num_heads=4 if long else 8,
                       max_length=seq, dropout=0.0)
    net.initialize()
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "adam", loss=gpt2_lm_loss,
            optimizer_params={"learning_rate": 1e-4}, mesh=mesh)
        toks = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        labels = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        dts = _run_steps(trainer, [(toks, labels)], warmup, steps)

    vals = [batch * seq * steps / dt for dt in dts]
    tokens_per_sec = _median(vals)
    # matmul flops per token: 6*(block params + tied lm head) + attention
    # (2 score + 2 value matmuls per layer, fwd; x3 for training)
    flops_per_token = (6.0 * (12 * layers * units * units + units * vocab)
                       + 12.0 * layers * units * seq)
    mfu = tokens_per_sec * flops_per_token / (
        peak_flops_per_device() * len(jax_devices()))
    name = "gpt2_124m_seq4096_train_throughput" if long \
        else "gpt2_124m_train_throughput"
    return _record(name, tokens_per_sec, "tokens/sec", mfu, batch=batch,
                   trials=vals)


def bench_gpt2(on_tpu: bool, batch_override=None) -> dict:
    return _bench_gpt2_config(on_tpu, long=False, batch_override=batch_override)


def bench_gpt2_long(on_tpu: bool, batch_override=None) -> dict:
    return _bench_gpt2_config(on_tpu, long=True, batch_override=batch_override)


# ------------------------------------------------------- guardrail overhead

def bench_guardrails(on_tpu: bool, batch_override=None) -> dict:
    """Guarded vs unguarded GPT-2 step time (docs/guardrails.md).

    The guardrails (in-graph all_finite flag + where-masked update +
    dynamic loss scaling + global-norm clip) must live INSIDE the one
    compiled step: this record proves it by timing the same workload
    with and without them.  ``value`` is the guard overhead in percent
    of the unguarded step (expected within trial noise — compare with
    ``spread_pct``); the absolute throughputs ride along.
    """
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import get_gpt2, gpt2_lm_loss

    if on_tpu:
        batch, seq, steps, warmup = 8, 1024, 20, 3   # see _bench_gpt2_config
        layers, units, vocab = 12, 768, 50257
        heads = 12
    else:
        batch, seq, steps, warmup = 4, 128, 3, 1
        layers, units, vocab, heads = 4, 256, 1024, 8

    def make_net():
        if on_tpu:
            return get_gpt2("gpt2_124m", max_length=seq, dropout=0.0)
        return get_gpt2("gpt2_124m", vocab_size=vocab, units=units,
                        num_layers=layers, num_heads=heads,
                        max_length=seq, dropout=0.0)

    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    toks = mx.nd.array(
        onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
    labels = mx.nd.array(
        onp.random.randint(0, vocab, (batch, seq)), dtype="int32")

    results = {}
    with par.use_mesh(mesh):
        for name, kw in (("unguarded", {}),
                         ("guarded", {"guard_nonfinite": True,
                                      "clip_global_norm": 1.0,
                                      "loss_scaler": amp.LossScaler()})):
            net = make_net()
            net.initialize()
            trainer = par.ShardedTrainer(
                net, "adam", loss=gpt2_lm_loss,
                optimizer_params={"learning_rate": 1e-4}, mesh=mesh, **kw)
            dts = _run_steps(trainer, [(toks, labels)], warmup, steps)
            results[name] = [batch * seq * steps / dt for dt in dts]

    un = _median(results["unguarded"])
    gu = _median(results["guarded"])
    overhead_pct = 100.0 * (un - gu) / un if un else 0.0
    rec = _record("gpt2_guarded_step_overhead", overhead_pct, "%", 0.0,
                  batch=batch)
    rec["vs_baseline"] = None        # a ratio, not an MFU claim
    rec["value"] = round(overhead_pct, 2)
    rec["unguarded_tokens_per_sec"] = round(un, 1)
    rec["guarded_tokens_per_sec"] = round(gu, 1)
    rec["guarded_trials"] = [round(v, 1) for v in results["guarded"]]
    rec["unguarded_trials"] = [round(v, 1) for v in results["unguarded"]]
    # per-side trial spread: an overhead smaller than this is noise
    rec["spread_pct"] = round(max(
        100.0 * (max(v) - min(v)) / _median(v)
        for v in results.values()), 2)
    return rec


# ------------------------------------------------------ checkpoint integrity

def bench_checkpoint(on_tpu: bool, batch_override=None) -> dict:
    """Verified-checkpoint overhead (docs/integrity.md).

    Every ``AtomicCheckpointer.save`` now digests the written files into
    ``MANIFEST.json`` before the commit rename, every ``restore``
    re-hashes before deserializing, and ``_gc`` verifies-or-skips before
    collecting.  This record times a RETENTION-SHAPED cycle — three
    saves under ``max_to_keep=2`` (so GC actually fires and pays its
    newest-step re-verification, the way a ResilientLoop run does) plus
    one restore — against a manifest-less floor that performs the
    IDENTICAL atomic mechanics (tmp dir + state + meta + rename commit +
    blind retention GC + load) minus every digest — i.e. the
    pre-integrity checkpointer; ``value`` is the verification overhead
    in percent of the floor, expected within trial noise (compare with
    ``spread_pct``): digests are chunk-parallel BLAKE2b, so the
    serialize/IO cost dominates.
    """
    import json as _json
    import os
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.resilience import AtomicCheckpointer
    from mxnet_tpu.utils import serialization as ser

    # a state dict shaped like what a ResilientLoop commit actually
    # moves: dozens of tensors sized to the tier's model (the CPU
    # fallback benches the REDUCED models, same convention as every
    # other workload here — the code path, not the scale)
    n_arrays, rows = (48, 1 << 16) if on_tpu else (24, 1 << 11)
    rs = onp.random.RandomState(0)
    tree = {f"param:block{i}.w": mx.nd.array(
        rs.randn(rows, 16).astype("float32")) for i in range(n_arrays)}

    def floor_roundtrip(d):
        """The pre-integrity atomic path: same writes, same rename
        commits, same blind retention GC, same reads — no manifest, no
        verification."""
        kept = []
        for s in (1, 2, 3):
            tmp = os.path.join(d, f".tmp-{s}")
            os.makedirs(tmp)
            ser.save(os.path.join(tmp, "state.mxtpu"), tree)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                _json.dump({"step": s}, f)
            final = os.path.join(d, f"step-{s:08d}")
            os.rename(tmp, final)
            kept.append(final)
            while len(kept) > 2:       # the old blind _gc
                shutil.rmtree(kept.pop(0), ignore_errors=True)
        out = ser.load(os.path.join(kept[-1], "state.mxtpu"))
        with open(os.path.join(kept[-1], "meta.json")) as f:
            _json.load(f)
        return out

    trials_v, trials_f = [], []
    workdir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        for t in range(5):
            ck = AtomicCheckpointer(os.path.join(workdir, f"v{t}"),
                                    max_to_keep=2)
            t0 = time.perf_counter()
            for s in (1, 2, 3):
                ck.save(s, tree)
            ck.restore()
            trials_v.append(time.perf_counter() - t0)
            fd = os.path.join(workdir, f"f{t}")
            os.makedirs(fd)
            t0 = time.perf_counter()
            floor_roundtrip(fd)
            trials_f.append(time.perf_counter() - t0)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # drop the first (cold-cache) trial of each side, then medians
    v = _median(sorted(trials_v[1:]))
    f = _median(sorted(trials_f[1:]))
    overhead_pct = 100.0 * (v - f) / f if f else 0.0
    rec = _record("checkpoint_verified_overhead", overhead_pct, "%", 0.0)
    rec["vs_baseline"] = None            # a ratio, not an MFU claim
    rec["value"] = round(overhead_pct, 2)
    rec["verified_cycle_ms"] = round(v * 1e3, 2)       # 3 saves + gc + restore
    rec["floor_cycle_ms"] = round(f * 1e3, 2)
    rec["verified_trials_ms"] = [round(x * 1e3, 2) for x in trials_v]
    rec["floor_trials_ms"] = [round(x * 1e3, 2) for x in trials_f]
    rec["spread_pct"] = round(max(
        100.0 * (max(xs[1:]) - min(xs[1:])) / _median(sorted(xs[1:]))
        for xs in (trials_v, trials_f)), 2)
    return rec


# --------------------------------------------------------------- ResNet-50

def bench_resnet50(on_tpu: bool, batch_override=None) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models.vision import get_resnet

    if on_tpu:
        # batch 128: the MXU wants large convs — 64 measured ~10% MFU on
        # v5e; bigger per-chip batch is the first lever (tools/tpu_tune.py
        # sweeps this).  NHWC: channels-last keeps C on the 128-lane minor
        # dim through conv/BN-stat/pool, eliminating the relayout copies
        # and f32 NCHW stat fusions the r3 profile showed dominating the
        # non-conv time (docs/resnet_roofline_r05.md).
        batch, steps, warmup, size = 128, 20, 3, 224
        net = get_resnet(1, 50, classes=1000, layout="NHWC")
        train_flops_per_img = 3 * 4.1e9   # fwd conv+fc flops, ResNet-50 v1
    else:
        batch, steps, warmup, size = 8, 2, 1, 64
        net = get_resnet(1, 18, classes=100, layout="NHWC")
        train_flops_per_img = 3 * 1.8e9 * (64 / 224) ** 2
    net.initialize()
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "sgd", loss=_ce_loss,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=mesh)
        imgs = mx.nd.array(
            onp.random.uniform(-1, 1, (batch, size, size, 3)).astype("float32"))
        labels = mx.nd.array(
            onp.random.randint(0, 100, (batch,)), dtype="int32")
        dts = _run_steps(trainer, [(imgs, labels)], warmup, steps)

    vals = [batch * steps / dt for dt in dts]
    imgs_per_sec = _median(vals)
    mfu = imgs_per_sec * train_flops_per_img / (
        peak_flops_per_device() * len(jax_devices()))
    return _record("resnet50_train_throughput", imgs_per_sec,
                   "images/sec", mfu, batch=batch, trials=vals)


# ------------------------------------------------- ResNet-50 + input pipeline

def bench_resnet50_io(on_tpu: bool, batch_override=None) -> dict:
    """ResNet-50 training fed by the RecordIO input pipeline (the C++
    decode/augment plane when available) — measures END-TO-END images/sec
    including host-side decode + augmentation + upload (VERDICT r1 #3:
    'exercises the data plane at throughput')."""
    import os
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models.vision import get_resnet
    from mxnet_tpu.recordio import IRHeader, MXRecordIO, pack_img

    if on_tpu:
        batch, steps, warmup, size, n_img = 128, 20, 3, 224, 512
        net = get_resnet(1, 50, classes=1000, layout="NHWC")
        train_flops_per_img = 3 * 4.1e9
    else:
        batch, steps, warmup, size, n_img = 8, 2, 1, 64, 64
        net = get_resnet(1, 18, classes=100, layout="NHWC")
        train_flops_per_img = 3 * 1.8e9 * (64 / 224) ** 2
    net.initialize()
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    # the pipeline must be able to fill every batch (an empty epoch would
    # loop forever in stream())
    n_img = max(n_img, batch * 2)

    with tempfile.TemporaryDirectory() as tmp:
        rec = os.path.join(tmp, "bench.rec")
        wr = MXRecordIO(rec, "w")
        rng = onp.random.RandomState(0)
        for i in range(n_img):
            img = rng.randint(0, 255, (size + 16, size + 16, 3))                 .astype("uint8")
            wr.write(pack_img(IRHeader(0, float(i % 100), i, 0), img,
                              quality=90))
        wr.close()
        # dtype=uint8: ship raw pixels (4x less host->device traffic — the
        # transfer, not decode, dominates when the chip is remote) and cast
        # on device; PrefetchingIter overlaps decode+upload with the step
        it = mx.io.PrefetchingIter(mx.io.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, size, size), batch_size=batch,
            shuffle=True, rand_crop=True, rand_mirror=True, dtype="uint8"))

        with par.use_mesh(mesh):
            trainer = par.ShardedTrainer(
                net, "sgd", loss=_ce_loss,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                mesh=mesh)

            def stream():
                while True:
                    for b in it:
                        # NCHW pipeline batch -> NHWC on device: the
                        # transpose rides the chip (free vs the uint8
                        # transfer); the uint8->f32 cast also stays
                        # device-side
                        yield (b.data[0].astype("float32")
                               .transpose((0, 2, 3, 1)),
                               b.label[0].astype("int32"))
                    it.reset()

            gen = iter(stream())
            dts = _run_steps(trainer, lambda: next(gen), warmup, steps)

    vals = [batch * steps / dt for dt in dts]
    imgs_per_sec = _median(vals)
    mfu = imgs_per_sec * train_flops_per_img / (
        peak_flops_per_device() * len(jax_devices()))
    return _record("resnet50_io_train_throughput", imgs_per_sec,
                   "images/sec", mfu, batch=batch, trials=vals)


# ----------------------------------------------------------- data plane

def bench_data_plane(on_tpu: bool, batch_override=None) -> dict:
    """Input-pipeline overlap (docs/data.md): the same io-shaped training
    loop fed five ways —

    - ``synthetic``       preloaded device batches, no input pipeline at
                          all (the compute ceiling every other arm is
                          judged against);
    - ``f32_sync``        host decode/augment to float32, handed to the
                          step synchronously (the classic stall);
    - ``f32_prefetch``    same producer behind a ``DevicePrefetcher``
                          (depth 2) shipping against the trainer's batch
                          shardings;
    - ``uint8_sync``      raw uint8 ship + jitted on-device crop/mirror/
                          normalize (``DeviceTransform``), synchronous;
    - ``uint8_prefetch``  uint8 ship + on-device augment behind the
                          prefetcher — the docs/data.md recommended
                          configuration.

    The producer performs the real host-side work (index, gather, crop/
    mirror/normalize for the f32 arms) plus a fixed sleep standing in
    for jpeg decode + storage fetch latency (which release the GIL
    exactly like this sleep does — the C++ decode plane and a remote
    read both overlap the same way).  The trunk is sized so the
    synthetic step costs >= 50ms; ``value`` is the uint8_prefetch
    throughput, and the record carries per-arm img/s, trials, and the
    fraction of each timed region spent waiting on input
    (``input_wait_frac``, from ``DevicePrefetcher.stats()`` for the
    prefetch arms and the measured producer time for the sync arms).
    """
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.data import DevicePrefetcher, DeviceTransform
    from mxnet_tpu.gluon import nn

    if on_tpu:
        batch, steps, warmup, trials = 128, 12, 3, 3
        size, crop, units, decode_ms = 232, 224, 4096, 20.0
    else:
        batch, steps, warmup, trials = 8, 6, 2, 3
        size, crop, units, decode_ms = 72, 64, 640, 60.0
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    mean, std = (124.0, 117.0, 104.0), (58.4, 57.1, 57.4)
    rs = onp.random.RandomState(0)
    pool_n = max(batch * 4, 32)
    pool = rs.randint(0, 255, (pool_n, size, size, 3)).astype("uint8")
    pool_labels = rs.randint(0, 100, (pool_n,)).astype("int32")
    mean_a = onp.asarray(mean, "float32")
    std_a = onp.asarray(std, "float32")
    produce_spent = [0.0]      # accumulated host production seconds

    def produce(i, as_f32):
        """One host-side batch: gather from the pool (+ crop/mirror/
        normalize for the f32 arms) behind the decode-latency stand-in."""
        t0 = time.perf_counter()
        time.sleep(decode_ms / 1e3)
        prs = onp.random.RandomState(7919 + i)
        sel = prs.randint(0, pool_n, size=batch)
        imgs, labels = pool[sel], pool_labels[sel]
        if as_f32:
            oy, ox = prs.randint(0, size - crop + 1, size=2)
            out = imgs[:, oy:oy + crop, ox:ox + crop, :].astype("float32")
            flip = prs.rand(batch) < 0.5
            out[flip] = out[flip, :, ::-1, :]
            out = (out - mean_a) / std_a
        else:
            out = imgs                        # raw uint8, 4x fewer bytes
        produce_spent[0] += time.perf_counter() - t0
        return mx.nd.array(out), mx.nd.array(labels, dtype="int32")

    def make_trainer():
        net = nn.HybridSequential()
        net.add(nn.Dense(units, activation="relu"),
                nn.Dense(units, activation="relu"),
                nn.Dense(100))
        net.initialize()
        return par.ShardedTrainer(
            net, "sgd", loss=_ce_loss,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            mesh=mesh)

    def as_loss(r):
        return r[0] if isinstance(r, tuple) else r

    def run_arm(trainer, next_batch, wait_fn):
        """warmup, then ``trials`` timed segments; returns the per-trial
        seconds and the input-wait seconds accrued over the timed region
        only (compiles never pollute the fraction).  Every step reads the
        loss back — the fit-loop shape (per-step metric update), held
        IDENTICAL across arms so the only variable is how the next batch
        gets to the device: without a prefetcher the producer runs inside
        the loss-sync gap it just created; with one, the feeder produced
        during that same gap and the batch is already resident."""
        for i in range(warmup):
            float(as_loss(trainer.step(*next_batch(i))).asnumpy())
        w0, times = wait_fn(), []
        for t in range(trials):
            t0 = time.perf_counter()
            for i in range(steps):
                float(as_loss(trainer.step(
                    *next_batch(warmup + t * steps + i))).asnumpy())
            times.append(time.perf_counter() - t0)
        return times, wait_fn() - w0

    arms = {}
    n_total = warmup + trials * steps + 4     # + prefetch ring slack
    with par.use_mesh(mesh):
        # -- synthetic ceiling: preloaded f32 batches, no input at all
        tr = make_trainer()
        fixed = [(mx.nd.array(
                      (pool[i * batch:(i + 1) * batch, :crop, :crop, :]
                       .astype("float32") - mean_a) / std_a),
                  mx.nd.array(pool_labels[i * batch:(i + 1) * batch],
                              dtype="int32"))
                 for i in range(2)]
        times, _ = run_arm(tr, lambda i: fixed[i % 2], lambda: 0.0)
        arms["synthetic"] = {"times": times, "wait": 0.0}

        # -- f32 host augment, synchronous hand-off
        tr = make_trainer()
        times, wait = run_arm(tr, lambda i: produce(i, True),
                              lambda: produce_spent[0])
        arms["f32_sync"] = {"times": times, "wait": wait}

        # -- f32 host augment behind the prefetcher
        tr = make_trainer()
        d0, l0 = produce(0, True)
        tr.build(d0, l0)

        def gen_f32():
            for i in range(n_total):
                yield produce(i, True)
        pf = DevicePrefetcher(gen_f32(), shardings=tr.batch_shardings,
                              depth=2)
        tr.attach_data_source(pf)
        times, wait = run_arm(tr, lambda i: next(pf),
                              lambda: pf.stats()["input_wait_seconds_total"])
        arms["f32_prefetch"] = {"times": times, "wait": wait}

        # -- uint8 ship + on-device augment, synchronous
        tf_sync = DeviceTransform(mean=mean, std=std, crop=crop,
                                  mirror=True, layout="NHWC", seed=11)
        tr = make_trainer()

        def sync_u8(i):
            d, lab = produce(i, False)
            return mx.nd.NDArray(tf_sync.apply(d.jax, i)), lab
        times, wait = run_arm(tr, sync_u8, lambda: produce_spent[0])
        arms["uint8_sync"] = {"times": times, "wait": wait}

        # -- uint8 ship + on-device augment behind the prefetcher
        tf_pf = DeviceTransform(mean=mean, std=std, crop=crop,
                                mirror=True, layout="NHWC", seed=11)
        tr = make_trainer()
        d0, l0 = produce(0, False)
        tr.build(mx.nd.NDArray(tf_pf.apply(d0.jax, 0)), l0)

        def gen_u8():
            for i in range(n_total):
                yield produce(i, False)
        pf = DevicePrefetcher(gen_u8(), shardings=None, depth=2,
                              transform=tf_pf)
        tr.attach_data_source(pf)
        times, wait = run_arm(tr, lambda i: next(pf),
                              lambda: pf.stats()["input_wait_seconds_total"])
        arms["uint8_prefetch"] = {"times": times, "wait": wait}

    out_arms = {}
    for name, a in arms.items():
        vals = [batch * steps / dt for dt in a["times"]]
        v = _median(vals)
        out_arms[name] = {
            "imgs_per_sec": round(v, 1),
            "trials": [round(x, 1) for x in vals],
            "input_wait_frac": round(a["wait"] / sum(a["times"]), 4)
            if sum(a["times"]) else 0.0,
        }
    rec = _record("data_plane_input_pipeline",
                  out_arms["uint8_prefetch"]["imgs_per_sec"],
                  "images/sec", 0.0, batch=batch)
    rec["vs_baseline"] = None          # overlap ratios, not an MFU claim
    rec["arms"] = out_arms
    rec["synthetic_step_ms"] = round(
        1e3 * _median(arms["synthetic"]["times"]) / steps, 2)
    for d in ("f32", "uint8"):
        on = out_arms[f"{d}_prefetch"]["imgs_per_sec"]
        off = out_arms[f"{d}_sync"]["imgs_per_sec"]
        rec[f"prefetch_speedup_{d}"] = round(on / off, 3) if off else None
    best_io = max(out_arms[k]["imgs_per_sec"]
                  for k in ("f32_prefetch", "uint8_prefetch"))
    rec["io_vs_synthetic"] = round(
        out_arms["synthetic"]["imgs_per_sec"] / best_io, 3) if best_io \
        else None
    return rec


# ------------------------------------------------------------ NMT (config 4)

def bench_nmt(on_tpu: bool, batch_override=None) -> dict:
    """Transformer-big WMT-style encoder-decoder training throughput
    (BASELINE config 4; Sockeye parity workload)."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import get_nmt, nmt_loss

    if on_tpu:
        from mxnet_tpu.models import nmt as _nmt
        batch, seq, steps, warmup = 16, 256, 10, 2
        layers, units, hidden, _heads = _nmt._CONFIGS["transformer_big"]
        vocab = 32000
        net = get_nmt("transformer_big", src_vocab_size=vocab,
                      dropout=0.0)
    else:
        batch, seq, steps, warmup = 4, 32, 2, 1
        layers, units, hidden, vocab = 2, 64, 128, 512
        net = get_nmt("transformer_base", src_vocab_size=vocab,
                      units=units, hidden_size=hidden, num_layers=layers,
                      num_heads=4, dropout=0.0)
    net.initialize()
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "adam", loss=lambda o, l: nmt_loss(o, l),
            optimizer_params={"learning_rate": 1e-4}, mesh=mesh)
        src = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        tgt = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        labels = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        dts = _run_steps(trainer, [((src, tgt), labels)], warmup, steps)

    vals = [batch * seq * steps / dt for dt in dts]
    tokens_per_sec = _median(vals)
    # per tgt token: decoder (self+cross attn + ffn) + encoder (per src
    # token, same count) + tied output projection; x3 for training
    enc_block = 4 * units * units + 2 * units * hidden
    dec_block = 8 * units * units + 2 * units * hidden
    flops_per_token = 6.0 * (layers * (enc_block + dec_block)
                             + units * vocab) \
        + 24.0 * layers * units * seq
    mfu = tokens_per_sec * flops_per_token / (
        peak_flops_per_device() * len(jax_devices()))
    return _record("transformer_big_nmt_train_throughput", tokens_per_sec,
                   "tokens/sec", mfu, batch=batch, trials=vals)


# -------------------------------------------------------------- BERT-large

def bench_bert(on_tpu: bool, batch_override=None) -> dict:
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import get_bert
    from mxnet_tpu.models.bert import BERTForPretrain
    from mxnet_tpu.ndarray import ops as F

    if on_tpu:
        batch, seq, steps, warmup = 8, 512, 10, 3
        layers, units, vocab, name = 24, 1024, 30522, "bert_large"
    else:
        batch, seq, steps, warmup = 2, 128, 2, 1
        layers, units, vocab, name = 2, 128, 1000, "bert_base"
    n_masked = max(1, seq // 8)
    # remat="dots": without it the scanned 24-layer stack saves every
    # per-layer intermediate (>16GB HBM at batch 8 seq 512) and OOMs v5e
    net = BERTForPretrain(get_bert(
        name, vocab_size=vocab, max_length=seq,
        **({"remat": "dots"} if on_tpu else
           {"units": units, "num_layers": layers, "num_heads": 2})))

    def mlm_loss(outs, mlm_labels, nsp_labels):
        mlm_logits, nsp_logits = outs
        lse = F.logsumexp(mlm_logits, axis=-1)
        mlm = (lse - F.pick(mlm_logits, mlm_labels, axis=-1)).mean()
        nlse = F.logsumexp(nsp_logits, axis=-1)
        nsp = (nlse - F.pick(nsp_logits, nsp_labels, axis=-1)).mean()
        return mlm + nsp

    net.initialize()
    mesh = par.make_mesh()
    batch = _fit_batch(batch_override or batch, mesh)
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "adam", loss=mlm_loss,
            optimizer_params={"learning_rate": 1e-4}, mesh=mesh)
        toks = mx.nd.array(
            onp.random.randint(0, vocab, (batch, seq)), dtype="int32")
        types = mx.nd.array(onp.zeros((batch, seq)), dtype="int32")
        vlen = mx.nd.array(onp.full((batch,), seq), dtype="int32")
        pos = mx.nd.array(
            onp.stack([onp.sort(onp.random.choice(seq, n_masked,
                                                  replace=False))
                       for _ in range(batch)]), dtype="int32")
        mlm_lab = mx.nd.array(
            onp.random.randint(0, vocab, (batch, n_masked)), dtype="int32")
        nsp_lab = mx.nd.array(onp.random.randint(0, 2, (batch,)),
                              dtype="int32")
        data = (toks, types, vlen, pos)
        dts = _run_steps(trainer, [(data, (mlm_lab, nsp_lab))], warmup,
                         steps)

    vals = [batch * steps / dt for dt in dts]
    samples_per_sec = _median(vals)
    flops_per_sample = seq * (6.0 * 12 * layers * units * units
                              + 12.0 * layers * units * seq) \
        + 6.0 * n_masked * units * vocab
    mfu = samples_per_sec * flops_per_sample / (
        peak_flops_per_device() * len(jax_devices()))
    return _record("bert_large_pretrain_throughput", samples_per_sec,
                   "samples/sec", mfu, batch=batch, trials=vals)


def jax_devices():
    import jax
    return jax.devices()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2",
                    choices=["gpt2", "gpt2_long", "resnet50", "resnet50_io",
                             "bert", "nmt", "guardrails", "checkpoint",
                             "data_plane", "all"])
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of each workload "
                         "into DIR (for the on-chip where-does-time-go "
                         "analysis)")
    args = ap.parse_args()

    from mxnet_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    from mxnet_tpu import amp
    amp.init("bfloat16")   # MXU wants bf16; master weights stay f32

    names = (["resnet50", "resnet50_io", "data_plane", "bert", "nmt",
              "guardrails", "checkpoint", "gpt2_long", "gpt2"]
             if args.workload == "all" else [args.workload])
    table = {"gpt2": bench_gpt2, "gpt2_long": bench_gpt2_long,
             "resnet50": bench_resnet50, "resnet50_io": bench_resnet50_io,
             "bert": bench_bert, "nmt": bench_nmt,
             "guardrails": bench_guardrails,
             "checkpoint": bench_checkpoint,
             "data_plane": bench_data_plane}
    import contextlib
    import os
    from mxnet_tpu.observability import flatten
    for name in names:
        if args.profile:
            import jax
            d = os.path.join(args.profile, name)
            os.makedirs(d, exist_ok=True)
            cm = jax.profiler.trace(d)
        else:
            cm = contextlib.nullcontext()
        with cm:
            rec = table[name](True)
        # perf trajectory and process counters travel together: embed
        # the observability registry snapshot (non-zero counters/gauges)
        # taken right after the workload (docs/observability.md)
        rec["registry"] = flatten()
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
