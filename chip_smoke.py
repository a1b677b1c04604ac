#!/usr/bin/env python3
"""Prove that the main path starts on the TPU chip: GPT-2 124M at its
published widths, a few training steps and a few served requests, through
the entry points a user calls.  One process; no measurement, no claim.

    python chip_smoke.py             # one chip: train phase + serve phase
    python chip_smoke.py --chips 4   # ONLY the mesh phase: dp=2 x tp=2
                                     # training and mesh=4 serving, each
                                     # against its one-device twin

Each phase prints one JSON line of what it saw (smoke timings are labelled
as such: they are single readings with compiles around them).  Any failed
check exits non-zero after the phase lines.  The last line is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` and is printed
only when every check passed on a TPU.

The phases are plain functions of a :class:`Size`, so
tests/test_chip_smoke.py drives them at a tiny size on the CPU backend;
``main`` itself has no CPU mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import time

import numpy as onp


@dataclasses.dataclass(frozen=True)
class Size:
    """What a phase is run at.  ``FULL`` is GPT-2 124M as published
    (``get_gpt2("gpt2_124m")``: 12 layers, 768 units, 12 heads, vocabulary
    50,257, context 1,024) in full depth."""
    model: dict                 # overrides of the published config
    batch: int                  # training batch (sequences per step)
    seq: int                    # training sequence length
    # serving: lattice and the four prompts
    seq_bucket: int             # the one prefill bucket == prefill chunk
    page_size: int
    prefix_len: int             # shared by two requests; > prefix_min_tokens
    tail_len: int
    long_len: int               # > seq_bucket: takes chunked prefill
    short_len: int
    short_new: int              # the request decoded to a few dozen tokens
    new: int                    # new tokens of the other three


# Batch 8: the chip's compiler gives the bf16 step at batch 8 x seq 1024
# 8.25 GB of temporaries beside 1.39 GB of arguments on a 15.75 GB chip;
# batch 16 needs 15.89 GB and does not fit (CHANGES.md, PR 21).
FULL = Size(model={}, batch=8, seq=1024, seq_bucket=128, page_size=16,
            prefix_len=96, tail_len=16, long_len=300, short_len=24,
            short_new=48, new=8)

_STEPS = 4          # three on one repeated batch, one on a fresh batch
# bf16 steps on dp=2 x tp=2 against one device (mesh phase); the four chips
# left the one by at most 7.5e-5 of the loss (chip run, PR 21)
_LOSS_RTOL = 2e-3
# A served bf16 token may sit this far below the float32 reference's argmax,
# as a share of the largest |logit|: 16 bf16 roundings' worth (eps = 2**-8).
# A token from a wrong page or position sits about 1.0 below.
_BF16_LOGIT_TOL = 16 * 2.0 ** -8


def _emit(**rec):
    print(json.dumps(rec), flush=True)


def _net(size: Size, seed: int):
    import mxnet_tpu as mx
    from mxnet_tpu.models import get_gpt2
    onp.random.seed(seed)
    mx.random.seed(seed)
    net = get_gpt2("gpt2_124m", dropout=0.0, **size.model)
    net.initialize()
    return net


def _token_batches(size: Size, vocab: int, seed: int):
    """Seeded (tokens, next-token labels) pairs: the first batch three
    times (its loss must fall), then fresh ones."""
    rs = onp.random.RandomState(seed)

    def one():
        t = rs.randint(0, vocab, (size.batch, size.seq + 1)).astype("int32")
        return t[:, :-1], t[:, 1:]

    first = one()
    return [first, first, first] + [one() for _ in range(_STEPS - 3)]


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


def _score_tensors(text: str, seq: int) -> int:
    """Count of ``[..., seq, seq]`` arrays in a compiled program: what the
    O(T^2) reference attention materializes and the flash kernel must not."""
    return len(re.findall(r"\[(?:\d+,)*%d,%d\]" % (seq, seq), text))


_xla_compiles = []


def _count_xla_compiles() -> int:
    """Backend compiles in this process since the first call."""
    if not _xla_compiles:
        import jax.monitoring
        _xla_compiles.append(0)
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: _xla_compiles.append(0)
            if name == "/jax/core/compile/backend_compile_duration"
            else None)
    return len(_xla_compiles)


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ train

def _train_steps(net, size: Size, mesh, seed: int) -> dict:
    """``_STEPS`` steps of ``ShardedTrainer`` on ``mesh`` fed through a
    ``DevicePrefetcher``; returns what the checks read."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel as par
    from mxnet_tpu.data import DevicePrefetcher
    from mxnet_tpu.models import gpt2_lm_loss

    batches = _token_batches(size, net.vocab_size, seed)
    sample = tuple(mx.nd.array(a, dtype="int32") for a in batches[0])
    with par.use_mesh(mesh):
        trainer = par.ShardedTrainer(
            net, "adam", loss=gpt2_lm_loss,
            optimizer_params={"learning_rate": 1e-3}, mesh=mesh)
        trainer.build(*sample)
        t0 = time.perf_counter()
        compiled = trainer.lower_step(*sample).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        feed = DevicePrefetcher(iter(batches),
                                shardings=trainer.batch_shardings)
        trainer.attach_data_source(feed)
        losses, step_s = [], []
        try:
            first_param = next(iter(
                net.collect_params().values())).data().jax
            for _ in range(_STEPS):
                data, labels = next(feed)
                t0 = time.perf_counter()
                losses.append(float(trainer.step(data, labels).asnumpy()))
                step_s.append(round(time.perf_counter() - t0, 4))
            fed = feed.stats()
        finally:
            feed.close()
    return {
        "losses": losses,
        "kernels": _kernels(text),
        "score_tensors": _score_tensors(text, size.seq),
        "donated": first_param.is_deleted(),
        # parameters, optimizer state and whatever else is alive
        "platforms": sorted({d.platform for a in jax.live_arrays()
                             for d in a.devices()}),
        "batches_shipped": fed["batches_shipped"],
        "batches_fallback": fed["batches_fallback"],
        "compile_seconds_smoke": round(compile_s, 2),
        "step_seconds_smoke": step_s,
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
    }


def run_train(size: Size, seed: int) -> dict:
    """bf16 AMP training on the default mesh (one chip: one device)."""
    import jax
    from mxnet_tpu import amp
    from mxnet_tpu import parallel as par

    amp.init("bfloat16")
    try:
        facts = _train_steps(_net(size, seed), size, par.make_mesh(), seed)
    finally:
        amp.reset()
    facts["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    return facts


def check_train(f: dict) -> list:
    bad = []
    if not all(onp.isfinite(f["losses"])):
        bad.append(f"train: non-finite loss in {f['losses']}")
    elif not f["losses"][2] < f["losses"][0]:
        bad.append(f"train: loss on the repeated batch did not fall: "
                   f"{f['losses'][:3]}")
    if f["batches_shipped"] != _STEPS or f["batches_fallback"]:
        bad.append(f"train: prefetcher shipped {f['batches_shipped']} of "
                   f"{_STEPS} batches, {f['batches_fallback']} fell back")
    # chip-only from here: the CPU rehearsal expects exactly these to fail
    if f["kernels"] < 1:
        bad.append("train: no flash kernel (tpu_custom_call) in the step")
    if f["score_tensors"]:
        bad.append(f"train: {f['score_tensors']} [..,T,T] score tensors in "
                   "the step — attention took the O(T^2) reference path")
    if f["platforms"] != ["tpu"]:
        bad.append(f"train: live arrays (parameters, optimizer state) on "
                   f"{f['platforms']}")
    if not f["donated"]:
        bad.append("train: donation off (the pre-step parameter buffer "
                   "survived the step)")
    return bad


# ------------------------------------------------------------------ serve

def _prompts(size: Size, vocab: int, seed: int):
    """Two prompts sharing a prefix, one longer than a prefill chunk, one
    short one that decodes long — with each one's new-token count."""
    rs = onp.random.RandomState(seed + 1)

    def toks(n):
        return rs.randint(0, vocab, (n,)).astype("int32")

    shared = toks(size.prefix_len)
    return [(onp.concatenate([shared, toks(size.tail_len)]), size.new),
            (onp.concatenate([shared, toks(size.tail_len)]), size.new),
            (toks(size.long_len), size.new),
            (toks(size.short_len), size.short_new)]


def _serve(net, size: Size, prompts, mesh=None) -> dict:
    """warmup → start → the four requests → stop, on the engine's default
    paged read arm for ``mesh``."""
    from mxnet_tpu.observability import flatten
    from mxnet_tpu.serving import InferenceEngine

    eng = InferenceEngine(
        net, kv_layout="paged", page_size=size.page_size, num_slots=4,
        max_batch=2, batch_buckets=(2,), seq_buckets=(size.seq_bucket,),
        default_max_new_tokens=size.new, mesh=mesh)
    t0 = time.perf_counter()
    warm = eng.warmup()
    warmup_s = time.perf_counter() - t0
    text = eng.lower_decode().compile().as_text()

    def compiles():
        return sum(v for k, v in flatten(prefix="mxtpu_serving").items()
                   if k.startswith("mxtpu_serving_compiles{")
                   and f'engine="{eng.name}"' in k)

    before, xla_before = compiles(), _count_xla_compiles()
    eng.start()
    try:
        t0 = time.perf_counter()
        # the first family member alone, so its pages are in the prefix
        # cache when its sibling is admitted; the rest concurrently
        outs = [eng.infer(prompts[0][0], max_new_tokens=prompts[0][1])]
        futs = [eng.submit(p, max_new_tokens=n) for p, n in prompts[1:]]
        outs += [f.result(timeout=600) for f in futs]
        serve_s = time.perf_counter() - t0
        stats = eng.stats()
        after = compiles()
    finally:
        eng.stop()
    outs = [onp.asarray(o).tolist() for o in outs]
    return {
        "echoes_prompt": [o[:len(p)] == p.tolist() and len(o) == len(p) + n
                          for o, (p, n) in zip(outs, prompts)],
        "new_tokens": [o[len(p):] for o, (p, _) in zip(outs, prompts)],
        "paged_attention": eng.paged_attention,
        "kernels": _kernels(text),
        "warmup_programs": warm,
        "compiles_at_warmup": before,
        "compiles_after_traffic": after,
        # every program XLA built while requests ran, the engine's own
        # and any stray eager op's (reported, not judged)
        "xla_compiles_on_traffic": _count_xla_compiles() - xla_before,
        "prefix_hits": stats["prefix_cache"]["prefix_hits"],
        "prefill_chunks": stats["batches"]["prefill_chunks"],
        "stopped": not eng.stats()["engine"]["running"],
        "warmup_seconds_smoke": round(warmup_s, 2),
        "requests_seconds_smoke": round(serve_s, 3),
    }


def _greedy_margins(size: Size, seed: int, served, prompts) -> list:
    """How far below the argmax each served token sits in a plain float32
    forward pass over the same bf16-rounded weights, teacher-forced along
    the served sequence itself: per request, the largest such gap as a
    share of the largest |logit| at that position.  0 = the token is the
    reference's own greedy choice.  One padded batch, one program (causal
    attention: right padding cannot reach an earlier position)."""
    import mxnet_tpu as mx
    net = _net(size, seed)
    net.cast("bfloat16")
    net.cast("float32")
    net.hybridize()
    width = max(len(t) for t in served)
    batch = onp.zeros((len(served), width), "int32")
    for row, t in zip(batch, served):
        row[:len(t)] = t
    logits = net(mx.nd.array(batch, dtype="int32")).asnumpy()
    worst = []
    for row, toks, (_, n_new) in zip(logits, served, prompts):
        at = range(len(toks) - n_new - 1, len(toks) - 1)
        worst.append(max(float((row[i].max() - row[i, toks[i + 1]])
                               / onp.abs(row[i]).max()) for i in at))
    return worst


def run_serve(size: Size, seed: int) -> dict:
    """bf16 parameters, paged KV, the engine's default read arm off a
    mesh.  The served tokens are judged against a float32 reference within
    a bf16 tolerance; whether they also equal ``net.generate`` token for
    token (the repo's float32 contract, which near-ties of a random model
    can break in bf16) is reported."""
    import jax
    import mxnet_tpu as mx
    net = _net(size, seed)
    net.cast("bfloat16")
    prompts = _prompts(size, net.vocab_size, seed)
    facts = _serve(net, size, prompts)
    served = [p.tolist() + new
              for (p, _), new in zip(prompts, facts["new_tokens"])]
    facts["equals_generate"] = [
        net.generate(mx.nd.array(p[None], dtype="int32"), n,
                     temperature=0).asnumpy()[0].tolist() == got
        for (p, n), got in zip(prompts, served)]
    facts["greedy_margin"] = _greedy_margins(size, seed, served, prompts)
    facts["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    return facts


def check_serve(f: dict) -> list:
    bad = []
    if not all(f["echoes_prompt"]):
        bad.append(f"serve: a result is not its prompt plus the new tokens "
                   f"asked for: {f['echoes_prompt']}")
    for i, m in enumerate(f["greedy_margin"]):
        if not m <= _BF16_LOGIT_TOL:
            bad.append(f"serve: request {i} emitted a token {m:.4f} of the "
                       f"largest logit below the float32 reference's argmax "
                       f"(tolerance {_BF16_LOGIT_TOL})")
    if f["compiles_after_traffic"] != f["compiles_at_warmup"] or \
            f["compiles_at_warmup"] != f["warmup_programs"]:
        bad.append(f"serve: mxtpu_serving_compiles {f['compiles_at_warmup']}"
                   f" after warmup() of {f['warmup_programs']} programs, "
                   f"{f['compiles_after_traffic']} after traffic")
    if f["prefix_hits"] < 1:
        bad.append("serve: no prefix hit for the shared-prefix pair")
    if f["prefill_chunks"] < 2:
        bad.append("serve: the long prompt was not prefilled in chunks")
    if not f["stopped"]:
        bad.append("serve: engine still running after stop()")
    if f["paged_attention"] != "kernel":
        bad.append(f"serve: read arm {f['paged_attention']!r}, not the "
                   "paged kernel")
    # chip-only: the CPU rehearsal expects exactly this one to fail
    if f["kernels"] < 1:
        bad.append("serve: no paged kernel (tpu_custom_call) in the decode "
                   "program")
    return bad


# ------------------------------------------------------------------- mesh

def run_mesh(size: Size, seed: int, devices) -> dict:
    """Four devices against one: the same seeded batches through
    ``ShardedTrainer`` on dp=2 x tp=2 and on a one-device mesh, and the
    same prompts through ``InferenceEngine(mesh=4)`` and ``mesh=None``
    (float32 parameters: tensor parallelism reassociates sums, and greedy
    tokens are compared exactly)."""
    from mxnet_tpu import amp
    from mxnet_tpu import parallel as par

    devices = list(devices)[:4]
    amp.init("bfloat16")
    try:
        one = _train_steps(_net(size, seed), size,
                           par.make_mesh(devices=devices[:1]), seed)
        net4 = _net(size, seed)
        four = _train_steps(net4, size,
                            par.make_mesh(dp=2, tp=2, devices=devices), seed)
    finally:
        amp.reset()
    wte = net4.wte.weight.data().jax
    held = {d: 0 for d in devices}
    for p in net4.collect_params().values():
        for s in p.data().jax.addressable_shards:
            held[s.device] += s.data.nbytes
    in_use = [d.memory_stats() for d in devices]
    facts = {
        "one_device": one, "dp2_tp2": four,
        "shard_bytes_per_device": [held[d] for d in devices],
        "bytes_in_use_per_device": None if None in in_use else
        [m["bytes_in_use"] for m in in_use],
        "embedding_layout": {
            "shape": list(wte.shape), "spec": str(wte.sharding.spec),
            "shard_shapes": sorted({tuple(s.data.shape)
                                    for s in wte.addressable_shards}),
            "why": f"{wte.shape[0]} rows % tp=2 = {wte.shape[0] % 2}"},
    }
    del net4, wte

    net = _net(size, seed)
    prompts = _prompts(size, net.vocab_size, seed)
    facts["serve_one_device"] = _serve(net, size, prompts)
    facts["serve_mesh4"] = _serve(net, size, prompts, mesh=4)
    return facts


def check_mesh(f: dict) -> list:
    bad = []
    a, b = f["one_device"]["losses"], f["dp2_tp2"]["losses"]
    if not all(onp.isfinite(a + b)):
        bad.append(f"mesh: non-finite loss: {a} / {b}")
    elif not onp.allclose(a, b, rtol=_LOSS_RTOL):
        bad.append(f"mesh: dp=2 x tp=2 losses {b} leave one-device losses "
                   f"{a} by more than rtol={_LOSS_RTOL}")
    if min(f["shard_bytes_per_device"]) <= 0:
        bad.append(f"mesh: a device holds no parameter shard: "
                   f"{f['shard_bytes_per_device']}")
    if f["serve_mesh4"]["new_tokens"] != \
            f["serve_one_device"]["new_tokens"]:
        bad.append("mesh: InferenceEngine(mesh=4) tokens differ from "
                   "mesh=None")
    for arm in ("serve_one_device", "serve_mesh4"):
        s = f[arm]
        if s["compiles_after_traffic"] != s["compiles_at_warmup"]:
            bad.append(f"mesh: {arm} compiled on traffic")
    # chip-only: the CPU rehearsal expects exactly these to fail
    if not f["bytes_in_use_per_device"] or \
            min(f["bytes_in_use_per_device"]) <= 0:
        bad.append(f"mesh: memory_stats() bytes_in_use per device: "
                   f"{f['bytes_in_use_per_device']}")
    if f["dp2_tp2"]["kernels"] < 1:
        bad.append("mesh: no flash kernel in the dp=2 x tp=2 step")
    return bad


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the mesh phase and its one-device "
                         "comparison (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from mxnet_tpu.utils import native
    from mxnet_tpu.utils.platform import enable_compile_cache, require_tpu

    dev = require_tpu()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} on "
                         f"{device['count']} device(s)")
    _emit(phase="start", device=device, seed=args.seed,
          compile_cache_dir=enable_compile_cache(),
          native_io_built=native.available())

    bad = []
    if args.chips == 4:
        facts = run_mesh(FULL, args.seed, jax.devices())
        _emit(phase="mesh", **facts)
        bad += check_mesh(facts)
    else:
        for phase, run, check in (("train", run_train, check_train),
                                  ("serve", run_serve, check_serve)):
            facts = run(FULL, args.seed)
            _emit(phase=phase, **facts)
            bad += check(facts)
    if bad:
        for line in bad:
            print(f"chip_smoke: FAILED {line}", file=sys.stderr)
        return 1
    _emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
