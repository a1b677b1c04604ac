"""Trace-replay load generator for the serving fleet.

An elastic fleet is only as good as the load you prove it against.
This tool synthesizes DETERMINISTIC arrival traces (seeded
nonhomogeneous Poisson: diurnal ramp, 10x flash spike, prompt-family
shift), records them as JSONL, replays recorded traces against any
``submit(...)``-shaped target (an ``InferenceEngine``, a
``FleetRouter``, or a stub), and reports what happened: issued /
completed / typed-error counts, per-request latency, and — the number
the autoscaler's tests live on — whether anything was LOST (submitted
but never resolved).

Trace events are plain dicts::

    {"t": 0.137,            # arrival offset, seconds from trace start
     "family": 3,           # prompt-family id (shared prefix head)
     "tokens": [5, 17, ...] # int token ids
     "priority": "interactive" | "best_effort",
     "max_new_tokens": 4}

Determinism contract: the same builder arguments + seed produce the
same trace, byte-for-byte after JSONL round-trip — replay-driven
tests and chaos scenarios compare runs on identical arrivals, so
the generator must never consult wall-clock or global RNG state.

Usage::

    python tools/loadgen.py --shape flash_spike --duration 10 \
        --base-rps 5 --spike-factor 10 --out trace.jsonl
    python tools/loadgen.py --replay trace.jsonl --dry-run
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Callable, List, Optional

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

__all__ = ["diurnal", "flash_spike", "family_shift", "make_prompts",
           "save_trace", "load_trace", "replay", "arrival_times"]

TRACE_SCHEMA_VERSION = 1


# ------------------------------------------------------------- arrivals
def arrival_times(rate_fn: Callable[[float], float], duration: float,
                  seed: int, max_rate: Optional[float] = None) -> List[float]:
    """Nonhomogeneous Poisson arrivals on ``[0, duration)`` with
    instantaneous rate ``rate_fn(t)`` (req/s), by Lewis-Shedler
    thinning: draw candidate gaps at the peak rate, keep each candidate
    with probability ``rate(t)/max_rate``.  Seeded ``RandomState`` —
    identical inputs give identical arrivals on any host."""
    if max_rate is None:
        max_rate = max(rate_fn(duration * i / 256.0) for i in range(257))
    if max_rate <= 0:
        return []
    rs = onp.random.RandomState(seed)
    out, t = [], 0.0
    while True:
        t += float(rs.exponential(1.0 / max_rate))
        if t >= duration:
            return out
        if rs.uniform() * max_rate <= rate_fn(t):
            out.append(round(t, 6))


def _events(times: List[float], *, families: int, family_weights,
            shared_len: int, tail_len: int, vocab: int, seed: int,
            max_new_tokens: int, interactive_frac: float,
            family_of: Optional[Callable[[float, int], int]] = None
            ) -> List[dict]:
    """Attach prompts to arrival times.  Each family is a shared
    ``shared_len``-token head (the prefix the fleet should keep warm)
    plus a per-request ``tail_len``-token unique suffix."""
    rs = onp.random.RandomState(seed + 1)
    heads = [rs.randint(0, vocab, (shared_len,)).tolist()
             for _ in range(families)]
    w = onp.asarray(family_weights, "float64")
    w = w / w.sum()
    events = []
    for i, t in enumerate(times):
        if family_of is not None:
            fam = int(family_of(t, i)) % families
        else:
            fam = int(rs.choice(families, p=w))
        tail = rs.randint(0, vocab, (tail_len,)).tolist()
        pri = "interactive" if rs.uniform() < interactive_frac \
            else "best_effort"
        events.append({"t": t, "family": fam,
                       "tokens": heads[fam] + tail, "priority": pri,
                       "max_new_tokens": max_new_tokens})
    return events


# ------------------------------------------------------------- builders
def diurnal(duration: float = 30.0, base_rps: float = 2.0,
            peak_rps: float = 8.0, *, seed: int = 0, families: int = 4,
            shared_len: int = 10, tail_len: int = 3, vocab: int = 61,
            max_new_tokens: int = 4, interactive_frac: float = 0.7
            ) -> List[dict]:
    """A compressed day: rate ramps sinusoidally base → peak → base
    over ``duration``.  The shape the autoscaler's hysteresis must
    track without thrashing — one growth leg, one shrink leg."""
    def rate(t):
        return base_rps + (peak_rps - base_rps) * \
            0.5 * (1.0 - math.cos(2.0 * math.pi * t / duration))
    times = arrival_times(rate, duration, seed, max_rate=peak_rps)
    return _events(times, families=families,
                   family_weights=[1.0] * families, shared_len=shared_len,
                   tail_len=tail_len, vocab=vocab, seed=seed,
                   max_new_tokens=max_new_tokens,
                   interactive_frac=interactive_frac)


def flash_spike(duration: float = 20.0, base_rps: float = 2.0,
                spike_factor: float = 10.0, spike_start: float = 0.35,
                spike_len: float = 0.25, *, seed: int = 0,
                families: int = 4, shared_len: int = 10, tail_len: int = 3,
                vocab: int = 61, max_new_tokens: int = 4,
                interactive_frac: float = 0.7) -> List[dict]:
    """Steady base load with a ``spike_factor``x step spike over
    ``[spike_start, spike_start + spike_len]`` (fractions of
    ``duration``).  The brownout/scale-up forcing function: the spike
    front must be absorbed by shedding best_effort while the
    autoscaler's evidence accumulates, and the spike tail must not
    leave the fleet over-provisioned."""
    t0, t1 = spike_start * duration, (spike_start + spike_len) * duration

    def rate(t):
        return base_rps * (spike_factor if t0 <= t < t1 else 1.0)
    times = arrival_times(rate, duration, seed,
                          max_rate=base_rps * spike_factor)
    return _events(times, families=families,
                   family_weights=[1.0] * families, shared_len=shared_len,
                   tail_len=tail_len, vocab=vocab, seed=seed,
                   max_new_tokens=max_new_tokens,
                   interactive_frac=interactive_frac)


def family_shift(duration: float = 20.0, rps: float = 4.0,
                 shift_at: float = 0.5, *, seed: int = 0,
                 families: int = 6, shared_len: int = 10, tail_len: int = 3,
                 vocab: int = 61, max_new_tokens: int = 4,
                 interactive_frac: float = 0.7) -> List[dict]:
    """Constant rate, shifting prompt population: the first half draws
    from the first half of the families, the second half from the
    rest.  Exercises affinity re-convergence and prefix-pool churn —
    the directory and HRW keys from the old families must not pin the
    new ones to cold replicas."""
    cut = shift_at * duration
    half = max(1, families // 2)

    def fam(t, i):
        rs = onp.random.RandomState(seed + 7919 * (i + 1))
        return int(rs.randint(0, half)) if t < cut \
            else half + int(rs.randint(0, families - half))
    times = arrival_times(lambda t: rps, duration, seed, max_rate=rps)
    return _events(times, families=families,
                   family_weights=[1.0] * families, shared_len=shared_len,
                   tail_len=tail_len, vocab=vocab, seed=seed,
                   max_new_tokens=max_new_tokens,
                   interactive_frac=interactive_frac, family_of=fam)


def make_prompts(trace: List[dict]):
    """The trace's prompts as int32 arrays, in arrival order."""
    return [onp.asarray(ev["tokens"], "int32") for ev in trace]


# --------------------------------------------------------------- JSONL
def save_trace(trace: List[dict], path: str) -> None:
    with open(path, "w") as f:
        f.write(json.dumps({"schema": TRACE_SCHEMA_VERSION,
                            "events": len(trace)}) + "\n")
        for ev in trace:
            f.write(json.dumps(ev, sort_keys=True) + "\n")


def load_trace(path: str) -> List[dict]:
    with open(path) as f:
        head = json.loads(f.readline())
        if head.get("schema") != TRACE_SCHEMA_VERSION:
            raise ValueError(f"trace schema {head.get('schema')!r} != "
                             f"{TRACE_SCHEMA_VERSION}")
        return [json.loads(line) for line in f if line.strip()]


# --------------------------------------------------------------- replay
def replay(trace: List[dict], target, *, speed: float = 1.0,
           timeout: float = 60.0, on_tick: Optional[Callable] = None
           ) -> dict:
    """Replay ``trace`` against ``target`` (anything with the engine's
    ``submit(prompt, max_new_tokens=..., priority=..., temperature=...)
    -> future`` shape) at ``speed``x recorded pacing, then resolve
    every future.

    The report's headline invariant is **nothing lost**: every
    submitted request resolves with tokens or a TYPED error inside
    ``timeout``.  ``lost`` counts futures that did neither — any
    nonzero value is a serving bug, not load.

    ``on_tick(now_offset)`` is called between arrivals (the hook the
    flash-spike chaos scenario uses to drive autoscaler ticks on the
    replay clock)."""
    futs, issued, rejected = [], 0, {}
    by_pri = {}

    def _pri(ev):
        return by_pri.setdefault(ev["priority"],
                                 {"issued": 0, "completed": 0,
                                  "rejected": 0, "errors": 0, "lost": 0})
    start = time.monotonic()
    for ev in trace:
        due = start + ev["t"] / max(1e-9, speed)
        while True:
            now = time.monotonic()
            if now >= due:
                break
            if on_tick is not None:
                on_tick(now - start)
            time.sleep(min(0.005, due - now))
        try:
            f = target.submit(onp.asarray(ev["tokens"], "int32"),
                              max_new_tokens=ev["max_new_tokens"],
                              priority=ev["priority"], temperature=0)
            futs.append((ev, f))
            issued += 1
            _pri(ev)["issued"] += 1
        except Exception as e:
            # typed admission refusal (queue full, brownout shed) is a
            # counted outcome, not a loss
            rejected[type(e).__name__] = \
                rejected.get(type(e).__name__, 0) + 1
            _pri(ev)["rejected"] += 1
    completed, lost = 0, 0
    errors = {}
    for ev, f in futs:
        try:
            f.result(timeout)
            completed += 1
            _pri(ev)["completed"] += 1
        except Exception as e:
            name = type(e).__name__
            if name in ("TimeoutError",):
                lost += 1
                _pri(ev)["lost"] += 1
            else:
                errors[name] = errors.get(name, 0) + 1
                _pri(ev)["errors"] += 1
    wall = time.monotonic() - start
    return {
        "events": len(trace), "issued": issued, "completed": completed,
        "rejected": rejected, "errors": errors, "lost": lost,
        "by_priority": by_pri,
        "wall_seconds": round(wall, 3),
        "throughput_rps": round(completed / wall, 3) if wall > 0 else 0.0,
    }


# ----------------------------------------------------------------- CLI
_SHAPES = {"diurnal": diurnal, "flash_spike": flash_spike,
           "family_shift": family_shift}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--shape", choices=sorted(_SHAPES),
                   default="flash_spike")
    p.add_argument("--duration", type=float, default=20.0)
    p.add_argument("--base-rps", type=float, default=2.0)
    p.add_argument("--peak-rps", type=float, default=8.0)
    p.add_argument("--spike-factor", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None, help="write trace JSONL here")
    p.add_argument("--replay", default=None,
                   help="replay a recorded trace instead of generating")
    p.add_argument("--dry-run", action="store_true",
                   help="with --replay: print the trace summary, "
                        "submit nothing")
    args = p.parse_args(argv)
    if args.replay:
        trace = load_trace(args.replay)
        if args.dry_run:
            fams = {}
            for ev in trace:
                fams[ev["family"]] = fams.get(ev["family"], 0) + 1
            dur = trace[-1]["t"] if trace else 0.0
            print(json.dumps({"events": len(trace),
                              "duration": dur, "families": fams},
                             sort_keys=True))
            return 0
        print("replay needs a programmatic target — import "
              "tools.loadgen.replay() from a test or a tool",
              file=sys.stderr)
        return 2
    if args.shape == "diurnal":
        trace = diurnal(args.duration, args.base_rps, args.peak_rps,
                        seed=args.seed)
    elif args.shape == "flash_spike":
        trace = flash_spike(args.duration, args.base_rps,
                            args.spike_factor, seed=args.seed)
    else:
        trace = family_shift(args.duration, args.base_rps,
                             seed=args.seed)
    if args.out:
        save_trace(trace, args.out)
    print(json.dumps({"shape": args.shape, "events": len(trace),
                      "duration": args.duration}, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
