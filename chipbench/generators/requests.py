"""The general request generator: a traffic file's parameters and a seed
give a list of requests ``{"t", "tokens", "new_tokens"}``.

Every seed gets the SAME multiset of (prompt length, new tokens) pairs and
the same multiset of gaps between arrivals — drawn once from the traffic
file's own ``shape_seed`` — in another order, with other token ids.  So
the work of a run does not change with the seed; only its order does.
(With one multiset for the whole run the chat cell's completed tokens
still swung 15% from seed to seed, by which requests fell inside the
window: my chip runs, PR 23.  So the lead-in is a multiset of its own.)

Parameters (``traffic["requests"]``):
  prompt_len / new_tokens: {"dist": "lognormal", "median", "sigma", "min",
      "max"} | {"dist": "uniform", "min", "max"} | {"dist": "fixed",
      "value"}
  max_total: prompt + new tokens is cut to this (new tokens give way)
  arrivals: {"process": "poisson", "rate"} | {"process": "closed",
      "pool_per_s"} (no due times: a pool for closed-loop clients)
"""
from __future__ import annotations

import math

import numpy as np


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full((n,), int(spec["value"]), "int64")
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = np.exp(math.log(spec["median"])
                   + spec["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype("int64")
    raise ValueError(f"unknown length distribution {dist!r}")


def _gaps(spec: dict, n: int, rng) -> np.ndarray:
    rate = float(spec["rate"])
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = rng.exponential(1.0, n)
    # the same offered load in every run: n arrivals in exactly n / rate s
    return g * (n / rate) / g.sum()


def _segment(spec, arr, n, shape, rng, vocab, closed):
    """``n`` requests of one stretch of traffic: the shapes (and gaps) that
    ``shape`` draws, in the order and with the token ids that ``rng``
    draws.  Due times start at 0."""
    plen = _lengths(spec["prompt_len"], n, shape)
    new = _lengths(spec["new_tokens"], n, shape)
    new = np.maximum(1, np.minimum(new, int(spec["max_total"]) - plen))
    gaps = None if closed else _gaps(arr, n, shape)

    order = rng.permutation(n)
    plen, new = plen[order], new[order]
    if gaps is not None:
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]          # first arrival at 0
    out = []
    for i in range(n):
        tokens = rng.integers(0, vocab, int(plen[i])).astype("int32")
        out.append({"t": None if closed else float(due[i]),
                    "tokens": tokens, "new_tokens": int(new[i])})
    return out


def generate(traffic: dict, seed: int, horizon_s: float, vocab: int,
             rate: float | None = None, lead_in_s: float = 0.0) -> list:
    """Requests for ``horizon_s`` seconds of traffic, of which the first
    ``lead_in_s`` are a lead-in.  The lead-in and the rest are drawn and
    shuffled apart, so the requests DUE in the measured window are one
    multiset for every seed, and only the window's edges (what the
    lead-in leaves in flight, what the end cuts off) change with the
    seed.  ``rate`` overrides the file's arrival rate (the sweep that
    finds the knee uses it)."""
    spec = traffic["requests"]
    arr = dict(spec["arrivals"])
    if rate is not None:
        arr["rate"] = rate
    closed = arr["process"] == "closed"
    per_s = float(arr["pool_per_s"] if closed else arr["rate"])
    shape = np.random.default_rng(int(traffic["shape_seed"]))
    rng = np.random.default_rng(int(seed))
    spans = [horizon_s] if closed or lead_in_s <= 0 else \
        [lead_in_s, horizon_s - lead_in_s]
    out, start = [], 0.0
    for span in spans:
        n = max(1, int(math.ceil(per_s * span)))
        for r in _segment(spec, arr, n, shape, rng, vocab, closed):
            if not closed:
                r["t"] += start
            out.append(r)
        start += span
    return out
