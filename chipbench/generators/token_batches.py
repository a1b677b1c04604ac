"""Seeded token batches for a training job: an endless iterator of
``(tokens, labels)`` int32 arrays of shape (batch, seq), labels already
shifted by one.  Every row of every batch is drawn afresh, so all differ.

Parameters (``traffic["batches"]``): {"batch", "seq"}.
"""
from __future__ import annotations

import numpy as np


def generate(traffic: dict, seed: int, vocab: int):
    spec = traffic["batches"]
    batch, seq = int(spec["batch"]), int(spec["seq"])
    rng = np.random.default_rng(int(seed))
    while True:
        t = rng.integers(0, vocab, (batch, seq + 1)).astype("int32")
        yield t[:, :-1], t[:, 1:]
