"""The benchmark's arithmetic against hand arithmetic: FLOP and byte
counts, the peaks table, exact percentiles, seeded weights and traffic."""
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness import counts, stats  # noqa: E402
from chipbench.harness.peaks import peaks  # noqa: E402

S124 = dict(n_layer=12, n_embd=768, n_head=12, vocab_size=50257,
            n_positions=1024)
S774 = dict(n_layer=36, n_embd=1280, n_head=20, vocab_size=50257,
            n_positions=1024)


@pytest.mark.parametrize("sizes,seq,want", [
    # 6 (12 L U^2 + U V) + 12 L U T
    (S124, 1024, 6 * (12 * 12 * 768 ** 2 + 768 * 50257)
     + 12 * 12 * 768 * 1024),
    (S774, 1024, 6 * (12 * 36 * 1280 ** 2 + 1280 * 50257)
     + 12 * 36 * 1280 * 1024),
])
def test_train_flops_per_token(sizes, seq, want):
    got = counts.gpt2_train_flops_per_token(sizes, seq)
    assert got == want
    # the figures PERF.md and the issue quote
    assert round(got / 1e6) in (854, 5199)


@pytest.mark.parametrize("sizes,want", [(S124, 124_439_808),
                                        (S774, 774_030_080)])
def test_param_count(sizes, want):
    assert counts.gpt2_params(sizes) == want


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(S774, 2) == 36 * 2 * 1280 * 2 == 184_320


def test_flash_counts_one_shape():
    # B=8, H=12, T=1024, D=64, bf16, causal
    f, b = counts.flash_flops_bytes(8, 12, 1024, 64, 2, causal=True)
    assert f == 4 * 8 * 12 * 1024 * 1024 * 64 / 2
    assert b == 4 * 8 * 12 * 1024 * 64 * 2
    fb, bb = counts.flash_flops_bytes(8, 12, 1024, 64, 2, causal=True,
                                      backward=True)
    assert fb == 2.5 * f and bb == 2 * b


def test_paged_counts_one_shape():
    # two rows, 100 and 16 tokens of context, one query each, H=20, D=64,
    # pages of 16, bf16: 7 + 1 pages of K and of V
    f, b = counts.paged_flops_bytes([100, 16], 1, 20, 64, 16, 2)
    assert f == 4 * (100 + 16) * 20 * 64
    assert b == 2 * 8 * 16 * 20 * 64 * 2 + 2 * 2 * 20 * 64 * 2


def test_roofline_names_the_bound():
    pk = peaks("TPU v5 lite")
    t, bound = counts.roofline_seconds(197e12, 1.0, pk)
    assert (round(t, 6), bound) == (1.0, "compute")
    t, bound = counts.roofline_seconds(1.0, 819e9, pk)
    assert (round(t, 6), bound) == (1.0, "memory")


def test_peaks_refuse_unknown_device():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


@pytest.mark.parametrize("xs,q,want", [
    ([1.0], 95, 1.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    (list(range(101)), 95, 95.0),
    ([1, 2, 3, math.inf], 50, 2.5),
    ([1, 2, math.inf, math.inf], 95, math.inf),
])
def test_percentile_exact(xs, q, want):
    assert stats.percentile(xs, q) == want


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _traffic(process="poisson", **arr):
    return {"shape_seed": 7, "requests": {
        "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.7,
                       "min": 8, "max": 90},
        "new_tokens": {"dist": "uniform", "min": 2, "max": 9},
        "max_total": 96,
        "arrivals": dict(process=process, **arr)}}


def test_requests_same_work_for_every_seed():
    from chipbench.generators import requests as gen

    a = gen.generate(_traffic(rate=20.0), 1, 10.0, 128)
    b = gen.generate(_traffic(rate=20.0), 2 ** 31 + 5, 10.0, 128)
    again = gen.generate(_traffic(rate=20.0), 1, 10.0, 128)
    pairs = lambda rs: sorted((len(r["tokens"]), r["new_tokens"])
                              for r in rs)
    assert len(a) == len(b) == 200
    assert pairs(a) == pairs(b)                  # the same multiset
    assert [len(r["tokens"]) for r in a] != [len(r["tokens"]) for r in b]
    from collections import Counter
    gaps = lambda rs: Counter(np.round(np.diff([r["t"] for r in rs]), 9))
    # the same multiset of gaps in another order (each run leaves out
    # one: the gap before its first arrival)
    assert sum((gaps(a) - gaps(b)).values()) <= 1
    assert all(np.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(a, again))        # same seed, same inputs
    assert a[0]["t"] == 0.0 and a[-1]["t"] < 10.0
    assert all(len(r["tokens"]) + r["new_tokens"] <= 96 for r in a)


def test_window_requests_are_one_multiset():
    """With a lead-in, the requests due after it are the same for every
    seed, and so is their number."""
    from chipbench.generators import requests as gen

    def due_in_window(seed):
        rs = gen.generate(_traffic(rate=5.0), seed, 12.0, 128,
                          lead_in_s=2.0)
        assert sum(1 for r in rs if r["t"] < 2.0) == 10
        return sorted((len(r["tokens"]), r["new_tokens"])
                      for r in rs if r["t"] >= 2.0)

    a, b = due_in_window(1), due_in_window(2 ** 31 + 9)
    assert len(a) == 50 and a == b


def test_requests_closed_pool():
    from chipbench.generators import requests as gen

    reqs = gen.generate(_traffic(process="closed", pool_per_s=5), 3, 4.0,
                        128)
    assert len(reqs) == 20 and all(r["t"] is None for r in reqs)


def test_unknown_arrival_process_is_refused():
    from chipbench.generators import requests as gen

    with pytest.raises(ValueError, match="unknown arrival process"):
        gen.generate(_traffic("gamma", rate=50.0, cv=3.0), 1, 20.0, 128)


def test_token_batches_rows_all_differ():
    from chipbench.generators import token_batches as gen

    it = gen.generate({"batches": {"batch": 4, "seq": 16}}, 2 ** 31 + 1,
                      128)
    tokens, labels = next(it)
    assert tokens.shape == labels.shape == (4, 16)
    assert np.array_equal(tokens[:, 1:], labels[:, :-1])
    assert len({tuple(r) for r in tokens}) == 4
    again = next(gen.generate({"batches": {"batch": 4, "seq": 16}},
                              2 ** 31 + 1, 128))
    assert np.array_equal(tokens, again[0])
