"""Trace reduction: interval arithmetic on hand-made intervals, and the
whole reduction on the small trace recorded on the chip."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench.harness import xtrace  # noqa: E402

SAMPLE = os.path.join(os.path.dirname(xtrace.__file__), "..", "data",
                      "sample.xplane.pb.gz")


def test_union_counts_overlaps_once():
    union = lambda iv: sum(e - s for s, e in xtrace.merge(iv))
    assert union([(0, 2), (1, 3), (5, 6)]) == 4
    assert union([(0, 1), (0, 1)]) == 1
    assert union([]) == 0
    assert xtrace.merge([(1, 3), (0, 2), (3, 4)]) == [(0, 4)]


def test_clip_and_gaps():
    busy = xtrace.merge(xtrace.clip([(0, 2), (3, 5), (8, 12)], 1, 10))
    assert busy == [(1, 2), (3, 5), (8, 10)]
    assert xtrace.gaps(busy, 1, 10) == [(2, 3), (5, 8)]
    assert xtrace.gaps([], 0, 4) == [(0, 4)]


def test_gap_named_by_host_range():
    host = [("chipbench:window", 0.0, 10.0), ("marker:serving:decode", 1.0,
             2.0),
            ("marker:serving:prefill", 4.0, 6.0)]
    assert xtrace._name_gap(host, 4.5, 5.5) == "marker:serving:prefill"
    assert xtrace._name_gap(host, 2.5, 3.5) == "after marker:serving:decode"
    assert xtrace._name_gap([], 0, 1) == "unattributed"
    markers = [h for h in host if h[0].startswith("marker:")]
    assert xtrace._launcher(markers, 1.5) == "marker:serving:decode"
    assert xtrace._launcher(markers, 7.0) == "marker:serving:prefill"
    assert xtrace._launcher(markers, 0.5) is None


@pytest.mark.parametrize("key", ["busy", "idle", "ops", "gaps"])
def test_reduce_recorded_trace(key):
    r = xtrace.reduce(SAMPLE)
    assert r["n_devices"] >= 1
    if key == "busy":
        assert 0 < r["busy_s"] <= r["window_s"]
        # leaf operations run one at a time on the core: their sum is
        # the union, less what only the loops around them account for
        leaf = sum(r["op_seconds"].values())
        assert 0.95 * r["busy_s"] <= leaf <= r["busy_s"] + 1e-9
        assert 0 < r["kernel_seconds"] < leaf
    elif key == "idle":
        idle = r["window_s"] - r["busy_s"]
        assert abs(idle - sum(s for _n, s in
                              xtrace.reduce(SAMPLE, top=10 ** 6)["idle_gaps"])
                   ) < 1e-6
    elif key == "ops":
        assert len(r["device_ops"]) <= 10
        secs = [s for _n, s in r["device_ops"]]
        assert secs == sorted(secs, reverse=True) and secs[0] > 0
    else:
        assert r["idle_gaps"] and all(s > 0 for _n, s in r["idle_gaps"])
