"""Where a run's ``setup_s`` went, from what the program kept of itself.

``mxnet_tpu.observability.compiles`` keeps, with nothing switched on, one
record for each thing ``jax.monitoring`` tells it, ``(kind, end, seconds,
thread, name)`` with ``end`` on ``time.monotonic()`` (kinds ``trace``, ``lower``,
``compile``, ``cache_load`` and the instants ``cache_hit``, ``cache_miss``),
and one ``(start, end, settle_s, state_s, place_s)`` for each
``ShardedTrainer`` built.  The driver's window and ``setup_s`` are on the
same clock, so set-up is the stretch from ``window[0] - setup_s`` (where
``run.py`` read the clock first) to ``window[0]``, the cut: the reference
compiles in the same process AFTER the window and is not counted.

Intervals overlap (a cache load inside its compile, threads side by
side), so every time given here is the length of a UNION of intervals
``[end - seconds, end]`` clipped to set-up, never seconds added up.  The
seven ``setup.*`` readers under ``layer_metrics/`` each take one key of
``split``; a program that keeps no such log (the commits before it did)
gives None and the metric is left out of the line.
"""
from __future__ import annotations

from chipbench.harness import xtrace

# the metric a record's interval is counted under, by its kind
COUNTED_UNDER = {"trace": "trace_s", "lower": "trace_s",
                 "compile": "compile_s", "cache_load": "compile_s"}


def program_log():
    """``(records, builds)`` as the program kept them, or None where it
    keeps none or has lost some (its log holds the newest few thousand)."""
    try:
        from mxnet_tpu.observability import compiles
        records, builds = compiles.log(), compiles.builds()
        lost = compiles.dropped()
    except (ImportError, AttributeError):
        return None
    if lost or not (records or builds):
        return None
    return records, builds


def covered(intervals, lo, hi) -> float:
    """Length of the union of ``(start, end)`` intervals inside
    ``[lo, hi]``."""
    return sum(e - s for s, e in
               xtrace.merge(xtrace.clip(intervals, lo, hi)))


def split(run) -> dict | None:
    """The seven numbers of one run, by the part of the metric's name
    after ``setup.``; a number that has nothing to be read from is
    missing (no trainer built: no ``build_s``)."""
    log = program_log()
    if log is None:
        return None
    records, builds = log
    cut = run["window"][0]
    setup_s = run["e2e"]["setup_s"]
    begin = cut - setup_s
    spans = {"trace_s": [], "compile_s": []}
    out = {"programs": 0, "cache_misses": 0}
    for kind, end, seconds, *_ in records:
        if kind in COUNTED_UNDER:
            spans[COUNTED_UNDER[kind]].append((end - seconds, end))
        if begin < end <= cut:
            out["programs"] += kind == "compile"
            out["cache_misses"] += kind == "cache_miss"
    for key, intervals in spans.items():
        out[key] = covered(intervals, begin, cut)
    named = spans["trace_s"] + spans["compile_s"]
    built = [b for b in builds if begin <= b[0] and b[1] <= cut]
    if built:
        start, end = built[-1][:2]     # the trainer the window drives
        out["before_build_s"] = start - begin
        out["build_s"] = end - start
        named += [(begin, start), (start, end)]
    out["unnamed_s"] = setup_s - covered(named, begin, cut)
    return out


def read(run, key):
    """One reader's whole body: ``key`` of ``split``, or None."""
    got = split(run)
    return None if got is None else got.get(key)
