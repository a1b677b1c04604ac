"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy union and idle share, time per device
operation and per program, which host range launched each program, and
the longest idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  On a TPU the device planes
are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed operation and ``XLA Modules`` one per executed program.  Host
ranges (``TraceAnnotation``) sit on the thread lines of ``/host:CPU``;
both share one clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_ANNOTATION = "chipbench:window"
_HOST_PREFIXES = ("marker:", "span:", "chipbench:")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """(start, end) intervals with overlaps joined: the busy union."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    """Idle (start, end) stretches of [lo, hi] between merged busy ones."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


_HLO = re.compile(r"^(%\S+) = (.*?)\s([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """A device event's name is the operation's whole HLO text on a TPU;
    keep its opcode (with a custom call's target) and the shape it
    produces, so that the 36 layers' copies of one operation add up
    under one name."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    ident, shape, opcode = m.groups()
    if opcode == "custom-call":
        t = _TARGET.search(name)
        opcode += ":" + (t.group(1) if t else "?")
    shape = re.sub(r"\{[^}]*\}", "", shape)
    return f"{opcode} {shape}"[:120]


def is_kernel(short: str) -> bool:
    """A Pallas (Mosaic) kernel's execution."""
    return short.startswith("custom-call:tpu_custom_call ")


def _events(line, shorten=False):
    return [(short_name(e.name) if shorten else e.name, e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def _profile(path: str):
    """``ProfileData`` of an ``.xplane.pb``, gzipped or not."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


# operations that only contain others (their time is their bodies')
_CONTAINERS = ("while ", "conditional ", "call ")


def load(path: str) -> dict:
    """Planes of interest as plain lists: ``devices`` (one dict per device
    plane with ``ops`` and ``modules``: (name, start_s, end_s)) and
    ``host`` (annotation ranges)."""
    data = _profile(path)
    devices, host = [], []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and "Core" not in name.split(
                ":")[-1]:
            dev = {"name": name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = _events(line, shorten=True)
                elif line.name == "XLA Modules":
                    dev["modules"] = _events(line)
            devices.append(dev)
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev[0].startswith(_HOST_PREFIXES):
                        host.append(ev)
    devices.sort(key=lambda d: d["name"])
    host.sort(key=lambda ev: ev[1])
    return {"devices": devices, "host": host}


def _window(host, devices) -> Tuple[float, float]:
    for name, s, e in host:
        if name == WINDOW_ANNOTATION:
            return s, e
    starts = [ev[1] for d in devices for ev in d["ops"]]
    ends = [ev[2] for d in devices for ev in d["ops"]]
    if not starts:
        raise ValueError("trace holds no device operation")
    return min(starts), max(ends)


def _name_gap(host, s: float, e: float) -> str:
    """What the host was doing in an idle gap: the innermost host range
    covering its midpoint, else the last range that ended before it."""
    mid = 0.5 * (s + e)
    covering = [ev for ev in host
                if ev[1] <= mid <= ev[2] and ev[0] != WINDOW_ANNOTATION]
    if covering:
        return min(covering, key=lambda ev: ev[2] - ev[1])[0]
    before = [ev for ev in host
              if ev[2] <= mid and ev[0] != WINDOW_ANNOTATION]
    if before:
        return "after " + max(before, key=lambda ev: ev[2])[0]
    return "unattributed"


def _launcher(host_markers, start: float) -> Optional[str]:
    """The last ``marker:`` range opened before a program started."""
    last = None
    for name, s, _e in host_markers:
        if s > start:
            break
        last = name
    return last


def reduce(path: str, top: int = 10) -> dict:
    """The reduced trace.  Keys: ``window_s``; ``busy_s`` (mean over
    devices) and ``busy_s_per_device``; ``op_seconds`` {name: s} and
    ``op_counts`` on device 0; ``modules`` [(name, launcher, start_s,
    dur_s)] on device 0; ``device_ops`` top-``top`` [[name, s]];
    ``idle_gaps`` top-``top`` [[what the host did, s]], merged by name."""
    planes = load(path)
    devices, host = planes["devices"], planes["host"]
    if not devices:
        raise ValueError(f"no /device:TPU plane in {path}")
    lo, hi = _window(host, devices)
    busy_per_dev, merged0 = [], []
    for i, dev in enumerate(devices):
        m = merge(clip([(s, e) for _n, s, e in dev["ops"]], lo, hi))
        busy_per_dev.append(sum(e - s for s, e in m))
        if i == 0:
            merged0 = m
    op_seconds: Dict[str, float] = {}
    op_counts: Dict[str, int] = {}
    for name, s, e in devices[0]["ops"]:
        if e <= lo or s >= hi or name.startswith(_CONTAINERS):
            continue
        d = min(e, hi) - max(s, lo)
        op_seconds[name] = op_seconds.get(name, 0.0) + d
        op_counts[name] = op_counts.get(name, 0) + 1
    markers = [ev for ev in host if ev[0].startswith("marker:")]
    modules = [(name, _launcher(markers, s), s - lo, e - s)
               for name, s, e in devices[0]["modules"]
               if s >= lo and e <= hi]
    gap_by_name: Dict[str, float] = {}
    for s, e in gaps(merged0, lo, hi):
        n = _name_gap(host, s, e)
        gap_by_name[n] = gap_by_name.get(n, 0.0) + (e - s)
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy_per_dev) / len(busy_per_dev),
        "busy_s_per_device": busy_per_dev,
        "n_devices": len(devices),
        "op_seconds": op_seconds, "op_counts": op_counts,
        "kernel_seconds": sum(v for k, v in op_seconds.items()
                              if is_kernel(k)),
        "modules": modules,
        "device_ops": rank(op_seconds),
        "idle_gaps": rank(gap_by_name),
    }


def describe(path: str, top: int = 40) -> str:
    """A look at a trace by hand: every plane and line with its event
    count, and the ``top`` event names of each device line by time."""
    out = []
    for plane in _profile(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device:"):
                names = sorted({e.name for e in evs
                                if e.name.startswith(_HOST_PREFIXES)})
                if names:
                    out.append(f"    host ranges: {names[:20]}")
                continue
            tot = {}
            for e in evs:
                t = tot.setdefault(e.name, [0.0, 0])
                t[0] += e.duration_ns * 1e-9
                t[1] += 1
            for name, (sec, n) in sorted(tot.items(),
                                         key=lambda kv: -kv[1][0])[:top]:
                out.append(f"    {sec:10.6f} s  x{n:<6d} {name[:140]}")
            if evs:
                e = evs[0]
                out.append(f"    first event stats: "
                           f"{[(k, str(v)[:60]) for k, v in e.stats][:12]}")
    return "\n".join(out)
