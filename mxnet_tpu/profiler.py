"""``mx.profiler`` — profiling bridge (parity: python/mxnet/profiler.py +
src/profiler/*, SURVEY.md §5.1).

TPU-first: the engine-level Opr timestamping is replaced by XLA/TPU's own
tracing — ``set_state('run')`` starts a ``jax.profiler`` trace whose output
(TensorBoard/perfetto protobuf) carries per-op device timelines with XLA
annotations, strictly more detail than the Chrome-trace the MXNet profiler
emitted.  The mx.profiler API surface (set_config/set_state/dump/Task/
Frame/Marker/pause/resume) is preserved.
"""
from __future__ import annotations

import os
import time
from typing import Optional

from . import base as _base

__all__ = ["set_config", "set_state", "dump", "dumps", "pause", "resume",
           "Task", "Frame", "Marker", "scope"]

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
_state = {"running": False, "dir": None, "t0": None}


def set_config(**kwargs):
    """Accepts MXNet profiler knobs; `filename` decides the dump directory."""
    _config.update(kwargs)


def set_state(state="stop", profile_process="worker"):
    import jax
    if state == "run" and not _state["running"]:
        logdir = os.path.splitext(_config["filename"])[0] + "_tpu_profile"
        os.makedirs(logdir, exist_ok=True)
        jax.profiler.start_trace(logdir)
        _state.update(running=True, dir=logdir, t0=time.time())
    elif state == "stop" and _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False


def pause(profile_process="worker"):
    """MXNet pause ≈ stop collecting; jax traces can't pause, so stop."""
    if _state["running"]:
        set_state("stop")
        _state["paused"] = True


def resume(profile_process="worker"):
    if _state.get("paused"):
        set_state("run")
        _state["paused"] = False


def dump(finished=True, profile_process="worker"):
    """Finish the trace; the perfetto/TensorBoard files land in the logdir
    derived from set_config(filename=...)."""
    if _state["running"]:
        set_state("stop")
    return _state["dir"]


def dumps(reset=False):
    """Aggregate stats summary string (parity: mx.profiler.dumps)."""
    d = _state["dir"]
    if d is None:
        return "(profiler never ran)"
    n = sum(len(files) for _, _, files in os.walk(d))
    return (f"Profile data in {d} ({n} files) — load with TensorBoard "
            f"or ui.perfetto.dev")


class _Annotation:
    """Named range visible in the device trace (parity: profiler.Task/Frame/
    Marker custom ranges; backed by jax.profiler.TraceAnnotation)."""

    def __init__(self, name: str):
        self.name = name
        self._ann = None

    def start(self):
        import jax
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Task(_Annotation):
    pass


class Frame(_Annotation):
    pass


class Marker:
    def __init__(self, name: str):
        self.name = name

    def mark(self, scope_="process", value=None):
        """Instant event in the device trace.  ``value`` (int/float/str)
        is embedded in the annotation name so counters exported by the
        serving layer (queue depth, batch size, shed events) line up
        with the XLA ops around them in the timeline."""
        import jax
        name = f"marker:{self.name}" if value is None else \
            f"marker:{self.name}={value}"
        with jax.profiler.TraceAnnotation(name):
            pass



def scope(name: str):
    """Context manager annotating a named range (jax.profiler bridge)."""
    return _Annotation(name)
