"""One process-wide count of XLA backend compiles.

``jax.monitoring`` reports ``/jax/core/compile/backend_compile_duration``
each time a program goes to the backend for an executable — a cold
compile and a persistent-cache load alike, never an in-memory hit — on
the thread that asked for it.  This module listens once per process and
keeps two counts:

- the registry counter ``mxtpu_xla_compiles_total``, what a scraper
  alerts on once warm-up is over;
- a per-thread count, so a caller can tell what ITS OWN calls compiled
  (``on_this_thread()`` before and after) whatever other threads do
  meanwhile.  ``ShardedTrainer.step`` records ``trainer.compile`` from
  it and the serving engine feeds ``mxtpu_serving_compiles`` and
  ``warmup()``'s return from it, in place of guessing from the first
  call per shape bucket (which a committed-vs-uncommitted argument
  defeats: the program compiles again and the guess stays put).
"""
from __future__ import annotations

import threading

import jax.monitoring

from .registry import default_registry

__all__ = ["on_this_thread"]

_EVENT = "/jax/core/compile/backend_compile_duration"
_tls = threading.local()


def _on_duration(name, *_a, **_kw):
    if name != _EVENT:
        return
    _tls.n = getattr(_tls, "n", 0) + 1
    # looked up per compile, not cached: a registry reset() between two
    # compiles must not leave the count on an orphaned metric
    default_registry().counter(
        "mxtpu_xla_compiles_total",
        help="XLA backend compiles in this process (persistent-cache "
             "loads included, in-memory hits not) — flat once every "
             "program is warm").inc()


def on_this_thread() -> int:
    """Compiles the calling thread has caused so far.  A compile runs
    on the thread whose call needed the executable, so the difference
    over a call is what that call compiled."""
    return getattr(_tls, "n", 0)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
