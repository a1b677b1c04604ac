"""``mxnet_tpu.observability`` — the unified telemetry plane.

One coherent surface over what used to be four disconnected ones
(serving histograms, profiler markers, monitor NaN provenance, ad-hoc
resilience/guardrail/io counter dicts):

- :mod:`.registry` — a process-wide, lock-guarded
  :class:`MetricsRegistry` of labeled counters/gauges/histograms that
  serving, resilience (loop/watchdog/checkpoint), guardrails, kvstore
  and ``io`` all register into: ONE ``collect()`` snapshot covers the
  whole process under stable metric names (catalog:
  docs/observability.md).
- :mod:`.trace` — low-overhead span tracing with request-id/trace-id
  propagation across the serving scheduler thread boundary and around
  ``ResilientLoop`` / ``ShardedTrainer.step``; bounded ring buffer,
  per-request timeline dump with explicit parents and self time,
  zero-cost when disabled (one global + ``None`` check — the FaultPlan
  pattern); ``host_range`` puts a host phase into any ``jax.profiler``
  capture, tracer or no tracer.
- :mod:`.compiles` — what ``jax.monitoring`` tells of getting programs:
  a process-wide count of XLA backend compiles
  (``mxtpu_xla_compiles_total``) with the seconds of tracing, lowering
  and compiling and the persistent cache's hits and misses, what the
  calling thread's own calls compiled, and a bounded log of each event
  with its instant (``log()``), always on.
- :mod:`.stalls` — the stalled step seen from inside: every thread's
  open phase in a slot (from ``host_range``), a running median a phase,
  one witness thread that keeps its own lateness and watches an overdue
  phase, and a bounded log of stall records with a verdict each
  (``log()``), always on once a ``ShardedTrainer`` was built.
- :mod:`.export` — Prometheus text-format and JSON-lines exporters plus
  a :class:`BackgroundExporter` thread with graceful drain (wired into
  ``InferenceEngine.stop()`` and SIGTERM handling).
- :mod:`.flightrecorder` — failure-time forensics: an always-on,
  bounded lifecycle-event ring (engine submit/shed/preempt/crash,
  watchdog trips, loop rewinds/quarantines, fleet deaths/gray
  ejections/brownouts, page faults, NaN scrubs) that on a trigger —
  watchdog trip, condemnation, NaN burst, replica death, SIGTERM, SLO
  breach, explicit ``dump()`` — atomically writes a debug **bundle**:
  last-N events, implicated span timelines, registry snapshot, every
  live engine's ``stats()``, the active fault plan, lock-witness
  graph, and versions.  ``tools/obs_bundle.py`` renders one.
- :mod:`.slo` — declared objectives (:class:`SLO`) evaluated at scrape
  time from the existing histograms/counters by :class:`SLOTracker`,
  exporting ``mxtpu_slo_*`` burn-rate/budget gauges; a breach is a
  flight-recorder trigger.

Quick start::

    from mxnet_tpu import observability as obs

    tracer = obs.enable_tracing()               # span recording on
    reg = obs.default_registry()
    exp = obs.BackgroundExporter(path="metrics.prom", interval=5.0)
    with InferenceEngine(net).attach_exporter(exp) as eng:
        fut = eng.submit(prompt)
        out = fut.result()
        print(obs.to_prometheus(reg.collect()))
        print(tracer.timeline(fut.trace_id))    # submit→…→complete
"""
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       default_registry)
from .trace import (Span, Tracer, active as active_tracer,
                    disable as disable_tracing, enable as enable_tracing,
                    host_range)
from .export import (BackgroundExporter, flatten, parse_prometheus,
                     to_json_lines, to_prometheus)
from .flightrecorder import (FlightRecorder,
                             active as active_flight_recorder,
                             disable as disable_flight_recorder,
                             enable as enable_flight_recorder)
from .slo import SLO, SLOTracker

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry",
    "Span", "Tracer", "enable_tracing", "disable_tracing",
    "active_tracer", "host_range",
    "BackgroundExporter", "to_prometheus", "to_json_lines",
    "parse_prometheus", "flatten",
    "FlightRecorder", "enable_flight_recorder",
    "disable_flight_recorder", "active_flight_recorder",
    "SLO", "SLOTracker",
]
