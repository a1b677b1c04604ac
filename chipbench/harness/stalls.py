"""What stalled inside a run's window, from what the program kept of itself.

``mxnet_tpu.observability.stalls`` keeps, with nothing switched on, one
record for each host phase that stayed open for more than twice its
running median and 0.15 s more (``log()``: ``phase``, ``thread``,
``start``, ``seconds``, ``expected_s``, ``verdict`` ... with ``start`` on
``time.monotonic()``), and for each second its witness thread ran how late
its latest wake was (``lateness()``: ``(instant, seconds)``).  The
driver's window is on the same clock, so the three ``trainer.*`` readers
under ``layer_metrics/`` clip both to ``run["window"]`` and read nothing
of the driver's own clocks.

A record is the window's if it STARTED inside it, and on a phase the
window's own thread waits in (the read of the loss, the wait for a batch,
the trainer's host phases): the feeder's ``input.pull`` that made the
step wait is the same stall seen from the other thread, and is not
counted twice.  What a record took over its median is clipped at the
window's end.  A program that keeps no such log (the commits before it
did) or whose bounded log has lost records gives None, and the metric is
left out of the line.
"""
from __future__ import annotations

# the phases the thread that drives a training window waits in
WINDOW_PHASES = ("ndarray.readback", "input.next")
WINDOW_PREFIX = "trainer."


def of_the_window_thread(phase: str) -> bool:
    return phase in WINDOW_PHASES or phase.startswith(WINDOW_PREFIX)


def program_log():
    """``(records, lateness)`` as the program kept them, or None where it
    keeps none or has lost some."""
    try:
        from mxnet_tpu.observability import stalls
        records, late = stalls.log(), stalls.lateness()
        lost = stalls.dropped()
    except (ImportError, AttributeError):
        return None
    if lost:
        return None
    return records, late


def in_window(run) -> list | None:
    """``(record, seconds over expected inside the window)`` for each
    stall of the window's own thread."""
    log = program_log()
    if log is None:
        return None
    w0, w1 = run["window"]
    out = []
    for rec in log[0]:
        if not of_the_window_thread(rec["phase"]) or \
                not w0 <= rec["start"] < w1:
            continue
        due = rec["start"] + (rec["expected_s"] or 0.0)
        end = min(rec["start"] + rec["seconds"], w1)
        out.append((rec, max(end - due, 0.0)))
    return out


def late_in_window(run) -> float | None:
    """The witness's largest lateness inside the window, seconds; None
    where no witness ran in it."""
    log = program_log()
    if log is None:
        return None
    w0, w1 = run["window"]
    inside = [late for at, late in log[1] if w0 <= at <= w1]
    return max(inside) if inside else None
