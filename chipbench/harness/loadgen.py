"""Offer load to an engine-shaped target on the host's clock.

Open loop: requests are submitted at their due instants whatever the
target does, each is timed from when it was DUE (so a stall costs every
later request its wait), and how late the generator itself ran is
reported.  Closed loop: a fixed number of clients, each sending its next
request when the last returned.  One thread drives either.

The target's ``submit(tokens, max_new_tokens=)`` returns a future with
``done()``, ``result(timeout)`` and ``t_done`` (``time.monotonic()`` at
completion).  A submit that raises, a result that raises and a request
still unfinished when the drain ends are failures.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional


UNFINISHED = "unfinished when the drain ended"


class Rec:
    """What the client saw of one request."""

    __slots__ = ("idx", "t_due", "t_submit", "t_done", "prompt_len",
                 "new_tokens", "result", "error", "future", "trace_id")

    def __init__(self, idx, t_due, prompt_len, new_tokens):
        self.idx = idx
        self.t_due = t_due
        self.t_submit = None
        self.t_done = None
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens
        self.result = None
        self.error: Optional[str] = None
        self.future = None
        self.trace_id = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.t_done is not None


def _send(submit, rec: Rec, req: dict):
    rec.t_submit = time.monotonic()
    try:
        rec.future = submit(req["tokens"], req["new_tokens"])
        rec.trace_id = getattr(rec.future, "trace_id", None)
    except Exception as e:                # a refusal is a failed request
        rec.error = f"{type(e).__name__}: {e}"[:200]
        rec.t_done = None


def _collect(rec: Rec):
    """Read a finished future into its record."""
    fut = rec.future
    try:
        rec.result = fut.result(timeout=0)
        rec.t_done = fut.t_done
    except Exception as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]
    rec.future = None


def _drain(recs, drain_s: float):
    """Wait up to ``drain_s`` in all for the requests still in flight."""
    deadline = time.monotonic() + drain_s
    for rec in recs:
        if rec.future is None:
            continue
        try:
            rec.future.result(timeout=max(deadline - time.monotonic(), 0.0))
        except Exception:          # read below, as a result or an error
            pass
        if rec.future.done():
            _collect(rec)
        else:
            rec.error = UNFINISHED
            rec.future = None


def open_loop(submit: Callable, requests: List[dict], t0: float,
              drain_s: float) -> List[Rec]:
    """Submit ``requests`` (each with ``t`` seconds after ``t0``) at their
    due instants, then wait up to ``drain_s`` for what is in flight."""
    recs = []
    for i, req in enumerate(requests):
        due = t0 + req["t"]
        while True:
            wait = due - time.monotonic()
            if wait <= 0:
                break
            time.sleep(min(wait, 0.05))
        rec = Rec(i, due, len(req["tokens"]), req["new_tokens"])
        _send(submit, rec, req)
        recs.append(rec)
    _drain(recs, drain_s)
    return recs


def closed_loop(submit: Callable, requests: List[dict], clients: int,
                t_end: Callable[[], float], drain_s: float,
                poll_s: float = 0.002) -> List[Rec]:
    """``clients`` callers, each sending the next request of the shared
    list when its last returns, until ``t_end()`` has passed (a callable,
    so the caller may fix the end once warm); then wait up to ``drain_s``
    for the requests in flight.  A request's due instant is the moment
    its client became free."""
    recs: List[Rec] = []
    live: List[Optional[Rec]] = [None] * clients
    nxt = 0

    def start(slot, now):
        nonlocal nxt
        live[slot] = None
        if nxt >= len(requests):
            return
        req = requests[nxt]
        rec = Rec(nxt, now, len(req["tokens"]), req["new_tokens"])
        nxt += 1
        _send(submit, rec, req)
        recs.append(rec)
        live[slot] = rec if rec.future is not None else None

    now = time.monotonic()
    for c in range(clients):
        start(c, now)
    while time.monotonic() < t_end():
        for c in range(clients):
            rec = live[c]
            if rec is None:
                start(c, time.monotonic())
            elif rec.future.done():
                _collect(rec)
                start(c, rec.t_done or time.monotonic())
        time.sleep(poll_s)
    _drain([rec for rec in live if rec is not None], drain_s)
    return recs
