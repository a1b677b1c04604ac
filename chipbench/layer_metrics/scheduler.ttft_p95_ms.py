"""Time to first token as the scheduler saw it: the end of each request's
``serving.prefill_phase`` span (the program's Tracer, ``time.monotonic``)
minus the instant the request was DUE on the client's clock; 95th
percentile over traced requests due in the window.  Holes in the span
ring (``dropped`` > 0) void the reading."""
NAME = "scheduler.ttft_p95_ms"


def read(run):
    from chipbench.harness.stats import percentile

    if run.get("spans_dropped") or not run.get("spans"):
        return None
    first = {s.trace_id: s.t1 for s in run["spans"]
             if s.name == "serving.prefill_phase"}
    w0, w1 = run["window"]
    ttft = [1e3 * (first[r.trace_id] - r.t_due) for r in run["recs"]
            if w0 <= r.t_due < w1 and r.trace_id in first]
    return percentile(ttft, 95) if ttft else None
