"""Requests parked for want of KV pages, per 100 completed."""
NAME = "kv.preemptions_per_100req"


def read(run):
    c = run.get("counters") or {}
    if not c.get("completed"):
        return None
    return 100.0 * c["preemptions"] / c["completed"]
