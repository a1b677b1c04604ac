"""Batch occupancy of the decode program: tokens generated per decode
step, from the engine's own counters over the whole traffic period."""
NAME = "scheduler.tokens_per_decode_step"


def read(run):
    c = run.get("counters") or {}
    if not c.get("decode_steps"):
        return None
    return c["tokens_generated"] / c["decode_steps"]
