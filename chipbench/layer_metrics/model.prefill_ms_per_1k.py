"""Device time of the prefill and chunk programs per thousand prompt
tokens admitted in the traced window (padding is the program's cost, so
it is in the time and not in the tokens)."""
NAME = "model.prefill_ms_per_1k"


def read(run):
    tokens = (run.get("trace_counters") or {}).get("prompt_tokens")
    if not tokens:
        return None
    total = sum(d for _n, launcher, _s, d in run["trace"]["modules"]
                if launcher and launcher.split(":")[-1]
                in ("prefill", "chunk"))
    return 1e3 * total / (tokens / 1e3) if total else None
