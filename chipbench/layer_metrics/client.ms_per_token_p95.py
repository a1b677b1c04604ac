"""The tail of the client's completion latency per output token, 95th
percentile over requests due in the window (failed or unfinished: worse
than any).  Recorded, not judged: with some tens of requests in a window
it is the second or third largest sample (PERF.md section 2)."""
NAME = "client.ms_per_token_p95"


def read(run):
    return run.get("client", {}).get("ms_per_token_p95")
