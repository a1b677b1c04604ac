"""Share of the window the trainer spent waiting for its next batch
(``DevicePrefetcher.stats()['input_wait_seconds_total']`` delta)."""
NAME = "input.wait_pct"


def read(run):
    if "input_wait_s" not in run:
        return None
    w0, w1 = run["window"]
    return 100.0 * run["input_wait_s"] / (w1 - w0)
